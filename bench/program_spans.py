"""The program's own spans (``repro.tracing``) over a cell's window, for
the readers of the ingest pipeline's steps.

The program keeps its spans in memory in the benchmark's process, from
set-up on. A reader runs in that process after the window and takes the
window's part: the last ``ingest.chunk`` spans, as many as the window's
feeds cut into chunks, with every span under them. A program without
``repro.tracing``, or one that kept too few chunks, gives None.

A step's share is its spans' self time (each span's length less the
union of its children's) over the window's length, on the host clock.
"""
from __future__ import annotations

CHUNK = "ingest.chunk"


def recorded() -> list:
    try:
        from repro import tracing
    except ImportError:
        return []
    return tracing.spans()


def window_spans(record) -> list | None:
    """The spans of the chunks the window fed, oldest first, or None."""
    if record["kind"] != "ingest_stream":
        return None
    run = record["run"]
    feed = int(record["traffic"]["feed_rows"])
    n = run["frames"] // feed * -(-feed // run["chunk"])
    spans = recorded()
    chunks = sorted((s for s in spans if s.name == CHUNK),
                    key=lambda s: s.start_s)[-n:]
    if n == 0 or len(chunks) < n:
        return None
    kids: dict = {}
    for s in spans:
        kids.setdefault(s.parent_id, []).append(s)
    out, todo = [], list(chunks)
    while todo:
        s = todo.pop()
        out.append(s)
        todo.extend(kids.get(s.span_id, ()))
    return sorted(out, key=lambda s: s.start_s)


def self_seconds(spans) -> dict:
    """{span name: summed self time in seconds}."""
    kids: dict = {}
    for s in spans:
        kids.setdefault(s.parent_id, []).append(s)
    out: dict = {}
    for s in spans:
        covered, edge = 0.0, s.start_s
        for k in sorted(kids.get(s.span_id, ()), key=lambda k: k.start_s):
            a, b = max(k.start_s, edge), min(k.end_s, s.end_s)
            if b > a:
                covered += b - a
                edge = b
        out[s.name] = out.get(s.name, 0.0) + (s.end_s - s.start_s - covered)
    return out


def self_pct(record, *names: str) -> float | None:
    """Self time (%) of the spans named any of ``names`` over the window."""
    spans = window_spans(record)
    if spans is None:
        return None
    secs = self_seconds(spans)
    return 100.0 * sum(secs.get(n, 0.0) for n in names) \
        / record["run"]["window_s"]
