"""The program's spans over a ``vlm_ingest_stream`` window, chosen as
``bench/program_spans.window_spans`` chooses them for ``ingest_stream``
(which reads that kind alone): the last ``ingest.chunk`` spans, as many
as the window's feeds cut into chunks, with every span under them. None
for another kind, or a program that kept too few chunks."""
from __future__ import annotations

from bench import program_spans

KIND = "vlm_ingest_stream"


def window_spans(record) -> list | None:
    if record.get("kind") != KIND:
        return None
    return program_spans.window_spans(dict(record, kind="ingest_stream"))


def self_pct(record, name: str) -> float | None:
    """Self time (%) of the spans called ``name`` over the window, or
    None where the window holds none of them."""
    spans = window_spans(record)
    if spans is None:
        return None
    secs = program_spans.self_seconds(spans)
    if name not in secs:
        return None
    return 100.0 * secs[name] / record["run"]["window_s"]
