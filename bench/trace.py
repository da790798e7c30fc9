"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

The traced run records the device only (the host tracer would log every
chunk of the runtime's host transposes, hundreds of MB a window, and
slow the host it measures). The harness brackets the window with two
tiny marker programs (``MARK``) and keeps its own host spans on the host
clock; the markers give both the window on the trace's clock and the
offset that puts the host spans on it.

A trace is read into plain data first (``load``): planes by name, each a
dict of lines, each a list of ``Event``. ``reduce`` then works on that
data alone, so a test can hand it a small synthetic trace:

* the window: from the end of the first marker to the start of the last;
* device busy time: the union of the op intervals on each device's op
  line inside the window, averaged over the devices that ran anything;
* per-op device time and call counts, by op name;
* idle gaps: the window minus the busy union, each labelled by the
  innermost host span over its midpoint; gaps under ``SHORT_GAP_NS``
  are pooled as "short gaps".

All times inside are nanoseconds on the trace's clock; the results are
seconds.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass
from pathlib import Path

DEVICE_PREFIX = "/device:"
OP_LINES = ("XLA Ops",)
MODULE_LINE = "XLA Modules"
MARK = "bench_mark"
# the Mosaic (Pallas) call's op text; the fused pyramid+stage-0 kernel is
# the ingest programs' only one until it carries a stable name= of its own
MOSAIC = 'custom_call_target="tpu_custom_call"'
# gaps shorter than this are the device's own scheduling between ops of
# one program, not the host holding it back; they are pooled unlabelled
SHORT_GAP_NS = 20_000


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(directory) -> Path:
    paths = sorted(Path(directory).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path) -> dict:
    """{plane name: {line name: [Event]}} from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes: dict = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            evs.extend(Event(ev.name, float(ev.start_ns),
                             float(ev.duration_ns)) for ev in line.events)
    return planes


def union(intervals) -> list[tuple[float, float]]:
    out: list = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def device_lines(planes: dict) -> dict:
    """{device plane: [op events]}: the op lines of every device plane."""
    out = {}
    for name, lines in planes.items():
        if not name.startswith(DEVICE_PREFIX):
            continue
        evs = [e for ln, es in lines.items() if ln in OP_LINES for e in es]
        if evs:
            out[name] = evs
    return out


def markers(planes: dict) -> list[Event]:
    """The marker programs' runs on the device, in time order."""
    return sorted((e for name, lines in planes.items()
                   if name.startswith(DEVICE_PREFIX)
                   for e in lines.get(MODULE_LINE, ()) if MARK in e.name),
                  key=lambda e: e.start_ns)


def window_of(planes: dict) -> tuple[float, float]:
    marks = markers(planes)
    if len(marks) < 2:
        raise ValueError(f"the trace holds {len(marks)} {MARK!r} runs; "
                         f"the window needs one on each side")
    return marks[0].end_ns, marks[-1].start_ns


def host_offset_ns(planes: dict, host_open_s: float,
                   host_close_s: float) -> float:
    """Trace clock = host clock (ns) - offset. ``host_open_s``: host time
    just after the first marker finished; ``host_close_s``: just before
    the last was sent. The two dispatch latencies lean opposite ways and
    mostly cancel."""
    lo, hi = window_of(planes)
    return ((host_open_s * 1e9 - lo) + (host_close_s * 1e9 - hi)) / 2


class _Labeller:
    """Labels a moment by the innermost host span over it. Spans nest,
    so the innermost one over ``t`` is the latest-starting one that has
    not ended by ``t``."""

    SCAN = 4096

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: s[1])
        self.starts = [s[1] for s in self.spans]

    def __call__(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        stop = max(i - self.SCAN, -1)
        while i > stop:
            name, _, end = self.spans[i]
            if end > t:
                return name
            i -= 1
        return "no span"


def reduce(planes: dict, spans=()) -> dict:
    """Window, busy, per-op and idle-gap figures of one traced window.
    ``spans``: (name, start_ns, end_ns) host spans on the trace clock."""
    lo, hi = window_of(planes)
    devices = device_lines(planes)
    busy_per_dev = []
    op_s: dict = {}
    op_n: dict = {}
    gaps: dict = {}
    label = _Labeller(spans)
    for evs in devices.values():
        inside = []
        for e in evs:
            a, b = _clip(e.start_ns, e.end_ns, lo, hi)
            if b <= a:
                continue
            inside.append((a, b))
            op_s[e.name] = op_s.get(e.name, 0.0) + (b - a) * 1e-9
            op_n[e.name] = op_n.get(e.name, 0) + 1
        busy = union(inside)
        if not busy:
            continue
        busy_per_dev.append(sum(b - a for a, b in busy) * 1e-9)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            name = (label((a + b) / 2) if b - a >= SHORT_GAP_NS
                    else "short gaps")
            gaps[name] = gaps.get(name, 0.0) + (b - a) * 1e-9
    n_dev = max(len(busy_per_dev), 1)
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(busy_per_dev) / n_dev,
            "devices": len(busy_per_dev),
            "op_s": op_s, "op_n": op_n,
            "gaps_s": {k: v / n_dev for k, v in gaps.items()}}


def ops_matching(reduced: dict, pattern: str) -> tuple[float, int]:
    """Device seconds and calls of the ops whose name (on a TPU, the
    op's HLO text) holds ``pattern``."""
    secs, calls = 0.0, 0
    for name, s in reduced["op_s"].items():
        if pattern in name:
            secs += s
            calls += reduced["op_n"][name]
    return secs, calls


def breakdown(reduced: dict, top: int = 10) -> dict:
    ops = sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(reduced["gaps_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
