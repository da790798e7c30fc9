"""The readers of the ingest pipeline's own spans, and the trace's gap
labels under program spans nested in the harness's."""
import sys

import pytest

from bench import harness, program_spans
from bench import trace as tr
from bench.tests.test_bench_trace import MS, SPANS, planes
from bench.tests.tiny import tiny_catalog
from repro import tracing
from repro.tracing import Span

CELL = "ingest-distinct"
STEPS = ("detect", "gather", "transfer", "dispatch", "wait", "grade")
METRICS = [f"ingest_{s}_pct" for s in STEPS]


def chunk(first_id, t0, compile_s=0.0):
    """One 10 ms chunk: detect 1 ms, gather 1 ms, transfer 1 ms, one
    dispatch 1 ms (holding a compile of ``compile_s``), the anchor's wait
    1 ms, one head's wait 3 ms, grade 1 ms, and 1 ms of the chunk's own
    between them."""
    c = first_id
    out = [Span("ingest.chunk", t0, t0 + 0.010, c, None)]
    steps = [("ingest.detect", 0, 1), ("ingest.gather", 1, 2),
             ("ingest.transfer", 2, 3), ("ingest.dispatch", 3, 4),
             ("ingest.anchor_wait", 4, 5), ("ingest.wait", 5, 8),
             ("ingest.grade", 8, 9)]
    for i, (name, a, b) in enumerate(steps, start=1):
        out.append(Span(name, t0 + a * 1e-3, t0 + b * 1e-3, c + i, c))
    if compile_s:
        out.append(Span(tracing.COMPILE, t0 + 0.004 - compile_s, t0 + 0.004,
                        c + 10, c + 4))
    return out


def record(window_s=0.020, frames=128):
    return {"kind": "ingest_stream", "traffic": {"feed_rows": 64},
            "run": {"window_s": window_s, "frames": frames, "chunk": 64}}


@pytest.fixture
def synthetic(monkeypatch):
    """A warm-up chunk that compiled, then two window chunks of 64
    frames, as the program's recorder would hold them."""
    spans = (chunk(1, 0.0, compile_s=0.0005) + chunk(20, 1.0)
             + chunk(40, 1.010))
    monkeypatch.setattr(tracing, "spans", lambda: list(spans))


@pytest.mark.parametrize("metric,want", [
    ("ingest_detect_pct", 10.0), ("ingest_gather_pct", 10.0),
    ("ingest_transfer_pct", 20.0), ("ingest_dispatch_pct", 10.0),
    ("ingest_wait_pct", 30.0), ("ingest_grade_pct", 10.0)])
def test_readers_take_self_time_over_the_window(synthetic, metric, want):
    cat = harness.Catalog(harness.Path(__file__).resolve().parents[2])
    assert cat.reader(metric)(record()) == pytest.approx(want)


def test_window_holds_only_the_last_chunks(synthetic):
    spans = program_spans.window_spans(record())
    assert {s.span_id for s in spans} == set(range(20, 28)) | set(
        range(40, 48))
    secs = program_spans.self_seconds(spans)
    assert secs["ingest.chunk"] == pytest.approx(0.002)
    assert tracing.COMPILE not in secs


def test_compile_comes_off_its_dispatch(synthetic):
    warm = [s for s in tracing.spans() if s.span_id < 20]
    secs = program_spans.self_seconds(warm)
    assert secs[tracing.COMPILE] == pytest.approx(0.0005)
    assert secs["ingest.dispatch"] == pytest.approx(0.0005)


def test_no_spans_reads_none(monkeypatch, synthetic):
    assert program_spans.self_pct(record(frames=64 * 4), "ingest.wait") \
        is None                               # fewer chunks than fed
    monkeypatch.setattr(tracing, "spans", lambda: [])
    assert program_spans.self_pct(record(), "ingest.wait") is None
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert program_spans.self_pct(record(), "ingest.wait") is None


def test_readers_cover_the_harness_ingest_spans(tmp_path):
    """A tiny window on the CPU: the six steps and the chunks' own time
    make up nearly all of the harness's ``ingest`` spans."""
    cat = harness.Catalog(tiny_catalog(tmp_path))
    cell = cat.cell(CELL)
    traffic = cat.traffic(cell["traffic"])
    load = cat.load_kind(traffic["kind"])(cat.config(cell["config"]),
                                          traffic, 2**33 + 5)
    load.setup(0.5)
    load.window(0.5)
    rec = {"kind": traffic["kind"], "traffic": traffic, "run": load.record()}
    shares = {m: cat.reader(m)(rec) for m in METRICS}
    assert all(v is not None and v > 0 for v in shares.values()), shares
    own = program_spans.self_pct(rec, "ingest.chunk")
    fed = sum(b - a for n, a, b in load.spans.events if n == "ingest")
    covered = (sum(shares.values()) + own) / 100 * rec["run"]["window_s"]
    assert 0.95 * fed <= covered <= fed


def test_gap_labelled_by_a_program_span_inside_a_harness_span():
    """The trace labels each idle gap by the innermost span over it, so
    program spans passed beside the harness's name the host's step."""
    steps = [("ingest.detect", MS // 5, 1 * MS),
             ("ingest.wait", 7 * MS, 10 * MS)]
    red = tr.reduce(planes(), SPANS + steps)
    assert red["gaps_s"]["ingest.detect"] == pytest.approx(0.001)
    assert red["gaps_s"]["ingest.wait"] == pytest.approx(0.003)
    assert red["gaps_s"]["transfer"] == pytest.approx(0.002)
    assert "scan_pass" not in red["gaps_s"]
