"""In-program spans (repro/tracing.py) on the ingest path.

The spans change nothing the program computes: the index built with the
recorder swapped for a no-op is bit-identical to the one built while it
records. Every chunk is one ``ingest.chunk`` span over exactly one
detect, gather, transfer, anchor wait and grade step, one dispatch per
predicate and one wait per head, and the only compiles are under the
first chunk's dispatches.
"""
from contextlib import nullcontext

import numpy as np
import pytest

from repro import tracing
from repro.engine.ingest import IngestPipeline
from test_query_engine import _toy_cascade, _uint8_images

N_PRED = 10
CHUNK = 64
ROWS = 2 * CHUNK + 20          # two whole chunks and a short tail
STEPS = ("ingest.detect", "ingest.gather", "ingest.transfer",
         "ingest.anchor_wait", "ingest.grade")


@pytest.fixture(scope="module")
def cascades():
    return [_toy_cascade(f"c{k}", k + 1) for k in range(N_PRED)]


@pytest.fixture(scope="module")
def frames():
    return _uint8_images(ROWS, 32, seed=3)


@pytest.fixture
def tracing_on():
    tracing.reset()
    yield
    tracing.reset()


def ingest(cascades, frames):
    pipe = IngestPipeline(cascades, len(frames), chunk=CHUNK, skip=False)
    pipe.ingest(frames, np.arange(len(frames)))
    return pipe


def index_arrays(pipe):
    idx = pipe.index
    out = {"alias": idx.alias, "indexed": idx.indexed}
    for c in pipe.cascades:
        out[f"score_{c.concept}"] = idx.scores[c.concept]
        out[f"cand_{c.concept}"] = idx.candidates[c.concept]
        out[f"dec_{c.concept}"] = idx.decided.column(c.key)
    return out


def children(spans):
    kids = {}
    for s in spans:
        kids.setdefault(s.parent_id, []).append(s)
    return kids


def chunk_trees(spans):
    """[(chunk span, [its child spans])] in time order."""
    kids = children(spans)
    chunks = sorted((s for s in spans if s.name == "ingest.chunk"),
                    key=lambda s: s.start_s)
    return [(c, kids.get(c.span_id, [])) for c in chunks]


def test_spans_nest_on_one_thread(tracing_on):
    with tracing.span("outer"):
        with tracing.span("inner"):
            pass
        with tracing.span("inner"):
            pass
    inner_a, inner_b, outer = tracing.spans()
    assert outer.name == "outer" and outer.parent_id is None
    assert inner_a.parent_id == inner_b.parent_id == outer.span_id
    assert outer.start_s <= inner_a.start_s <= inner_a.end_s \
        <= inner_b.start_s <= inner_b.end_s <= outer.end_s
    tracing.reset()
    assert tracing.spans() == []


def test_off_records_nothing_and_builds_the_same_index(cascades, frames,
                                                        tracing_on,
                                                        monkeypatch):
    on = index_arrays(ingest(cascades, frames))
    assert tracing.spans()
    tracing.reset()
    monkeypatch.setattr(tracing, "span", lambda name: nullcontext())
    off = index_arrays(ingest(cascades, frames))
    assert {s.name for s in tracing.spans()} <= {tracing.COMPILE}
    assert on.keys() == off.keys()
    for k in on:
        assert on[k].dtype == off[k].dtype
        assert on[k].tobytes() == off[k].tobytes(), k


def test_each_chunk_is_one_tree_of_steps(cascades, frames, tracing_on):
    ingest(cascades, frames)
    trees = chunk_trees(tracing.spans())
    assert len(trees) == -(-ROWS // CHUNK)
    for chunk, kids in trees:
        assert chunk.parent_id is None
        steps = [s.name for s in kids if s.name != tracing.COMPILE]
        for name in STEPS:
            assert steps.count(name) == 1, (name, steps)
        assert steps.count("ingest.dispatch") == N_PRED
        assert steps.count("ingest.wait") == N_PRED - 1
        assert len(steps) == len(STEPS) + 2 * N_PRED - 1


def test_child_spans_fit_inside_their_chunk(cascades, frames, tracing_on):
    ingest(cascades, frames)
    for chunk, kids in chunk_trees(tracing.spans()):
        kids = sorted(kids, key=lambda s: s.start_s)
        for a, b in zip(kids, kids[1:]):
            assert a.end_s <= b.start_s          # steps do not overlap
        assert all(chunk.start_s <= s.start_s and s.end_s <= chunk.end_s
                   for s in kids)
        own = sum(s.end_s - s.start_s for s in kids)
        assert own <= chunk.end_s - chunk.start_s


def test_counters_match_what_was_fed(cascades, frames):
    pipe = ingest(cascades, frames)
    st = pipe.stats
    chunks = -(-ROWS // CHUNK)
    assert st.frames == st.refs == ROWS and st.skipped == 0
    assert st.chunks == chunks
    assert st.stage0_scores == ROWS * N_PRED


def test_compiles_show_under_the_first_chunks_dispatches(cascades, frames,
                                                         tracing_on):
    before = tracing.COMPILES.programs
    ingest(cascades, frames)
    spans = tracing.spans()
    by_id = {s.span_id: s for s in spans}
    compiles = [s for s in spans if s.name == tracing.COMPILE]
    assert compiles
    assert tracing.COMPILES.programs - before >= len(compiles)
    first = chunk_trees(spans)[0][0]
    for s in compiles:
        parent = by_id[s.parent_id]
        assert parent.name == "ingest.dispatch"
        assert parent.parent_id == first.span_id
        assert parent.start_s <= s.start_s <= s.end_s <= parent.end_s
