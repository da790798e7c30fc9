"""Streaming ingest-time indexing (engine/ingest.py, DESIGN.md §14) and
the bugfix sweep it rides on: the skip detector must alias exactly the
held scene repeats, exact-mode indexed queries must stay bit-identical
to cold ScanEngine / naive_scan across shard counts and detector
settings (the differential oracle), ingest-decided rows must answer at
query time with ZERO model invocations (engine stats + service
store_hits), persistence round-trips (VirtualColumnStore,
RepresentationCache, CandidateIndex) must be bit-identical and refuse a
different corpus, and the OnlineReorderer's conditional-vs-marginal
selectivity bias must be provably FIXED (the legacy estimator flips an
ordering the corrected one gets right)."""
import numpy as np
import pytest

from repro.core.pipeline import build_ingest_pipeline
from repro.data.synthetic import DEFAULT_PREDICATES, make_camera_stream
from repro.engine.ingest import (CandidateIndex, IngestPipeline,
                                 frame_signature, indexed_execute)
from repro.engine.planner import (OnlineReorderer, PhysicalPlan,
                                  PlannedPredicate, expected_scan_cost)
from repro.engine.scan import ScanEngine, VirtualColumnStore, naive_scan
from repro.engine.sharded import ShardedScanEngine
from repro.serve.repcache import RepresentationCache, corpus_token
from test_query_engine import _toy_cascade, _uint8_images

SPECS = DEFAULT_PREDICATES[:3]


@pytest.fixture(scope="module")
def stream():
    """Small camera stream + toy cascades + a built index (module-scoped:
    the ingest pass and the cascades' jit cache are shared)."""
    frames, labels, scene = make_camera_stream(SPECS, 240, hw=32, seed=0)
    cascades = [_toy_cascade(c, s) for c, s in
                [("a", 1), ("b", 2), ("c", 3)]]
    pipe = IngestPipeline(cascades, len(frames), chunk=64, skip=True)
    pipe.run(frames)
    return frames, labels, scene, cascades, pipe


# ---------------------------------------------------------- skip detect ---
def test_skip_detector_aliases_exactly_the_scene_repeats(stream):
    frames, _, scene, _, pipe = stream
    idx = pipe.index
    self_ref = idx.alias == np.arange(len(frames))
    # one reference per scene, every held repeat aliased to it
    assert int(self_ref.sum()) == scene.max() + 1
    assert pipe.stats.skipped == len(frames) - (scene.max() + 1)
    # an alias NEVER crosses a scene boundary (the jitter-vs-scene-change
    # separation margin the corpus is constructed with)
    assert np.array_equal(scene[idx.alias], scene)
    # only references were scored
    assert pipe.stats.refs == int(self_ref.sum())
    assert pipe.stats.stage0_scores == pipe.stats.refs * 3


def test_detector_margin_separates_jitter_from_scene_changes(stream):
    frames, _, scene, _, pipe = stream
    sigs = frame_signature(frames, pipe.skip_res)
    diffs = np.abs(sigs[1:] - sigs[:-1]).mean(axis=(1, 2))
    same = scene[1:] == scene[:-1]
    assert diffs[same].max() < pipe.skip_threshold          # jitter below
    assert diffs[~same].min() > 2 * pipe.skip_threshold     # changes above


def test_autocalibrated_threshold_lands_in_the_margin(stream):
    """skip_threshold=None LEARNS the per-camera threshold from the
    warmup window: the learned value must land strictly between the
    jitter and scene-change diff clusters (the same margin the pinned
    default is tested for above), no frame may be skipped before
    calibration completes, and the alias invariants survive."""
    frames, _, scene, cascades, _ = stream
    auto = IngestPipeline(cascades, len(frames), chunk=64, skip=True,
                          skip_threshold=None)
    assert auto.skip_threshold is None            # nothing learned yet
    auto.run(frames)
    thr = auto.skip_threshold
    assert thr is not None
    sigs = frame_signature(frames, auto.skip_res)
    diffs = np.abs(sigs[1:] - sigs[:-1]).mean(axis=(1, 2))
    same = scene[1:] == scene[:-1]
    assert diffs[same].max() < thr < diffs[~same].min()
    # calibration holds skipping off: every warmup frame is a reference
    calib = auto.calib_frames
    assert np.array_equal(auto.index.alias[:calib], np.arange(calib))
    # skipping resumed afterwards, and aliases never cross a scene
    assert auto.stats.skipped > 0
    assert np.array_equal(scene[auto.index.alias], scene)


def test_calibrate_threshold_unit():
    lo = 1e-3 * np.linspace(0.5, 1.5, 20)         # jitter cluster
    hi = 0.2 * np.linspace(0.8, 1.2, 6)           # scene changes
    thr = IngestPipeline.calibrate_threshold(np.concatenate([hi, lo]))
    assert lo.max() < thr < hi.min()
    # the threshold is the geometric mean of the largest-gap endpoints
    assert thr == pytest.approx(np.sqrt(lo.max() * hi.min()))
    # non-positive diffs (chain starts) are ignored
    assert IngestPipeline.calibrate_threshold(
        np.concatenate([[0.0, 0.0], hi, lo])) == pytest.approx(thr)
    # too few samples, or no clear multiplicative gap: pinned fallback
    assert IngestPipeline.calibrate_threshold([1e-3] * 5) == 0.008
    assert IngestPipeline.calibrate_threshold(
        np.linspace(0.01, 0.02, 30)) == 0.008


def test_ingest_factory_passes_calibration_knobs(stream):
    frames, _, _, cascades, _ = stream
    pipe = build_ingest_pipeline(cascades, len(frames), chunk=32,
                                 skip_threshold=None, calib_frames=24)
    assert pipe.skip_threshold is None
    assert pipe.calib_frames == 24


def test_streaming_granularity_invariant(stream):
    """Feeding the stream in ragged batches (the detector chains across
    ingest() calls) builds the identical index to one full run()."""
    frames, _, _, cascades, pipe = stream
    ragged = IngestPipeline(cascades, len(frames), chunk=64, skip=True)
    ids = np.arange(len(frames))
    for lo, hi in [(0, 7), (7, 64), (64, 65), (65, 200), (200, len(frames))]:
        ragged.ingest(frames[lo:hi], ids[lo:hi])
    assert np.array_equal(ragged.index.alias, pipe.index.alias)
    for c in ragged.index.concepts:
        assert np.array_equal(ragged.index.candidates[c],
                              pipe.index.candidates[c])
    for k in pipe.index.decided.keys():
        assert np.array_equal(ragged.index.decided.column(k),
                              pipe.index.decided.column(k))


def _reference_signature(frames, res):
    """The detector signature as plainly written: channel mean, then the
    box mean over a res x res grid of the cropped frame."""
    frames = np.asarray(frames, np.float32)
    b, hw = frames.shape[0], frames.shape[1]
    res = min(res, hw)
    k = hw // res
    gray = frames[:, : res * k, : res * k].mean(axis=3)
    return gray.reshape(b, res, k, res, k).mean(axis=(2, 4))


@pytest.mark.parametrize("shape,res,view", [
    ((64, 240, 240, 3), 8, None),         # the benchmark cell's chunk
    ((4, 250, 250, 3), 8, None),          # hw % res != 0: cropped
    ((4, 4, 4, 3), 8, None),              # res > hw
    ((4, 32, 32, 1), 8, None),            # single channel
    ((8, 67, 67, 4), 8, np.s_[::2, 1::2, 1::2, :3]),   # strided view
    ((1, 96, 96, 3), 8, None),            # batch of one
])
def test_frame_signature_matches_reference(shape, res, view):
    frames = np.random.default_rng(7).random(shape, dtype=np.float32)
    if view is not None:
        frames = frames[view]
        assert not frames.flags.c_contiguous
    sig = frame_signature(frames, res)
    want = _reference_signature(frames, res)
    assert sig.dtype == np.float32 and sig.shape == want.shape
    np.testing.assert_allclose(sig, want, rtol=0, atol=1e-6)


def _reference_aliases(frames, threshold, calib_frames):
    """Sequential skip decisions from the reference signatures: a frame
    whose diff to the previous frame is at most the threshold aliases
    the last reference; with ``threshold=None`` nothing is skipped until
    the first ``calib_frames`` diffs have set it."""
    sigs = _reference_signature(frames, 8)
    diffs = np.abs(sigs[1:] - sigs[:-1]).mean(axis=(1, 2))
    live_from = 1
    if threshold is None:
        threshold = IngestPipeline.calibrate_threshold(diffs[:calib_frames])
        live_from = calib_frames
    alias = np.zeros(len(frames), np.int64)
    ref = 0
    for i in range(1, len(frames)):
        if i >= live_from and diffs[i - 1] <= threshold:
            alias[i] = ref
        else:
            alias[i] = ref = i
    return alias


@pytest.mark.parametrize("threshold", [0.008, None])
def test_skip_decisions_match_reference_for_any_feed_size(stream,
                                                          threshold):
    """Feeding the stream 64, 7 or 33 frames a call gives the same
    aliases, skips and references, equal to the reference's; with a
    learned threshold, calibration ends inside a chunk."""
    frames, _, _, cascades, _ = stream
    ids = np.arange(len(frames))
    want = _reference_aliases(frames, threshold, 48)
    n_refs = int((want == ids).sum())
    assert 0 < n_refs < len(frames)
    for feed in (64, 7, 33):
        pipe = IngestPipeline(cascades, len(frames), chunk=64, skip=True,
                              skip_threshold=threshold, calib_frames=48)
        for lo in range(0, len(frames), feed):
            pipe.ingest(frames[lo:lo + feed], ids[lo:lo + feed])
        assert np.array_equal(pipe.index.alias, want)
        assert pipe.stats.refs == n_refs
        assert pipe.stats.skipped == len(frames) - n_refs


# --------------------------------------------------- differential oracle --
def _cold_rows(frames, cascades):
    return ScanEngine(frames, chunk=32).execute(cascades).indices


@pytest.mark.parametrize("shards", [0, 8])
def test_exact_mode_bit_identical_oracle(stream, shards):
    """THE exactness gate: exact-mode indexed row sets == cold ScanEngine
    == naive_scan, serial and sharded."""
    frames, _, _, cascades, pipe = stream
    cold = _cold_rows(frames, cascades)
    assert np.array_equal(cold, naive_scan(frames, cascades, chunk=32))
    if shards:
        eng = ShardedScanEngine(frames, shards=shards, chunk=32)
    else:
        eng = ScanEngine(frames, chunk=32)
    pipe.index.seed_store(eng.store, exact=True)
    surv = pipe.index.survivors(np.arange(len(frames)), cascades,
                                exact=True)
    res = eng.execute(cascades, survivors=surv)
    assert np.array_equal(res.indices, cold)
    # and the index genuinely removed work: pruned rows plus seeded
    # stage-0 labels both cut evaluated rows vs the cold scan
    cold_res = ScanEngine(frames, chunk=32).execute(cascades)
    assert res.stats.rows_evaluated < cold_res.stats.rows_evaluated


@pytest.mark.slow
@pytest.mark.parametrize("shards", [1, 8])
@pytest.mark.parametrize("skip", [True, False])
def test_exact_mode_oracle_full_grid(shards, skip):
    """Full {shards} x {skip-detector} differential grid (slow marker:
    each cell re-ingests and re-compiles)."""
    frames, _, _ = make_camera_stream(SPECS, 150, hw=32, seed=3)
    cascades = [_toy_cascade(c, s) for c, s in [("a", 11), ("b", 12)]]
    pipe = IngestPipeline(cascades, len(frames), chunk=64, skip=skip)
    pipe.run(frames)
    cold = _cold_rows(frames, cascades)
    assert np.array_equal(cold, naive_scan(frames, cascades, chunk=32))
    eng = ShardedScanEngine(frames, shards=shards, chunk=32)
    pipe.index.seed_store(eng.store, exact=True)
    surv = pipe.index.survivors(np.arange(len(frames)), cascades,
                                exact=True)
    assert np.array_equal(eng.execute(cascades, survivors=surv).indices,
                          cold)


def test_approx_mode_prunes_at_measured_recall(stream):
    frames, labels, _, cascades, pipe = stream
    idx = pipe.index
    ids = np.arange(len(frames))
    exact_surv = idx.survivors(ids, cascades, exact=True)
    approx_surv = idx.survivors(ids, cascades, exact=False)
    assert len(approx_surv) < len(exact_surv)   # aliases + candidates prune
    eng = ScanEngine(frames, chunk=32)
    idx.seed_store(eng.store, exact=False)
    res = eng.execute(cascades, survivors=approx_surv)
    cold = _cold_rows(frames, cascades)
    hit = len(np.intersect1d(res.indices, cold))
    # the recall knob's cost is measured, not assumed: per-concept
    # measured_recall is honest about the synthetic truth...
    for k, c in enumerate(idx.concepts):
        r = idx.measured_recall(c, labels[:, k])
        assert 0.0 <= r <= 1.0
    # ...and the end-to-end conjunction keeps most of the cold rows at a
    # fraction of the work (loose floor: the toy heads are weak learners)
    assert hit / max(len(cold), 1) > 0.6
    assert res.stats.rows_evaluated < 0.5 * ScanEngine(
        frames, chunk=32).execute(cascades).stats.rows_evaluated


# ----------------------------------------------------- zero invocations ---
def test_indexed_decided_rows_invoke_zero_models(stream):
    """Rows fully decided at ingest scan with ZERO model invocations:
    no evaluated rows, no flushes, no ingest chunks."""
    frames, _, _, cascades, pipe = stream
    idx = pipe.index
    decided_all = np.ones(len(frames), bool)
    for c in cascades:
        decided_all &= idx.decided.column(c.key) >= 0
    rows = np.where(decided_all)[0]
    assert len(rows) > 4                        # scenario is non-trivial
    eng = ScanEngine(frames, chunk=32)
    idx.seed_store(eng.store, exact=True)
    res = eng.scan_rows(cascades, rows)
    assert res.stats.rows_evaluated == 0
    assert res.stats.chunks == 0
    assert all(s.batches == 0 for s in res.stats.stages)
    assert sum(s.rows_cached for s in res.stats.stages) >= len(rows)


def test_service_answers_ingest_indexed_rows_with_store_hits(stream):
    from repro.serve.batcher import Request
    from repro.serve.service import AsyncCascadeService

    frames, _, _, cascades, pipe = stream
    casc = cascades[0]
    col = pipe.index.decided.column(casc.key)
    rows = np.where(col >= 0)[0][:16]
    svc = AsyncCascadeService(frames, {"a": casc}, shards=2,
                              ingest_index=pipe.index, ingest_exact=True)
    reqs = [Request(rid=i, payload=int(r)) for i, r in enumerate(rows)]
    for r in reqs:
        svc.submit("a", r)
    # answered AT SUBMIT: store hits, no batches, labels match the index
    assert svc.stats["a"].store_hits == len(rows)
    assert svc.stats["a"].batches == 0
    assert svc.stats["a"].rows_evaluated == 0
    assert [r.result for r in reqs] == [int(v) for v in col[rows]]


# -------------------------------------------------------- planner seams ---
def test_plan_carries_index_and_explains_it(stream):
    from repro.core.selector import Selection

    frames, _, _, cascades, pipe = stream
    plan = PhysicalPlan("CAMERA", {}, [
        PlannedPredicate(c, Selection(0, 0.9, 100.0), "toy", 0.1)
        for c in cascades], index=pipe.index, index_mode="approx")
    txt = plan.explain(n_rows=len(frames))
    assert "ingest index:" in txt and "skip-aliased" in txt
    ids = np.arange(len(frames))
    assert np.array_equal(
        plan.index_prefilter(ids),
        pipe.index.survivors(ids, cascades, exact=False))
    # exact-fallback mode via indexed_execute: bit-identical to cold
    plan_exact = PhysicalPlan("CAMERA", {}, plan.predicates,
                              index=pipe.index, index_mode="exact")
    eng = ScanEngine(frames, chunk=32)
    res = indexed_execute(eng, plan_exact)
    assert np.array_equal(res.indices, _cold_rows(frames, cascades))


def test_plan_query_rejects_unknown_index_mode():
    from repro.engine.planner import QuerySpec, plan_query

    with pytest.raises(ValueError, match="index mode"):
        plan_query({}, QuerySpec(metadata_eq={}, predicates=[]),
                   index_mode="fuzzy")


def test_ingest_factory_builds_pipeline(stream):
    frames, _, _, cascades, _ = stream
    pipe = build_ingest_pipeline({c.concept: c for c in cascades},
                                 len(frames), chunk=32, skip=False)
    assert isinstance(pipe, IngestPipeline)
    assert [c.concept for c in pipe.cascades] == ["a", "b", "c"]


# ----------------------------------------------------------- persistence --
def test_virtual_column_store_roundtrip(tmp_path, stream):
    frames, _, _, cascades, pipe = stream
    token = corpus_token(frames)
    store = VirtualColumnStore(len(frames))
    pipe.index.seed_store(store, exact=True)
    p = tmp_path / "store.npz"
    store.save(p, token)
    back = VirtualColumnStore.load(p, token)
    assert back.n_rows == store.n_rows
    assert set(back.keys()) == set(store.keys())
    for k in store.keys():
        assert np.array_equal(back.column(k), store.column(k))  # bit-exact
    with pytest.raises(ValueError, match="different corpus"):
        VirtualColumnStore.load(p, corpus_token(frames[:-1]))


def test_repcache_roundtrip(tmp_path):
    imgs = _uint8_images(12, 32, seed=9)
    cache = RepresentationCache(1 << 20)
    cache.bind_corpus(corpus_token(imgs))
    rng = np.random.default_rng(0)
    for row in range(12):
        cache.put(row, 8, rng.random((8, 8, 3)).astype(np.float32))
    p = tmp_path / "repcache.npz"
    cache.save(p)
    back = RepresentationCache.load(p, corpus_token(imgs))
    assert len(back) == len(cache) and back.nbytes == cache.nbytes
    for row in range(12):
        assert np.array_equal(back.get(row, 8), cache.get(row, 8))
    with pytest.raises(ValueError, match="different corpus"):
        RepresentationCache.load(p, corpus_token(imgs[:-1]))
    # LRU order survives: the oldest entry is evicted first either way
    cache.put(99, 8, np.zeros((8, 8, 3), np.float32))
    back.put(99, 8, np.zeros((8, 8, 3), np.float32))
    assert list(cache._od) == list(back._od)


def test_candidate_index_roundtrip(tmp_path, stream):
    frames, _, _, cascades, pipe = stream
    token = corpus_token(frames)
    p = tmp_path / "index.npz"
    pipe.index.save(p, token)
    back = CandidateIndex.load(p, token)
    ids = np.arange(len(frames))
    for exact in (True, False):
        assert np.array_equal(back.survivors(ids, cascades, exact=exact),
                              pipe.index.survivors(ids, cascades,
                                                   exact=exact))
    for k in pipe.index.decided.keys():
        assert np.array_equal(back.decided.column(k),
                              pipe.index.decided.column(k))
    with pytest.raises(ValueError, match="different corpus"):
        CandidateIndex.load(p, corpus_token(frames[:-1]))


# ------------------------------------- selectivity-feedback bias (FIXED) --
def test_conditional_bias_provably_flips_ordering_legacy_vs_fixed():
    """THE regression the estimator fix is for (DESIGN.md §11.3):

    two correlated predicates, planned order [b, a]; costs equal; true
    marginals sel(b)=0.4, sel(a)=0.5, but P(a | b passes)=0.1. Stage-1
    flushes observe the CONDITIONAL 0.1. The legacy estimator adopted it
    as if marginal -> rank(a)=1/(1-0.1) beats rank(b)=1/(1-0.4) -> it
    flips to [a, b], whose true cost 1 + 0.5 = 1.5 is WORSE than the
    planned 1 + 0.4 = 1.4. The corrected estimator keeps conditional
    exposure out of refinement, so the planned (optimal) order stands."""
    b = _toy_cascade("b", 21)
    a = _toy_cascade("a", 22)
    b.cost_s, b.selectivity = 1.0, 0.4
    a.cost_s, a.selectivity = 1.0, 0.5
    true_marg = {b.key: 0.4, a.key: 0.5}
    cond_a = np.zeros(100, np.int64)
    cond_a[:10] = 1                       # P(a | b) = 0.1, n >= min_rows
    marg_b = np.zeros(100, np.int64)
    marg_b[:40] = 1                       # b's stage-0 marginal: no drift

    def run(legacy: bool):
        mon = OnlineReorderer([b, a], drift_threshold=0.1, min_rows=32)
        mon.observe(b.key, marg_b, marginal=True)
        # stage-1 flush of `a` sees only b-survivors; the legacy
        # estimator treated this as marginal exposure
        mon.observe(a.key, cond_a, marginal=legacy)
        return mon.propose([b, a])

    flipped = run(legacy=True)
    assert flipped == [1, 0]              # legacy: bias flips to [a, b]
    cost = [b.cost_s, a.cost_s]
    sels = [true_marg[b.key], true_marg[a.key]]
    assert expected_scan_cost(cost, sels, flipped) > \
        expected_scan_cost(cost, sels)    # ...which is provably worse
    assert run(legacy=False) is None      # fixed: planned order stands
    # the conditional exposure is still visible for introspection
    mon = OnlineReorderer([b, a], min_rows=32)
    mon.observe(a.key, cond_a, marginal=False)
    assert mon.conditional(a.key) == pytest.approx(0.1)
    assert mon.observed(a.key) is None


@pytest.mark.parametrize("shards", [0, 2])
def test_engines_flag_only_stage0_flushes_as_marginal(shards):
    """The engines' side of the contract: every observe() for the
    first-position cascade is marginal, every later-stage observe is
    conditional — serial and sharded (incl. the fused ingest path)."""
    class Recorder(OnlineReorderer):
        def __init__(self, cascades):
            super().__init__(cascades, drift_threshold=10.0)  # never fire
            self.seen = []

        def observe(self, key, labels, *, marginal=False):
            self.seen.append((key, marginal))
            super().observe(key, labels, marginal=marginal)

    imgs = _uint8_images(150, 32, seed=5)
    cascades = [_toy_cascade("a", 31), _toy_cascade("b", 32)]
    mon = Recorder(cascades)
    if shards:
        eng = ShardedScanEngine(imgs, shards=shards, chunk=32)
    else:
        eng = ScanEngine(imgs, chunk=32)
    eng.execute(cascades, monitor=mon)
    by_key = {c.key: {m for k, m in mon.seen if k == c.key}
              for c in cascades}
    assert by_key[cascades[0].key] == {True}
    assert by_key[cascades[1].key] == {False}
    # refinement uses only the marginal stream
    assert mon.observed(cascades[0].key) is not None
    assert mon.observed(cascades[1].key) is None
    assert mon.conditional(cascades[1].key) is not None
