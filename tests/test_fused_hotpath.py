"""Fused Pallas pyramid+stage-0 hot path and lazy level materialization
(DESIGN.md §13, PR 7).

Covers, per the tentpole acceptance list:
* kernel bit-exactness property tests: fused_pyramid_stage0 vs the
  unfused reference composition across dyadic base sizes and interpret
  modes — pooled levels BIT-exact, f32 scores to float tolerance, int8
  scores within the pinned calibrated tolerance
  (benchmarks/calibrated_int8_stage0.json);
* invocation/materialization-counting regressions: lazy scheduling
  materializes strictly fewer level-rows than eager with bit-identical
  row sets; fused and unfused engines agree; warm reruns build nothing;
* the engine-costing contract: measured ScanStats.level_rows matches
  the level_schedule first-touch prediction exactly on a cold scan;
* sharded lockstep vs serial differentials under lazy scheduling;
* observed-selectivity feedback into shard skew weights (satellite 2).
"""
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import TahomaCNNConfig
from repro.core.executor import Stage0, make_fused_ingest
from repro.core.transforms import Representation, materialize_pyramid
from repro.engine.scan import (CompiledCascade, ScanEngine,
                               level_schedule, naive_scan)
from repro.kernels.image_transform import fused_pyramid_stage0
from repro.kernels.ref import fused_pyramid_stage0_ref
from repro.models.cnn import (cnn_predict_proba, dequantize_cnn, init_cnn,
                              quantize_cnn)

CAL_PATH = Path(__file__).resolve().parents[1] / "benchmarks" \
    / "calibrated_int8_stage0.json"


def _dyadic_images(n, hw, seed=0):
    """uint8-quantized pixels (k/256): box-filter pooling over dyadic
    windows is EXACT in f32 for these — the bit-exactness precondition
    (core/transforms.materialize_pyramid)."""
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 256, (n, hw, hw, 3))
            .astype(np.float32) / 256.0)


def _stage0(seed, res, color="gray", n_conv=2):
    cfg = TahomaCNNConfig(n_conv_layers=n_conv, conv_nodes=4,
                          dense_nodes=8, input_hw=res,
                          input_channels=1 if color != "rgb" else 3)
    params = init_cnn(jax.random.PRNGKey(seed), cfg)
    rep = Representation(res, color)
    return Stage0(params=params, rep=rep, qparams=quantize_cnn(params))


# ------------------------------------------------ kernel bit-exactness ----
@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([16, 32]),
       st.sampled_from([True, None]))
def test_fused_kernel_bit_exact_vs_unfused_reference(seed, base, interpret):
    """Property: one kernel pass == materialize_pyramid + stage-0 CNN.
    Pooled levels are BIT-exact (dyadic pixels); scores match the jnp
    composition to f32 tolerance. interpret=None resolves per backend
    (True off-TPU), True forces interpret mode — both must agree."""
    imgs = _dyadic_images(3, base, seed)
    s0 = _stage0(seed, base // 4)
    out_res = [base // 2, base // 4]
    levels, scores = fused_pyramid_stage0(
        jnp.asarray(imgs), out_res, s0.params, s0.rep,
        interpret=interpret)
    ref_levels, ref_scores = fused_pyramid_stage0_ref(
        jnp.asarray(imgs), out_res, s0.params, s0.rep)
    for r in out_res:
        assert np.array_equal(np.asarray(levels[r]),
                              np.asarray(ref_levels[r])), r
        assert np.array_equal(np.asarray(levels[r]),
                              np.asarray(materialize_pyramid(
                                  jnp.asarray(imgs), [r])[r])), r
    np.testing.assert_allclose(np.asarray(scores),
                               np.asarray(ref_scores), atol=1e-5)


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 10_000))
def test_fused_kernel_int8_matches_ref_and_calibration(seed):
    """int8 weight path: the kernel's dequantize-at-use epilogue matches
    the unfused int8 reference to f32 tolerance, and int8-vs-f32 score
    deviation stays inside the PINNED calibrated tolerance — the same
    contract calibrated_infer_costs.json pins for cost estimates."""
    cal = json.loads(CAL_PATH.read_text())
    base = 32
    imgs = _dyadic_images(3, base, seed)
    s0 = _stage0(seed, base // 4)
    _, s_int8 = fused_pyramid_stage0(jnp.asarray(imgs), [base // 4],
                                     s0.params, s0.rep,
                                     qparams=s0.qparams)
    _, ref_int8 = fused_pyramid_stage0_ref(jnp.asarray(imgs), [base // 4],
                                           s0.params, s0.rep,
                                           qparams=s0.qparams)
    _, s_f32 = fused_pyramid_stage0(jnp.asarray(imgs), [base // 4],
                                    s0.params, s0.rep)
    np.testing.assert_allclose(np.asarray(s_int8), np.asarray(ref_int8),
                               atol=1e-5)
    dev = float(np.max(np.abs(np.asarray(s_int8) - np.asarray(s_f32))))
    assert dev <= cal["score_abs_tol"], (dev, cal["score_abs_tol"])


@given(st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_int8_quantize_roundtrip_error_bounded(seed):
    """Per-tensor symmetric int8: |w - dequant(quant(w))| <= scale/2,
    with scale = absmax/127 — the rounding bound the calibrated score
    tolerance rests on."""
    cfg = TahomaCNNConfig(n_conv_layers=2, conv_nodes=4, dense_nodes=8,
                          input_hw=8, input_channels=1)
    params = init_cnn(jax.random.PRNGKey(seed), cfg)
    dq = dequantize_cnn(quantize_cnn(params))
    pairs = [(l["w"], m["w"]) for l, m in zip(params["conv"], dq["conv"])]
    pairs += [(params["dense_w"], dq["dense_w"]),
              (params["out_w"], dq["out_w"])]
    for w, w2 in pairs:
        scale = float(jnp.max(jnp.abs(w))) / 127.0
        assert float(jnp.max(jnp.abs(w - w2))) <= scale / 2 + 1e-9
    # biases pass through untouched
    for l, m in zip(params["conv"], dq["conv"]):
        assert np.array_equal(np.asarray(l["b"]), np.asarray(m["b"]))
    assert np.array_equal(np.asarray(params["dense_b"]),
                          np.asarray(dq["dense_b"]))


def test_stage0_fits_reduced_grid_not_trusted_cnn():
    """The kernel epilogue holds the reduced grid's largest stage-0 model
    at base 224; a 224 px trusted CNN's banded weights do not fit, so
    use_kernel=None leaves such a stage 0 on the unfused path."""
    from repro.kernels.image_transform import stage0_fits

    def stage0(arch, res):
        cfg = TahomaCNNConfig(arch.n_conv_layers, arch.conv_nodes,
                              arch.dense_nodes, input_hw=res,
                              input_channels=3)
        params = init_cnn(jax.random.PRNGKey(0), cfg)
        return Stage0(params, Representation(res, "rgb"),
                      quantize_cnn(params))

    small = stage0(TahomaCNNConfig(2, 32, 32), 56)
    assert stage0_fits(small, 224, [112, 56, 28])
    assert stage0_fits(small, 224, [112, 56, 28], int8=True)
    assert not stage0_fits(stage0(TahomaCNNConfig(3, 48, 64), 224), 224)


def test_make_fused_ingest_kernel_flag_validation():
    s0 = _stage0(0, 8)
    casc_fns = [lambda x: jnp.zeros(x.shape[0])]
    with pytest.raises(ValueError):
        make_fused_ingest(casc_fns, [(None, None)],
                          [Representation(8, "gray")], [], [],
                          use_kernel=True, stage0=None)
    with pytest.raises(ValueError):
        make_fused_ingest(casc_fns, [(None, None)],
                          [Representation(8, "gray")], [], [],
                          stage0=Stage0(s0.params, s0.rep), int8=True)


# --------------------------------------------------- scan-engine toys -----
def _linear_cascade(concept, seed, resolutions, thresholds, *,
                    cost_s=1e-4, selectivity=0.5):
    """Linear toy cascade over arbitrary per-level resolutions (rgb), so
    different cascades touch DIFFERENT pyramid levels and the lazy
    schedule has real later-stage-only levels to defer."""
    r = np.random.default_rng(seed)
    reps = [Representation(res, "rgb") for res in resolutions]
    dims = [res * res * 3 for res in resolutions]
    ws = [jnp.asarray(r.standard_normal((d, 1)).astype(np.float32))
          for d in dims]

    def mk(i):
        def f(x):
            z = (x.reshape(x.shape[0], -1) - 0.5) @ ws[i]
            return jax.nn.sigmoid(z[:, 0] * 60.0 / math.sqrt(dims[i]))
        return f
    return CompiledCascade(concept, ("lin", seed), reps,
                           [mk(i) for i in range(len(reps))],
                           list(thresholds), cost_s=cost_s,
                           selectivity=selectivity)


@pytest.fixture(scope="module")
def lazy_setup():
    imgs = _dyadic_images(200, 32, seed=7)
    cascades = [
        _linear_cascade("a", 1, [8], [(None, None)], cost_s=1e-4),
        _linear_cascade("b", 2, [16, 32], [(0.3, 0.7), (None, None)],
                        cost_s=2e-4),
        _linear_cascade("c", 3, [4, 16], [(0.35, 0.65), (None, None)],
                        cost_s=4e-4),
    ]
    metadata = {"cam": np.arange(len(imgs)) % 2}
    return imgs, cascades, metadata


def test_lazy_strictly_fewer_level_rows_same_rows(lazy_setup):
    """Lazy scheduling must materialize STRICTLY fewer level-rows than
    eager while returning a bit-identical row set (tentpole acceptance:
    the §11 estimated-vs-measured gap closes without changing
    results)."""
    imgs, cascades, metadata = lazy_setup
    res_e = ScanEngine(imgs, metadata, chunk=32, lazy=False).execute(
        cascades, {"cam": 0})
    res_l = ScanEngine(imgs, metadata, chunk=32, lazy=True).execute(
        cascades, {"cam": 0})
    assert np.array_equal(res_e.indices, res_l.indices)
    ref = naive_scan(imgs, cascades, metadata, {"cam": 0}, chunk=32)
    assert np.array_equal(res_l.indices, ref)
    eager, lazy = res_e.stats.level_rows, res_l.stats.level_rows
    assert set(lazy) == set(eager)          # same levels get touched
    assert all(lazy[r] <= eager[r] for r in eager)
    assert sum(lazy.values()) < sum(eager.values())
    # the static union set is reported identically either way
    assert res_l.stats.pyramid_levels == res_e.stats.pyramid_levels


def test_fused_and_unfused_engines_identical(lazy_setup):
    """The fused single-program ingest is a pure fusion: labels, row
    sets, and materialization counters all match the unfused
    pyramid-program + stage-0-buffer baseline."""
    imgs, cascades, metadata = lazy_setup
    res_f = ScanEngine(imgs, metadata, chunk=32, fused=True).execute(
        cascades, {"cam": 0})
    res_u = ScanEngine(imgs, metadata, chunk=32, fused=False).execute(
        cascades, {"cam": 0})
    assert np.array_equal(res_f.indices, res_u.indices)
    assert res_f.stats.level_rows == res_u.stats.level_rows
    assert res_f.stats.chunks == res_u.stats.chunks


def test_level_rows_match_schedule_exactly_on_cold_scan(lazy_setup):
    """The engine-costing contract (closes DESIGN.md §11's known gap):
    on a cold scan every ingest level is pooled for exactly the scanned
    rows, and every first-touch level for exactly the rows its stage
    evaluated — ScanStats.level_rows equals the level_schedule
    prediction with NO slack."""
    imgs, cascades, metadata = lazy_setup
    eng = ScanEngine(imgs, metadata, chunk=32, lazy=True)
    res = eng.execute(cascades, {"cam": 0})
    ingest_set, _, derive = level_schedule(cascades, imgs.shape[1], True)
    want = {r: res.stats.rows_scanned for r in ingest_set}
    for s, levels in enumerate(derive):
        for r in levels:
            want[r] = res.stats.stages[s].rows_evaluated
    assert res.stats.level_rows == want


def test_lazy_warm_rerun_builds_nothing(lazy_setup, monkeypatch):
    """Second identical scan against a warm virtual-column store: zero
    chunks, zero pyramid materializations, zero level-rows — and the
    same row set."""
    import repro.engine.scan as scan_mod

    imgs, cascades, metadata = lazy_setup
    eng = ScanEngine(imgs, metadata, chunk=32, jit=False)
    first = eng.execute(cascades, {"cam": 0})
    calls = []
    real = scan_mod.materialize_pyramid

    def counting(img, resolutions):
        calls.append(tuple(resolutions))
        return real(img, resolutions)

    monkeypatch.setattr(scan_mod, "materialize_pyramid", counting)
    again = eng.execute(cascades, {"cam": 0})
    assert np.array_equal(first.indices, again.indices)
    assert again.stats.chunks == 0
    assert again.stats.level_rows == {}
    assert calls == []


@pytest.mark.multidevice
@pytest.mark.parametrize("shards", [1, 8])
@pytest.mark.parametrize("parallel", [True, False])
def test_sharded_lazy_bit_identical_and_counters(lazy_setup, shards,
                                                 parallel):
    """Sharded lockstep and serial-fallback backends under lazy
    scheduling: row sets bit-identical to the serial engine, and the
    cross-shard level_rows totals equal the serial counters on a cold
    scan (both engines follow the same first-touch schedule)."""
    from repro.engine.sharded import ShardedScanEngine

    imgs, cascades, metadata = lazy_setup
    ref = ScanEngine(imgs, metadata, chunk=32).execute(
        cascades, {"cam": 0})
    eng = ShardedScanEngine(imgs, metadata, shards=shards, chunk=32)
    res = eng.execute(cascades, {"cam": 0}, parallel=parallel)
    assert np.array_equal(res.indices, ref.indices)
    assert res.stats.level_rows == ref.stats.level_rows


def test_monitor_observed_selectivity_feeds_shard_weights(lazy_setup):
    """Satellite: OnlineReorderer's per-flush observations flow into
    plan_shards skew weights on re-plan — a predicate observed to kill
    everything collapses the expected cost of every later predicate."""
    from repro.engine.planner import OnlineReorderer
    from repro.engine.sharded import ShardedScanEngine

    imgs, cascades, metadata = lazy_setup
    eng = ShardedScanEngine(imgs, metadata, shards=2, chunk=32)
    ids = np.where(eng.metadata_mask({"cam": 0}))[0]
    mon = OnlineReorderer(cascades, min_rows=1)
    mon.observe(cascades[0].key, np.zeros(128, np.int64),
                marginal=True)                             # observed sel 0
    w_static = eng.row_weights(cascades, ids)
    w_refined = eng.row_weights(cascades, ids, monitor=mon)
    # refined: nothing survives predicate 0, so only its own cost remains
    assert np.allclose(w_refined, cascades[0].cost_s)
    assert w_refined.sum() < w_static.sum()
    plan = eng.plan_for(cascades, ids=ids, monitor=mon)
    assert plan.n_shards == 2 and plan.validate(ids) is None
    # executing with the monitor attached keeps feeding it (observe-only
    # on sharded backends: proposals are never applied mid-scan)
    res = eng.execute(cascades, {"cam": 0}, monitor=mon)
    ref = ScanEngine(imgs, metadata, chunk=32).execute(
        cascades, {"cam": 0})
    assert np.array_equal(res.indices, ref.indices)
    assert mon.n[cascades[0].key] > 128      # ingest flushes observed
