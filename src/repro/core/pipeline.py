"""TAHOMA system initialization (paper Fig. 2): model trainer -> cost
profiler -> cascade builder -> cascade evaluator, per binary predicate.

Scaled to this container: base resolution and grid sizes come from the
caller (benchmarks use the reduced grid in configs/tahoma_cnn.py); the
structure (A x F model grid, three data splits, 5 precision targets,
per-scenario cost profiles, Pareto selection) is the paper's.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TahomaCNNConfig
from repro.core import thresholds as thr_mod
from repro.core.cascade import (CascadeSpace, evaluate_cascades,
                                evaluate_cascades_streaming)
from repro.core.costs import CostProfile
from repro.core.transforms import (Representation, apply_transform,
                                   materialize_representations)
from repro.models.cnn import bce_loss, cnn_predict_proba, init_cnn
from repro.train.optimizer import adamw

# Scoring and profiling run every bank model through this one jitted
# predictor at one batch width, so each model shape compiles once: on
# TPU a Precision.HIGHEST conv program takes seconds to compile.
_predict = jax.jit(cnn_predict_proba)
SCORE_BATCH = 32


@dataclass
class ModelEntry:
    name: str
    arch: TahomaCNNConfig
    rep: Representation
    params: object
    trusted: bool = False

    def predict(self, raw_images) -> np.ndarray:
        x = apply_transform(jnp.asarray(raw_images), self.rep)
        return np.asarray(cnn_predict_proba(self.params, x))


@dataclass
class ModelBank:
    entries: list[ModelEntry] = field(default_factory=list)

    @property
    def names(self):
        return [e.name for e in self.entries]

    @property
    def reps(self):
        return [e.rep for e in self.entries]

    @property
    def trusted_index(self) -> int:
        return next(i for i, e in enumerate(self.entries) if e.trusted)

    def score_matrix(self, raw_images) -> np.ndarray:
        """(M, I): inference once per model (paper §V-D) — cached scores
        power every downstream cascade simulation. All representations
        the bank needs are materialized in ONE progressive pyramid pass
        (core/transforms.materialize_representations) instead of each
        model re-transforming from the raw base images; models score
        ``SCORE_BATCH`` rows per call (the last call zero-padded)."""
        rep_cache = materialize_representations(
            jnp.asarray(raw_images), [e.rep for e in self.entries])
        n = len(raw_images)
        rows = []
        for e in self.entries:
            x = np.asarray(rep_cache[e.rep])
            x = np.concatenate([x, np.zeros((-n % SCORE_BATCH,)
                                            + x.shape[1:], x.dtype)])
            rows.append(np.concatenate([
                np.asarray(_predict(e.params, x[i:i + SCORE_BATCH]))
                for i in range(0, len(x), SCORE_BATCH)])[:n])
        return np.stack(rows)


# ------------------------------------------------------------- training ----
def train_cnn(arch: TahomaCNNConfig, x, y, *, steps: int = 120,
              batch: int = 16, lr: float = 3e-3, seed: int = 0):
    """Train one specialized classifier (paper: 1-20 min on K80; here a
    few seconds at reduced scale)."""
    params = init_cnn(jax.random.PRNGKey(seed), arch)
    opt = adamw(lr, weight_decay=1e-4)
    state = opt.init(params)
    x = jnp.asarray(x)
    y = jnp.asarray(y, jnp.float32)

    @jax.jit
    def step(params, state, xb, yb):
        loss, grads = jax.value_and_grad(bce_loss)(params, xb, yb)
        params, state, _ = opt.update(grads, state, params)
        return params, state, loss

    n = x.shape[0]
    rng = np.random.default_rng(seed)
    for s in range(steps):
        idx = rng.integers(0, n, size=batch)
        params, state, _ = step(params, state, x[idx], y[idx])
    return params


def train_model_grid(train_x, train_y, archs: Sequence[TahomaCNNConfig],
                     reps: Sequence[Representation], *,
                     trusted_arch: TahomaCNNConfig | None = None,
                     steps: int = 120, seed: int = 0,
                     log: Callable[[str], None] | None = None) -> ModelBank:
    """The A x F grid (paper §V-B) + one trusted heavy model (ResNet50
    stand-in: deepest/widest CNN at full resolution, full color)."""
    bank = ModelBank()
    # one progressive pyramid pass materializes every training input
    rep_cache = {rep: np.asarray(x) for rep, x in
                 materialize_representations(jnp.asarray(train_x),
                                             reps).items()}
    for ai, arch0 in enumerate(archs):
        for rep in reps:
            arch = TahomaCNNConfig(
                n_conv_layers=arch0.n_conv_layers,
                conv_nodes=arch0.conv_nodes, dense_nodes=arch0.dense_nodes,
                input_hw=rep.resolution, input_channels=rep.channels)
            params = train_cnn(arch, rep_cache[rep], train_y, steps=steps,
                               seed=seed + ai)
            bank.entries.append(ModelEntry(
                f"{arch.arch_id}_{rep.name}", arch, rep, params))
            if log:
                log(f"trained {bank.entries[-1].name}")
    base_hw = train_x.shape[1]
    t_arch = trusted_arch or TahomaCNNConfig(
        n_conv_layers=3, conv_nodes=48, dense_nodes=64,
        input_hw=base_hw, input_channels=3)
    t_rep = Representation(base_hw, "rgb")
    t_params = train_cnn(t_arch, train_x, train_y, steps=steps * 3,
                         seed=seed + 999)
    bank.entries.append(ModelEntry(
        f"trusted_{t_arch.arch_id}", t_arch, t_rep, t_params, trusted=True))
    return bank


# -------------------------------------------------------------- profiling --
def profile_infer_costs(bank: ModelBank, sample_raw, *,
                        batch: int = SCORE_BATCH,
                        repeats: int = 3) -> dict[str, float]:
    """Measured seconds/image of pure inference (the cost profiler of
    Fig. 2, run in the current deployment)."""
    out = {}
    for e in bank.entries:
        x = apply_transform(jnp.asarray(sample_raw[:batch]), e.rep)
        _predict(e.params, x).block_until_ready()
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            _predict(e.params, x).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        out[e.name] = best / batch
    return out


# ---------------------------------------------------------- full pipeline --
@dataclass
class TahomaSystem:
    bank: ModelBank
    p_low: np.ndarray
    p_high: np.ndarray
    infer_s: dict[str, float]
    profile: CostProfile
    eval_scores: np.ndarray
    eval_truth: np.ndarray
    targets: tuple
    space_cache: dict = field(default_factory=dict)
    dec_cache: dict = field(default_factory=dict)

    def cascade_space(self, scenario: str, *, max_level: int = 3,
                      reps_subset=None, streaming: bool = False,
                      **stream_kw) -> CascadeSpace:
        """Re-cost + re-evaluate all cascades under a deployment scenario
        (cheap: pure linear algebra over cached scores — §V-E).
        streaming=True runs the bounded-memory chunked evaluator and
        returns only the surviving (Pareto/top-K) cascades; extra kwargs
        (chunk, keep, top_k, ...) pass through. Plain evaluations (no
        subset/kwargs) are memoized per (scenario, max_level, streaming)
        so repeated query planning reuses the evaluated space."""
        plain = reps_subset is None and not stream_kw
        key = (scenario, max_level, streaming)
        if plain and key in self.space_cache:
            return self.space_cache[key]
        keep = None
        if reps_subset is not None:
            keep = [i for i, e in enumerate(self.bank.entries)
                    if e.rep in reps_subset or e.trusted]
        infer = np.array([self.infer_s[n] for n in self.bank.names])
        evaluate = (evaluate_cascades_streaming if streaming
                    else evaluate_cascades)
        space = evaluate(
            self.eval_scores, self.eval_truth, self.p_low, self.p_high,
            self.bank.reps, infer, self.profile, scenario,
            self.bank.trusted_index, max_level=max_level,
            first_level_models=keep, **stream_kw)
        if plain:
            self.space_cache[key] = space
        return space

    def decomposed_cost(self, space: CascadeSpace, index: int,
                        scenario: str, *, dense_levels: bool = False):
        """Cascade ``index``'s §VI cost split into inference vs
        per-pyramid-level representation handling
        (core/costs.DecomposedCost) — the joint planner's costing unit
        (DESIGN.md §11). ``dense_levels`` prices the scan engine's
        full-width level execution (every level at reach 1) instead of
        the paper's reach-weighted walk. Memoized per (scenario, mode,
        physical cascade): the walk re-simulates the cascade over the
        cached eval scores, and joint planning prices every
        candidate-pool member."""
        from repro.core.cascade import spec_levels
        from repro.core.costs import decompose_cascade_cost

        key = (scenario, bool(dense_levels), int(space.kind[index]),
               int(space.i1[index]), int(space.i2[index]))
        if key not in self.dec_cache:
            infer = np.array([self.infer_s[n] for n in self.bank.names])
            self.dec_cache[key] = decompose_cascade_cost(
                spec_levels(space, index, self.p_low, self.p_high),
                self.eval_scores, self.bank.reps, infer, self.profile,
                scenario, dense_levels=dense_levels)
        return self.dec_cache[key]

    def compiled_ladder(self, space: CascadeSpace, index: int, *,
                        concept: str = "pred",
                        min_accuracy: float | None = None,
                        max_rungs: int | None = None) -> list:
        """The serving degradation ladder for the cascade at ``index``:
        every strictly cheaper Pareto-frontier cascade (optionally
        floored/truncated), compiled to executables with DISTINCT
        cascade ids so their labels land in their own virtual columns
        (core/selector.degradation_ladder; serve/service.py ladders=)."""
        from repro.core.selector import degradation_ladder

        return [self.compiled_cascade(space, sel.index, concept=concept)
                for sel in degradation_ladder(space, index,
                                              min_accuracy=min_accuracy,
                                              max_rungs=max_rungs)]

    def compiled_cascade(self, space: CascadeSpace, index: int, *,
                         concept: str = "pred", capacities=None):
        """Bridge to the query engine (DESIGN.md §4): decode cascade
        ``index`` of an evaluated space into an executable
        engine.scan.CompiledCascade — per-level model closures over this
        bank's trained params, thresholds, representations, plus the
        planner's cost (expected s/row under the space's scenario) and
        selectivity (simulated over the cached eval scores) estimates.
        The level-0 model's raw params also ride along in kernel-
        foldable form (executor.Stage0, with an int8-quantized copy) so
        the scan engines' fused ingest can fold stage 0 into the Pallas
        pyramid kernel on TPU (DESIGN.md §13)."""
        from functools import partial

        from repro.core.cascade import spec_levels
        from repro.core.executor import Stage0
        from repro.core.selector import estimate_selectivity
        from repro.engine.scan import CompiledCascade
        from repro.models.cnn import quantize_cnn

        levels = spec_levels(space, index, self.p_low, self.p_high)
        reps, fns, ths = [], [], []
        for m, lo, hi in levels:
            e = self.bank.entries[m]
            reps.append(e.rep)
            fns.append(partial(cnn_predict_proba, e.params))
            ths.append((None if lo is None else float(lo),
                        None if hi is None else float(hi)))
        sel = estimate_selectivity(space, index, self.eval_scores,
                                   self.p_low, self.p_high)
        cascade_id = (int(space.kind[index]), int(space.i1[index]),
                      int(space.i2[index]))
        e0 = self.bank.entries[levels[0][0]]
        stage0 = Stage0(params=e0.params, rep=e0.rep,
                        qparams=quantize_cnn(e0.params))
        return CompiledCascade(
            concept=concept, cascade_id=cascade_id, reps=reps,
            model_fns=fns, thresholds=ths,
            cost_s=float(space.time_s[index]), selectivity=sel,
            capacities=capacities, stage0=stage0)


def initialize_system(train_split, config_split, eval_split,
                      archs, reps, *, targets=thr_mod.PRECISION_TARGETS,
                      steps: int = 120, seed: int = 0,
                      log=None) -> TahomaSystem:
    (tr_x, tr_y), (cf_x, cf_y), (ev_x, ev_y) = (train_split, config_split,
                                                eval_split)
    bank = train_model_grid(tr_x, tr_y, archs, reps, steps=steps,
                            seed=seed, log=log)
    cfg_scores = bank.score_matrix(cf_x)
    p_low, p_high = thr_mod.compute_thresholds_batch(cfg_scores, cf_y,
                                                     targets)
    infer_s = profile_infer_costs(bank, ev_x)
    profile = CostProfile.modeled(infer_s, list(set(bank.reps)),
                                  base_hw=tr_x.shape[1])
    eval_scores = bank.score_matrix(ev_x)
    return TahomaSystem(bank, p_low, p_high, infer_s, profile,
                        eval_scores, ev_y, tuple(targets))


def build_scan_engine(images, metadata=None, *, shards: int | None = None,
                      chunk: int = 64, jit: bool = True,
                      strategy: str = "range", repcache=None,
                      fused: bool = True, lazy: bool = True,
                      int8: bool = False, use_kernel: bool | None = None):
    """System-level scan-executor factory (the ``--shards N`` path in
    examples/ and benchmarks/): ``shards=None``/0 builds the single-host
    ScanEngine; any explicit shard count (including 1, for scaling-curve
    baselines) builds the sharded engine (DESIGN.md §9). Both share the
    same execute(cascades, metadata_eq) surface and virtual-column
    semantics. ``repcache`` (serial engine only) plugs a cross-query
    representation cache into per-chunk pyramid materialization
    (DESIGN.md §10.3). ``fused``/``lazy``/``int8``/``use_kernel`` are
    the hot-path knobs (DESIGN.md §13): fused single-program chunk
    ingest, lazy first-touch level materialization, int8 stage-0
    weights, and the Pallas pyramid+stage-0 kernel override."""
    from repro.engine.scan import ScanEngine
    from repro.engine.sharded import ShardedScanEngine

    if shards:
        return ShardedScanEngine(images, metadata, shards=int(shards),
                                 chunk=chunk, jit=jit, strategy=strategy,
                                 fused=fused, lazy=lazy, int8=int8,
                                 use_kernel=use_kernel)
    return ScanEngine(images, metadata, chunk=chunk, jit=jit,
                      repcache=repcache, fused=fused, lazy=lazy,
                      int8=int8, use_kernel=use_kernel)


def build_cascade_service(images, cascades, *, mode: str = "async",
                          shards: int | None = None, batch_size: int = 32,
                          max_wait_s: float = 0.005, clock=None,
                          repcache_bytes: int | None = 64 << 20,
                          repcache=None, store=None, jit: bool = True,
                          host: bool = False, **hardening):
    """System-level serving factory (DESIGN.md §10, §12):
    ``mode='async'`` builds the shard-aware AsyncCascadeService
    (deadline scheduler, per-shard device queues, cross-query
    representation cache — a fresh ``repcache_bytes``-budget cache
    unless the caller shares one via ``repcache``, e.g. the same object
    backing a ScanEngine); ``mode='sync'`` builds the legacy
    synchronous-polling CascadeService from the same
    {concept -> CompiledCascade} table. ``store`` shares a scan
    engine's virtual columns with the service so previously scanned
    rows are served with zero model invocations.

    Hardening (async only; DESIGN.md §12): extra keyword args pass
    straight to AsyncCascadeService — ``queue_limit``, ``overload``,
    ``ladders`` (e.g. from ``TahomaSystem.compiled_ladder``),
    ``degrade`` (a DegradeConfig), ``batch_timeout_s``,
    ``request_deadline_s``, ``dispatch_retries``, ``faults``, and the
    ingest-index seeds ``ingest_index``/``ingest_exact`` (DESIGN.md
    §14: a CandidateIndex built by build_ingest_pipeline seeds the
    service store so ingest-decided rows answer at submit with zero
    model invocations).
    ``host=True`` wraps the service in a started wall-clock EventHost
    (serve/host.py) so deadlines fire without caller cooperation; the
    caller gets the HOST (``host.service`` reaches the service) and
    must ``stop()`` it."""
    import time

    from repro.serve.batcher import CascadeService
    from repro.serve.repcache import RepresentationCache
    from repro.serve.service import AsyncCascadeService

    clock = clock or time.perf_counter
    if mode == "sync":
        if hardening or host:
            raise ValueError("hardening knobs require mode='async'")
        return CascadeService.from_cascades(cascades, batch_size,
                                            max_wait_s, clock, jit=jit)
    if mode != "async":
        raise ValueError(f"unknown serving mode {mode!r}")
    if repcache is None and repcache_bytes:
        repcache = RepresentationCache(repcache_bytes)
    service = AsyncCascadeService(images, cascades, shards=shards,
                                  batch_size=batch_size,
                                  max_wait_s=max_wait_s, clock=clock,
                                  repcache=repcache, store=store,
                                  jit=jit, **hardening)
    if host:
        from repro.serve.host import EventHost
        return EventHost(service).start()
    return service


def build_ingest_pipeline(cascades, n_rows: int, *, chunk: int = 64,
                          skip: bool = True,
                          skip_threshold: float | None = 0.008,
                          calib_frames: int = 48,
                          top_k: int | None = None,
                          prune_margin: float = 0.25, jit: bool = True,
                          int8: bool = False,
                          use_kernel: bool | None = None):
    """System-level ingest factory (DESIGN.md §14): a streaming
    IngestPipeline over the planned ``cascades`` (a sequence, or a
    {concept -> CompiledCascade} table as built for serving) for a
    corpus/stream of ``n_rows`` frames. Feed arriving frames with
    ``.ingest(frames, ids)`` (any batch granularity — the temporal skip
    detector chains across calls) or sweep a resident corpus with
    ``.run(images)``; the resulting ``.index`` plugs into
    ``plan_query(..., index=...)`` and ``build_cascade_service(...,
    ingest_index=...)``. The cascades must be the SAME physical
    cascades queries will select — labels are keyed by
    CompiledCascade.key. ``skip_threshold=None`` auto-calibrates the
    temporal-difference threshold per camera from the first
    ``calib_frames`` frames (IngestPipeline.calibrate_threshold)."""
    from repro.engine.ingest import IngestPipeline

    if isinstance(cascades, dict):
        cascades = list(cascades.values())
    return IngestPipeline(cascades, n_rows, chunk=chunk, skip=skip,
                          skip_threshold=skip_threshold,
                          calib_frames=calib_frames, top_k=top_k,
                          prune_margin=prune_margin, jit=jit, int8=int8,
                          use_kernel=use_kernel)
