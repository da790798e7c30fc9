"""Online batched cascade execution — the TPU-native adaptation of the
paper's per-image early-exit control flow (DESIGN.md §3).

TPUs want static shapes, so instead of branching per image we run
two-phase batch compaction per level:
  1. classify the full (sub-)batch with level l;
  2. argsort the uncertainty mask, gather the uncertain prefix into a
     FIXED-CAPACITY sub-batch, run level l+1 on it, scatter results back.
Capacity per level is a knob calibrated offline (e.g. the p99 uncertain
fraction measured on I_config); overflow items keep level-l's forced
decision (o >= 0.5) and are counted in the returned stats.

Representation derivation (DESIGN.md §3): when levels are given as
``Representation``s instead of opaque transform callables, each level's
input is derived from the nearest already-materialized pyramid level
rather than by re-gathering and re-transforming the raw base images. The
executor maintains a full-batch RGB pyramid cache: running a level
materializes its resolution (pooled from the smallest cached level that
divides it — box filters nest, so derived inputs are exactly what
apply_transform would produce from raw), and later levels gather rows
from that level's (much smaller) tensor. For a 224px base with 56/28px
levels that is a 16-64x cut in gathered bytes, and the bytes read per
level are exactly what core/cascade's pyramid cost matrices price
(``derivation_sources``).

Everything here is jit-compatible; model_fns[l] maps the level's input
representation tensor (already transformed) to probabilistic scores.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core.transforms import (Representation, color_transform,
                                   materialize_pyramid, resize_area)


def derivation_sources(res_seq: list[int], base: int) -> list[int]:
    """Source resolution each level's representation derives from: the
    smallest already-materialized pyramid level it divides (base is always
    materialized; running a level materializes its resolution). EXACTLY
    the policy core/cascade._cost_matrices prices — the executor and the
    cost model agree on bytes read per level."""
    out = []
    materialized = {base}
    for r in res_seq:
        usable = [m for m in materialized if m % r == 0]
        out.append(min(usable) if usable else base)
        materialized.add(r)
    return out


def run_cascade_on_pyramid(pyramid, model_fns: Sequence[Callable],
                           thresholds, reps: Sequence[Representation],
                           capacities: Sequence[int], level0_scores=None):
    """Run a cascade whose level inputs all derive from a CALLER-PROVIDED
    RGB pyramid cache ``{resolution: (B, r, r, 3) tensor}`` — the entry
    point the scan engine (engine/scan.py) uses so ONE materialized
    pyramid per corpus chunk serves every selected cascade. Missing
    levels are pooled on the fly from the nearest (smallest) cached level
    whose resolution they divide, exactly the derivation_sources policy,
    and cached back into a local copy (the caller's dict is not mutated).
    ``level0_scores``: precomputed level-0 probabilities (B,) — the fused
    Pallas pyramid+stage-0 kernel's epilogue output; when given, level 0's
    model is not invoked (its input derivation is skipped entirely).
    Returns (labels (B,), stats) like run_cascade_batch."""
    pyr_cache = dict(pyramid)
    base = max(pyr_cache)
    res_seq = [r.resolution for r in reps]

    def _pyramid_level(res: int):
        if res not in pyr_cache:
            usable = [m for m in pyr_cache if m % res == 0]
            src = min(usable) if usable else base
            pyr_cache[res] = resize_area(pyr_cache[src], res)
        return pyr_cache[res]

    def get_input(l: int, take):
        level = _pyramid_level(res_seq[l])
        # gather the (small) already-derived rows, not raw images
        sub = level if take is None else jnp.take(level, take, axis=0)
        return color_transform(sub, reps[l].color)

    b = next(iter(pyr_cache.values())).shape[0]
    return _cascade_loop(b, get_input, model_fns, thresholds, capacities,
                         level0_scores=level0_scores)


def run_cascade_batch(images, model_fns: Sequence[Callable],
                      thresholds: Sequence[tuple[float | None,
                                                 float | None]],
                      transforms, capacities: Sequence[int],
                      pyramid_cache=None):
    """images: raw batch (B, H, W, 3). Returns (labels (B,), stats).
    thresholds[l] = (p_low, p_high); final level may be (None, None).
    transforms: per-level transform callables, or per-level
    ``Representation``s (enables pyramid source derivation — see module
    docstring). capacities[l]: static sub-batch size for level l >= 1.
    pyramid_cache: optional pre-materialized {resolution: tensor} levels
    (merged with the raw base) for the Representation path — lets callers
    share one pyramid across several cascades."""
    pyramid = (len(transforms) > 0
               and isinstance(transforms[0], Representation))
    if pyramid:
        # full-batch RGB pyramid cache: each level's resolution is pooled
        # from the nearest (smallest) materialized level, then cached for
        # later levels — total extra memory is a geometric tail of the
        # base batch, and bytes read per level match the cost model's
        # derivation_sources policy
        pyr = {images.shape[1]: images}
        if pyramid_cache:
            pyr.update(pyramid_cache)
        return run_cascade_on_pyramid(pyr, model_fns, thresholds,
                                      list(transforms), capacities)

    def get_input(l: int, take):
        sub = images if take is None else jnp.take(images, take, axis=0)
        return transforms[l](sub)

    return _cascade_loop(images.shape[0], get_input, model_fns,
                         thresholds, capacities)


def _cascade_loop(b: int, get_input, model_fns, thresholds, capacities,
                  level0_scores=None):
    """Two-phase compaction loop shared by both input paths.
    get_input(l, take): level-l input representation for the full batch
    (take=None) or the gathered rows ``take``. level0_scores: optional
    precomputed level-0 probabilities (B,) — skips the level-0 model
    invocation (the fused-kernel ingest path)."""
    labels = jnp.zeros((b,), jnp.int32)
    decided = jnp.zeros((b,), bool)
    overflow = jnp.zeros((), jnp.int32)
    levels_used = jnp.zeros((len(model_fns),), jnp.int32)

    # level 0 on the full batch
    if level0_scores is None:
        o = model_fns[0](get_input(0, None))
    else:
        o = level0_scores
    lo, hi = thresholds[0]
    if lo is None:
        return (o >= 0.5).astype(jnp.int32), {
            "overflow": overflow,
            "levels_used": levels_used.at[0].set(b)}
    certain = (o <= lo) | (o >= hi)
    labels = jnp.where(o >= hi, 1, 0)
    forced = (o >= 0.5).astype(jnp.int32)   # fallback if never decided
    decided = certain
    levels_used = levels_used.at[0].set(b)

    active_mask = ~decided
    for l in range(1, len(model_fns)):
        cap = int(capacities[l - 1])
        # compact: uncertain items first (stable order)
        order = jnp.argsort(~active_mask, stable=True)
        take = order[:cap]
        valid = active_mask[take]
        overflow = overflow + jnp.sum(active_mask) - jnp.sum(valid)
        o = model_fns[l](get_input(l, take))
        levels_used = levels_used.at[l].set(jnp.sum(valid.astype(jnp.int32)))
        lo, hi = thresholds[l]
        final = lo is None
        if final:
            sub_decided = valid
            sub_labels = (o >= 0.5).astype(jnp.int32)
        else:
            cert = (o <= lo) | (o >= hi)
            sub_decided = valid & cert
            sub_labels = jnp.where(o >= hi, 1, 0)
        labels = labels.at[take].set(
            jnp.where(sub_decided, sub_labels, labels[take]))
        decided = decided.at[take].set(decided[take] | sub_decided)
        active_mask = active_mask.at[take].set(
            active_mask[take] & ~sub_decided)
        if final:
            break
    labels = jnp.where(decided, labels, forced)
    return labels, {"overflow": overflow, "levels_used": levels_used}


def calibrate_capacity(uncertain_fraction: float, batch: int,
                       quantile_margin: float = 1.3) -> int:
    """Capacity knob: expected uncertain count x a margin, clamped."""
    return int(min(batch, max(8, round(batch * uncertain_fraction
                                       * quantile_margin))))


# ------------------------------------------------- fused chunk ingest --
# The per-chunk hot path shared by the serial scan engine, the sharded
# lockstep ingest runner, and the serving flush assembly (DESIGN.md §13):
# ONE program per chunk does pyramid materialization + the full stage-0
# cascade + carried-level emission, instead of separate XLA dispatches
# with host round-trips between them. On TPU with real CNN params the
# pyramid + level-0 model run as ONE Pallas pass (kernels/image_transform
# .fused_pyramid_stage0, one HBM read of the base); elsewhere the same
# composition runs unfused inside one jit — bit-exact, since every stage
# is the identical jnp program.


@dataclass(frozen=True)
class Stage0:
    """The first cascade stage's model, in kernel-foldable form: the raw
    CNN parameter pytree + its input representation (CompiledCascade's
    model_fns are opaque closures — the Pallas epilogue needs the actual
    weights). ``qparams`` (models/cnn.quantize_cnn) enables the int8
    weight path."""
    params: Any
    rep: Representation
    qparams: Any = None


def make_fused_ingest(model_fns: Sequence[Callable], thresholds,
                      reps: Sequence[Representation],
                      capacities: Sequence[int], out_res,
                      *, stage0: Stage0 | None = None,
                      materialize: Callable | None = None,
                      use_kernel: bool | None = None, int8: bool = False,
                      jit: bool = True, emit_scores: bool = False):
    """Build the fused per-chunk ingest: fn(imgs (B,H,H,3)) ->
    (labels (B,), {res: (B,res,res,3) raw pooled level for res in
    out_res}).

    Runs the FULL stage-0 cascade (all its levels, full width — the
    engine's dense_levels execution) and emits the ``out_res`` pyramid
    levels the scan engine carries forward for later stages, in one
    program. ``materialize(imgs, resolutions) -> {res: level}`` overrides
    pyramid materialization on the unfused path (the scan engine injects
    its module-global so tests can count calls); default is
    core.transforms.materialize_pyramid. ``use_kernel=None`` resolves to
    the compiled kernel on TPU when ``stage0`` carries real CNN params
    the kernel can hold in VMEM (image_transform.stage0_fits: every
    reduced-grid model; a 224 px trusted CNN runs unfused). ``int8`` swaps
    stage-0's weights for the int8-quantized copy (dequantize-at-use;
    requires ``stage0.qparams``). ``emit_scores=True`` additionally
    returns the raw level-0 probability scores (B,) as a third output —
    on the kernel path they are the Pallas epilogue's ``s0`` for free;
    on the unfused path level 0 is scored explicitly and fed back via
    ``level0_scores`` so the composed program stays bit-identical. The
    ingest-time indexing pipeline (engine/ingest.py) consumes the
    scores for confident stage-0 decisions and candidate ranking."""
    out_res = [int(r) for r in out_res]
    need = sorted({r.resolution for r in reps} | set(out_res))
    if use_kernel and stage0 is None:
        raise ValueError("use_kernel requires stage0 params")
    if int8 and (stage0 is None or stage0.qparams is None):
        raise ValueError("int8 requires stage0.qparams")
    mat = materialize if materialize is not None else materialize_pyramid
    on_tpu = jax.default_backend() == "tpu"

    model_fns = list(model_fns)
    unfused_fns = list(model_fns)
    if int8:
        # unfused int8: dequantize once at build, identical arithmetic
        # to the kernel's dequantize-at-use epilogue
        from repro.models.cnn import cnn_predict_proba, dequantize_cnn
        unfused_fns[0] = partial(cnn_predict_proba,
                                 dequantize_cnn(stage0.qparams))

    def kernel_for(base: int) -> bool:
        if use_kernel is not None:
            return bool(use_kernel)
        if stage0 is None or not on_tpu:
            return False
        from repro.kernels.image_transform import stage0_fits
        return stage0_fits(stage0, base, [r for r in need if r != base],
                           int8)

    def run(imgs):
        base = imgs.shape[1]
        small = [r for r in need if r != base]
        if kernel_for(base):
            from repro.kernels.image_transform import fused_pyramid_stage0
            levels, s0 = fused_pyramid_stage0(
                imgs, small, stage0.params, stage0.rep,
                qparams=stage0.qparams if int8 else None)
            pyr = {base: imgs, **levels}
            fns = model_fns
        else:
            pyr = dict(mat(imgs, small))
            pyr.setdefault(base, imgs)
            fns = unfused_fns
            s0 = None
            if emit_scores:
                # score level 0 explicitly (same input derivation as
                # run_cascade_on_pyramid's get_input) and feed it back
                # as level0_scores — the composition is the identical
                # jnp program, so labels stay bit-exact
                s0 = fns[0](color_transform(pyr[reps[0].resolution],
                                            reps[0].color))
        labels, _ = run_cascade_on_pyramid(pyr, fns, thresholds, reps,
                                           capacities, level0_scores=s0)
        emitted = {r: pyr[r] for r in out_res}
        if emit_scores:
            return labels, emitted, s0
        return labels, emitted

    return jax.jit(run) if jit else run
