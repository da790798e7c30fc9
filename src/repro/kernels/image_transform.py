"""Fused physical-representation transform kernels (paper §V-B / §VI).

``fused_pyramid_transform`` — ONE HBM read of the base image emits every
(resolution, color) representation a cascade (or the whole A x F grid)
needs: area-average resize, color projection (RGB keep / channel select
/ grayscale — a length-3 channel weight matrix) and normalization, so
HBM traffic is one base read plus the (much smaller) representation
writes. ``fused_transform`` is its one-output case.

``fused_pyramid_stage0`` — the scan engine's chunk ingest: one HBM read
of the base image emits the raw pooled RGB pyramid levels AND the
stage-0 cascade model's scores (the small CNN runs in the epilogue).

Layout (all three kernels). An image travels as a 2-D ``(H, W*3)`` slab
— rows on sublanes, the colour channels interleaved with the columns on
the 128-wide lane axis — a free reshape of the caller's ``(B, H, W, 3)``
that keeps the 3 channels off the lane axis of their own. Each grid step
takes ``IMAGES_PER_STEP`` images (the batch is zero-padded to a
multiple). Every level is pooled straight from the base: one MXU dot
with a constant column-pooling matrix (entries 1/f) followed by f
strided row loads summed on the VPU. For uint8-valued pixels (k/256) all
of it is exact, so levels are bit-identical to
core/transforms.materialize_pyramid. A 3x3 SAME conv layer is three
MXU dots with banded weight matrices (one per kernel row, built in the
wrapper from the conv weights by gather, so they carry the weights
unrounded), row shifts through a VMEM scratch, and 2x2 max-pooling as
even/odd output columns plus strided row loads. Every dot runs at
``Precision.HIGHEST``, the precision models/cnn.py runs its XLA
reference at. The kernel returns stage-0 logits; the sigmoid is applied
outside, by the same XLA op as the reference.

``interpret=None`` (default) resolves by backend: compiled Mosaic on
TPU, interpret mode elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import numpy as np

from repro.core.transforms import _GRAY, plan_pyramid
from repro.kernels import resolve_interpret

IMAGES_PER_STEP = 8
_HIGHEST = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
_LANES = 128            # score block width: one (8, 128)-legal tile
_MIB = 1 << 20
_VMEM_CAP = 100 * _MIB


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _col_pool_matrix(width: int, res: int) -> np.ndarray:
    """(width*3, res*3) column pooling in the interleaved layout:
    1/f where the input column falls in the output column's window and
    the channels agree."""
    f = width // res
    m = np.zeros((width, res), np.float32)
    m[np.arange(width), np.arange(width) // f] = 1.0 / f
    return np.kron(m, np.eye(3, dtype=np.float32))


def _tiles(lanes: int) -> int:
    return -(-lanes // _LANES)


def _store_rows(ref, v, row0: int = 0) -> None:
    """Store v (R, L) at rows row0.. of a (tiles, rows, 128) scratch,
    128 lanes per tile: strided row loads need a 128-lane base."""
    rows, lanes = v.shape
    for k in range(_tiles(lanes)):
        lo = k * _LANES
        hi = min(lanes, lo + _LANES)
        ref[k, pl.ds(row0, rows), :hi - lo] = v[:, lo:hi]


def _load_rows(ref, lanes: int, start: int, count: int, stride: int):
    """Rows start, start+stride, ... (count of them) of what _store_rows
    put in ``ref``, reassembled to (count, lanes)."""
    pieces = []
    for k in range(_tiles(lanes)):
        width = min(_LANES, lanes - k * _LANES)
        pieces.append(ref[k, pl.ds(start, count, stride=stride), :width])
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, 1)


def _pool_levels(x, pool_refs, rows_ref, resolutions):
    """x (H, W*3) one image -> {res: (res, res*3)} area-averaged levels,
    each pooled from the base (column dot, then strided row sums)."""
    h = x.shape[0]
    levels = {h: x}
    for res, p_ref in zip(resolutions, pool_refs):
        f, lanes = h // res, res * 3
        _store_rows(rows_ref, _dot(x, p_ref[...]))
        acc = _load_rows(rows_ref, lanes, 0, res, f)
        for j in range(1, f):
            acc = acc + _load_rows(rows_ref, lanes, j, res, f)
        levels[res] = acc * (1.0 / f)
    return levels


def _pad_batch(images, tb: int):
    b = images.shape[0]
    bp = -(-b // tb) * tb
    x = images.astype(jnp.float32).reshape(b, images.shape[1], -1)
    if bp != b:
        x = jnp.pad(x, ((0, bp - b), (0, 0), (0, 0)))
    return x, bp


class VmemOverflow(ValueError):
    """The kernel's blocks, resident weights and scratch need more VMEM
    than it may request."""


def _vmem_limit(block_bytes: int, resident_bytes: int) -> int:
    """Scoped-VMEM request: double-buffered pipelined blocks, single-
    copy resident operands and scratch, plus room for the body's
    intermediate values (v5e holds 128 MiB; the default scope is 16)."""
    need = 2 * block_bytes + 2 * resident_bytes + 8 * _MIB
    if need > _VMEM_CAP:
        raise VmemOverflow(f"needs ~{need // _MIB} MiB of VMEM; at most "
                           f"{_VMEM_CAP // _MIB} MiB may be requested")
    return max(need, 16 * _MIB)


def _nbytes(shape, itemsize=4) -> int:
    return int(np.prod(shape)) * itemsize


def _row_scratch(rows: int, lanes: int):
    return pltpu.VMEM((_tiles(lanes), rows, _LANES), jnp.float32)


def _resident():
    """Operand copied into VMEM once for the whole grid (no pipelining,
    so no second buffer)."""
    return pl.BlockSpec(memory_space=pltpu.VMEM)


# ------------------------------------------------ fused pyramid transform --
def _pyramid_kernel(x_ref, *refs, tb: int, pooled, out_meta,
                    mean: float, inv_std: float):
    """refs = (pool_0.., proj_0.., out_0.., rows scratch).
    pooled: resolutions pooled from the base (one pool matrix each);
    out_meta: ((res, out_ch), ...) per output."""
    n_p, n_o = len(pooled), len(out_meta)
    pool_refs, proj_refs = refs[:n_p], refs[n_p:n_p + n_o]
    out_refs, rows_ref = refs[n_p + n_o:n_p + 2 * n_o], refs[-1]
    def one_image(t, carry):
        levels = _pool_levels(x_ref[t], pool_refs, rows_ref, pooled)
        for i, (res, _) in enumerate(out_meta):
            proj = _dot(levels[res], proj_refs[i][...])
            out_refs[i][t] = (proj - mean) * inv_std
        return carry

    jax.lax.fori_loop(0, tb, one_image, 0)


def fused_pyramid_transform(images, rep_specs,
                            mean: float = 0.5, std: float = 0.25,
                            interpret: bool | None = None):
    """Multi-output fused transform: images (B, H, H, 3) float32 ->
    tuple of (B, res_i, res_i, C'_i) normalized tensors, one per
    (res, channel_weights) pair in ``rep_specs``, all emitted from a
    single HBM read of the base image per batch element."""
    b, h, w, _ = images.shape
    assert h == w, (h, w)
    specs = [(int(res), jnp.asarray(cw, jnp.float32))
             for res, cw in rep_specs]
    plan_pyramid([r for r, _ in specs], h)      # validates nesting
    pooled = tuple(sorted({r for r, _ in specs} - {h}, reverse=True))
    out_meta = tuple((res, int(cw.shape[1])) for res, cw in specs)
    tb = IMAGES_PER_STEP
    x, bp = _pad_batch(images, tb)
    pools = [jnp.asarray(_col_pool_matrix(h, r)) for r in pooled]
    projs = [jnp.kron(jnp.eye(res, dtype=jnp.float32), cw)
             for res, cw in specs]
    rows_lanes = max([r * 3 for r in pooled], default=_LANES)
    scratch = [_row_scratch(h, rows_lanes)]
    block = _nbytes((tb, h, w * 3)) + sum(
        _nbytes((tb, r, r * c)) for r, c in out_meta)
    resident = sum(_nbytes(a.shape) for a in pools + projs) + sum(
        _nbytes(a.shape) for a in scratch)
    kernel = functools.partial(
        _pyramid_kernel, tb=tb, pooled=pooled, out_meta=out_meta,
        mean=mean, inv_std=1.0 / std)
    out = pl.pallas_call(
        kernel,
        grid=(bp // tb,),
        in_specs=([pl.BlockSpec((tb, h, w * 3), lambda i: (i, 0, 0))]
                  + [_resident() for _ in pools + projs]),
        out_specs=[pl.BlockSpec((tb, r, r * c), lambda i: (i, 0, 0))
                   for r, c in out_meta],
        out_shape=[jax.ShapeDtypeStruct((bp, r, r * c), jnp.float32)
                   for r, c in out_meta],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(block, resident)),
        interpret=resolve_interpret(interpret),
    )(x, *pools, *projs)
    return tuple(o[:b].reshape(b, r, r, c)
                 for o, (r, c) in zip(out, out_meta))


def fused_transform(images, channel_weights, res: int,
                    mean: float = 0.5, std: float = 0.25,
                    interpret: bool | None = None):
    """images (B, H, H, 3) float32; channel_weights (3, C') encodes the
    color representation (identity columns / unit column / gray weights).
    -> (B, res, res, C') normalized: the one-output pyramid transform."""
    return fused_pyramid_transform(images, [(res, channel_weights)],
                                   mean, std, interpret)[0]


# ------------------------------------------- fused pyramid + stage-0 pass --
# One HBM read of the base image emits (a) the raw pooled RGB pyramid
# levels the scan engine carries between cascade stages and (b) the
# stage-0 cascade model's logits, with the small CNN folded into the
# kernel epilogue. Weights ride in as resident VMEM operands; the int8
# path carries int8 weight tensors and dequantizes at use (per-tensor
# scale baked in as a trace constant — models/cnn.quantize_cnn).

def color_weight_matrix(color: str) -> np.ndarray:
    """(3, C') channel-projection matrix matching core.transforms.
    color_transform exactly (identity / unit column / gray weights)."""
    if color == "rgb":
        return np.eye(3, dtype=np.float32)
    if color == "gray":
        return _GRAY.reshape(3, 1).astype(np.float32)
    idx = {"r": 0, "g": 1, "b": 2}[color]
    w = np.zeros((3, 1), np.float32)
    w[idx, 0] = 1.0
    return w


def _conv_bands(w, width: int):
    """3x3 SAME conv weights (3, 3, Cin, Cout) -> banded matrices
    (3 kernel rows, 2 output-column parities, width*Cin,
    (width//2)*Cout): row dy maps an input row (w, ci)-interleaved to
    the conv row's output columns 2j+parity, so max-pooling over column
    pairs is an elementwise max of the two parities. Built by gather
    and select only (no arithmetic), so int8 weights stay int8."""
    cin, cout = w.shape[2], w.shape[3]
    half = width // 2
    w_in = np.arange(width)[:, None]
    w_out = np.arange(2 * half)[None, :]
    dx = w_in - w_out + 1
    valid = jnp.asarray((dx >= 0) & (dx <= 2))
    taps = w[:, np.clip(dx, 0, 2)]          # (3, W, 2*half, Cin, Cout)
    taps = jnp.where(valid[None, :, :, None, None], taps,
                     jnp.zeros((), w.dtype))
    taps = taps.transpose(0, 1, 3, 2, 4).reshape(
        3, width * cin, half, 2, cout)
    return taps.transpose(0, 3, 1, 2, 4).reshape(
        3, 2, width * cin, half * cout)


def _stage0_operands(params, qparams, res: int, cin: int):
    """Kernel operands for the stage-0 CNN at input ``res`` with ``cin``
    channels. Returns (tensors, scales, layer dims, (hp, flat lanes)):
    per conv layer a band stack and a tiled bias, then the dense weights
    split per pooled row, dense bias, output weights and bias. ``scales``
    holds the per-weight-tensor dequant scales on the int8 path, else
    None."""
    quant = qparams is not None
    p = qparams if quant else params

    def wt(t):
        return t["q"] if quant else jnp.asarray(t, jnp.float32)

    tensors, scales, dims = [], [], []
    size, ch = res, cin
    for layer in p["conv"]:
        w = wt(layer["w"])
        cout = int(w.shape[-1])
        tensors += [_conv_bands(w, size),
                    jnp.tile(jnp.asarray(layer["b"], jnp.float32),
                             size // 2).reshape(1, -1)]
        if quant:
            scales.append(float(layer["w"]["scale"]))
        dims.append((size, size, ch, cout))
        size, ch = size // 2, cout
    dense = wt(p["dense_w"])
    tensors += [dense.reshape(size, size * ch, dense.shape[-1]),
                jnp.reshape(p["dense_b"], (1, -1)).astype(jnp.float32),
                wt(p["out_w"]),
                jnp.reshape(p["out_b"], (1, 1)).astype(jnp.float32)]
    if quant:
        scales += [float(p["dense_w"]["scale"]), float(p["out_w"]["scale"])]
    return tensors, (tuple(scales) if quant else None), tuple(dims), \
        (size, size * ch)


def _conv_relu_pool(x, band_ref, bias_ref, shift_ref, pool_ref, dims,
                    weight):
    """relu(conv3x3-SAME(x) + b) then 2x2 max-pool, one image.
    x (H, W*Cin) -> (H//2, (W//2)*Cout). Output row h sums kernel rows
    dy = 0, 1, 2 applied to input rows h-1, h, h+1: rows shift through
    ``shift_ref`` with zero rows at both ends (the SAME padding)."""
    h, w, _, cout = dims
    lanes = (w // 2) * cout
    zero_row = jnp.zeros((1, lanes), jnp.float32)
    bias = bias_ref[...]
    parity = []
    for par in (0, 1):
        above = _dot(x, weight(band_ref, (0, par)))
        acc = _dot(x, weight(band_ref, (1, par)))
        below = _dot(x, weight(band_ref, (2, par)))
        shift_ref[pl.ds(0, 1), :lanes] = zero_row
        shift_ref[pl.ds(h + 1, 1), :lanes] = zero_row
        shift_ref[pl.ds(1, h), :lanes] = above
        acc = acc + shift_ref[pl.ds(0, h), :lanes]
        shift_ref[pl.ds(1, h), :lanes] = below
        acc = acc + shift_ref[pl.ds(2, h), :lanes]
        parity.append(jnp.maximum(acc + bias, 0.0))
    _store_rows(pool_ref, jnp.maximum(parity[0], parity[1]))
    half = h // 2
    return jnp.maximum(_load_rows(pool_ref, lanes, 0, half, 2),
                       _load_rows(pool_ref, lanes, 1, half, 2))


def _pyramid_stage0_kernel(x_ref, *refs, tb: int, pooled, out_res,
                           s0_res: int, project: bool, conv_dims, flat,
                           scales):
    """refs = (pool_0.., [proj], band_0, bias_0, .., dense_w, dense_b,
    out_w, out_b, level_out_0.., logit_out, rows, shift, pool, flat
    scratch). Images of the step run one at a time through pooling and
    the conv layers; their pooled feature rows meet in ``flat_ref`` so
    the dense head runs as (tb, .) dots over the whole step."""
    n_p, n_c = len(pooled), len(conv_dims)
    pool_refs = refs[:n_p]
    k = n_p
    proj_ref = None
    if project:
        proj_ref, k = refs[k], k + 1
    conv_refs = refs[k:k + 2 * n_c]
    dense_w, dense_b, out_w, out_b = refs[k + 2 * n_c:k + 2 * n_c + 4]
    k += 2 * n_c + 4
    level_refs = refs[k:k + len(out_res)]
    logit_ref, rows_ref, shift_ref, mpool_ref, flat_ref = \
        refs[k + len(out_res):]
    hp, flat_lanes = flat

    def weight(ref, idx, si=None):
        w = ref[idx]
        if scales is not None:
            w = w.astype(jnp.float32) * scales[si]
        return w

    def one_image(t, carry):
        levels = _pool_levels(x_ref[t], pool_refs, rows_ref, pooled)
        for i, res in enumerate(out_res):
            level_refs[i][t] = levels[res]
        x = levels[s0_res]
        if project:
            x = _dot(x, proj_ref[...])
        for li, d in enumerate(conv_dims):
            x = _conv_relu_pool(
                x, conv_refs[2 * li], conv_refs[2 * li + 1], shift_ref,
                mpool_ref, d,
                lambda ref, idx, _li=li: weight(ref, idx, _li))
        _store_rows(flat_ref, x, t * hp)
        return carry

    jax.lax.fori_loop(0, tb, one_image, 0)

    n_conv = len(conv_dims)
    hid = dense_b[...]
    for r in range(hp):
        hid = hid + _dot(_load_rows(flat_ref, flat_lanes, r, tb, hp),
                         weight(dense_w, r, n_conv))
    hid = jnp.maximum(hid, 0.0)
    logit = _dot(hid, weight(out_w, ..., n_conv + 1)) + out_b[...]
    logit_ref[...] = jnp.broadcast_to(logit, (tb, _LANES))


def fused_pyramid_stage0(images, out_res, params, rep, *, qparams=None,
                         interpret: bool | None = None):
    """ONE Pallas pass per group of images: raw RGB (B, H, H, 3) float32
    -> ({res: (B, res, res, 3) raw pooled RGB level for res in out_res},
     stage-0 sigmoid scores (B,)).

    Levels are the engine's carry currency — raw [0,1] pooled RGB, bit-
    identical to core.transforms.materialize_pyramid for uint8-valued
    pixels (NOT the normalized projected reps fused_pyramid_transform
    emits). ``rep`` names the stage-0 model's input representation; its
    resolution is pooled in VMEM even when not in ``out_res``.
    ``qparams`` (models/cnn.quantize_cnn output) selects the int8 weight
    path."""
    b, h, w, _ = images.shape
    assert h == w, (h, w)
    out_res = [int(r) for r in out_res]
    s0_res = int(rep.resolution)
    plan_pyramid(set(out_res) | {s0_res}, h)     # validates nesting
    pooled = tuple(sorted((set(out_res) | {s0_res}) - {h}, reverse=True))
    tensors, scales, conv_dims, flat = _stage0_operands(
        params, qparams, s0_res, rep.channels)
    project = rep.color != "rgb"
    pools = [jnp.asarray(_col_pool_matrix(h, r)) for r in pooled]
    proj = ([jnp.kron(jnp.eye(s0_res, dtype=jnp.float32),
                      jnp.asarray(color_weight_matrix(rep.color)))]
            if project else [])
    tb = IMAGES_PER_STEP
    x, bp = _pad_batch(images, tb)
    rows_lanes = max([r * 3 for r in pooled], default=_LANES)
    conv_h = max([d[0] for d in conv_dims], default=8)
    conv_lanes = max([(d[1] // 2) * d[3] for d in conv_dims],
                     default=_LANES)
    scratch = [_row_scratch(h, rows_lanes),
               pltpu.VMEM((conv_h + 2, conv_lanes), jnp.float32),
               _row_scratch(conv_h, conv_lanes),
               _row_scratch(tb * flat[0], flat[1])]
    operands = pools + proj + tensors
    block = (_nbytes((tb, h, w * 3)) + _nbytes((tb, _LANES))
             + sum(_nbytes((tb, r, r * 3)) for r in out_res))
    resident = (sum(_nbytes(a.shape, a.dtype.itemsize) for a in operands)
                + sum(_nbytes(s.shape) for s in scratch))
    kernel = functools.partial(
        _pyramid_stage0_kernel, tb=tb, pooled=pooled,
        out_res=tuple(out_res), s0_res=s0_res, project=project,
        conv_dims=conv_dims, flat=flat, scales=scales)
    out = pl.pallas_call(
        kernel,
        grid=(bp // tb,),
        in_specs=([pl.BlockSpec((tb, h, w * 3), lambda i: (i, 0, 0))]
                  + [_resident() for _ in operands]),
        out_specs=([pl.BlockSpec((tb, r, r * 3), lambda i: (i, 0, 0))
                    for r in out_res]
                   + [pl.BlockSpec((tb, _LANES), lambda i: (i, 0))]),
        out_shape=([jax.ShapeDtypeStruct((bp, r, r * 3), jnp.float32)
                    for r in out_res]
                   + [jax.ShapeDtypeStruct((bp, _LANES), jnp.float32)]),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(block, resident)),
        interpret=resolve_interpret(interpret),
        name="fused_pyramid_stage0",
    )(x, *operands)
    levels = {r: out[i][:b].reshape(b, r, r, 3)
              for i, r in enumerate(out_res)}
    return levels, jax.nn.sigmoid(out[-1][:b, 0])


def stage0_fits(stage0, base: int, out_res=(), int8: bool = False) -> bool:
    """Whether fused_pyramid_stage0 can hold ``stage0`` (core/executor.
    Stage0) in VMEM at this base resolution. The epilogue's banded conv
    weights grow with width^2 x channels: the reduced grid's models on
    28-112 px inputs fit, a 224 px trusted CNN does not. Shape-only:
    traces the wrapper, compiles nothing."""
    qparams = stage0.qparams if int8 else None
    images = jax.ShapeDtypeStruct((IMAGES_PER_STEP, base, base, 3),
                                  jnp.float32)
    try:
        jax.eval_shape(lambda x: fused_pyramid_stage0(
            x, out_res, stage0.params, stage0.rep, qparams=qparams,
            interpret=True), images)
    except VmemOverflow:
        return False
    return True
