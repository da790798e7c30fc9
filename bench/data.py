"""Inputs made from the seed: frames, weights, thresholds, cascades.

Frames are uint8-valued RGB (``k / 256``, k in 0..255), made on the device
in blocks and copied into one host float32 array, the form the ingest
pipeline takes. Row ``i`` depends only on the seed and ``i``, so the
reference can make any block again on the device without a copy.

Weights are made in one jitted call, in float32, the type they are served
in. Each predicate's cuts ``(p_low, p_high)`` are quantiles of its scores
on calibration frames, scored by the plain reference. The program
compiles its weights and thresholds into its programs as constants, so
both come from the configuration's ``weights_seed`` and not from the
run's seed: every run then finds every program in the compile cache, and
the run's seed draws the frames.
"""
from __future__ import annotations

import math
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from functools import partial

import numpy as np

COLORS = {"rgb": 3, "gray": 1, "r": 1, "g": 1, "b": 1}


# ------------------------------------------------------------- frames --
def _frame(key, row, hw: int, rects: int):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.fold_in(key, row), 8)
    base = jax.random.uniform(ks[7], (3,), minval=0.2, maxval=0.8)
    yy, xx = jnp.meshgrid(jnp.arange(hw) / hw, jnp.arange(hw) / hw,
                          indexing="ij")
    ang = jax.random.uniform(ks[1]) * 2 * math.pi
    slope = jax.random.uniform(ks[2], minval=-0.4, maxval=0.4)
    grad = slope * ((xx - 0.5) * jnp.cos(ang) + (yy - 0.5) * jnp.sin(ang))
    img = base[None, None, :] + grad[..., None]
    centre = jax.random.uniform(ks[3], (rects, 2))
    half = jax.random.uniform(ks[4], (rects, 2), minval=0.03, maxval=0.25)
    colour = jax.random.uniform(ks[5], (rects, 3))
    for j in range(rects):
        inside = ((jnp.abs(xx - centre[j, 0]) < half[j, 0])
                  & (jnp.abs(yy - centre[j, 1]) < half[j, 1]))
        img = jnp.where(inside[..., None], colour[j], img)
    img = img + 0.04 * jax.random.normal(ks[6], (hw, hw, 3))
    return jnp.clip(jnp.floor(img * 256.0), 0, 255).astype(jnp.uint8)


_GEN: dict = {}


def frame_block(key, rows, hw: int, rects: int = 6):
    """uint8 frames of ``rows`` on the device as one 1-D array (it copies
    to the host without a re-layout), one program per (block, hw)."""
    import jax

    fkey = (len(rows), hw, rects)
    if fkey not in _GEN:
        gen = jax.vmap(partial(_frame, hw=hw, rects=rects),
                       in_axes=(None, 0))
        _GEN[fkey] = jax.jit(lambda *a: gen(*a).reshape(-1))
    return _GEN[fkey](key, rows)


def data_key(seed: int):
    """The seed's frame key. Seeds run past 32 bits, so both halves go in."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def weight_key(seed: int):
    import jax

    return jax.random.fold_in(data_key(seed), 0x5EED)


def make_frames(seed: int, n: int, hw: int, *, block: int = 256,
                threads: int = 4) -> np.ndarray:
    """Host float32 (n, hw, hw, 3) frames; blocks are made on the device
    while earlier ones are copied out and widened on host threads."""
    import jax.numpy as jnp

    key = data_key(seed)
    out = np.empty((n, hw, hw, 3), np.float32)
    scale = np.float32(1.0 / 256.0)

    def land(dev, lo, hi):
        u8 = np.asarray(dev).reshape(block, hw, hw, 3)
        np.multiply(u8[:hi - lo], scale, out=out[lo:hi])

    with ThreadPoolExecutor(threads) as pool:
        pending: deque = deque()
        for lo in range(0, n, block):
            hi = min(lo + block, n)
            rows = np.arange(lo, lo + block)
            rows[hi - lo:] = hi - 1
            dev = frame_block(key, jnp.asarray(rows, jnp.int32), hw)
            pending.append(pool.submit(land, dev, lo, hi))
            while len(pending) > 2 * threads:
                pending.popleft().result()
        for fut in pending:
            fut.result()
    return out


def device_frames(seed: int, rows: np.ndarray, hw: int):
    """Frames ``rows`` as device float32, made again from the seed (the
    reference's input; the same frames the program got, bit for bit)."""
    import jax.numpy as jnp

    u8 = frame_block(data_key(seed), jnp.asarray(rows, jnp.int32), hw)
    return (u8.astype(jnp.float32) * (1.0 / 256.0)).reshape(
        len(rows), hw, hw, 3)


# ------------------------------------------------------------ weights --
def level_shapes(level: dict) -> dict:
    """Parameter shapes of one cascade level's CNN
    ([conv3x3 -> relu -> maxpool2] x L -> dense relu -> 1 logit)."""
    hw, cin = level["resolution"], COLORS[level["color"]]
    conv = []
    for _ in range(level["conv_layers"]):
        conv.append(((3, 3, cin, level["conv_nodes"]),
                     (level["conv_nodes"],)))
        cin, hw = level["conv_nodes"], hw // 2
    flat = hw * hw * cin
    return {"conv": conv, "dense": (flat, level["dense_nodes"]),
            "out": (level["dense_nodes"], 1)}


def _init_level(key, shapes):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(key, len(shapes["conv"]) + 2)
    conv = []
    for k, (w, b) in zip(ks, shapes["conv"]):
        fan_in = w[0] * w[1] * w[2]
        conv.append({"w": jax.random.normal(k, w) * (2.0 / fan_in) ** 0.5,
                     "b": jnp.zeros(b, jnp.float32)})
    flat, dn = shapes["dense"]
    return {"conv": conv,
            "dense_w": jax.random.normal(ks[-2], (flat, dn))
            * (2.0 / flat) ** 0.5,
            "dense_b": jnp.zeros((dn,), jnp.float32),
            "out_w": jax.random.normal(ks[-1], (dn, 1)) * (1.0 / dn) ** 0.5,
            "out_b": jnp.zeros((1,), jnp.float32)}


def make_weights(seed: int, predicates: list) -> list:
    """Every level of every predicate, float32, in one jitted call:
    ``[[params of level 0, ...] per predicate]`` in the layout
    ``models/cnn.cnn_forward`` reads."""
    import jax

    shapes = [[level_shapes(lv) for lv in p["levels"]] for p in predicates]

    def init(key):
        out = []
        for pi, pshapes in enumerate(shapes):
            pk = jax.random.fold_in(key, pi)
            out.append([_init_level(jax.random.fold_in(pk, li), s)
                        for li, s in enumerate(pshapes)])
        return out
    return jax.jit(init)(weight_key(seed))


# --------------------------------------------------------------- cuts --
def _between(sorted_s: np.ndarray, q: float) -> np.float32:
    """A cut that leaves the share ``q`` of ``sorted_s`` below it, halfway
    between two neighbouring values so no calibration row sits on it."""
    n = len(sorted_s)
    k = min(max(int(round(q * n)), 1), n - 1)
    return np.float32((np.float64(sorted_s[k - 1])
                       + np.float64(sorted_s[k])) / 2)


def quantile_cuts(scores: np.ndarray, low: float, high: float
                  ) -> tuple[np.float32, np.float32]:
    """``(p_low, p_high)`` that leave the shares ``low`` and ``1 - high``
    of the calibration ``scores`` at or below and at or above them."""
    s = np.sort(np.asarray(scores, np.float32))
    return _between(s, low), _between(s, high)


# ----------------------------------------------------------- cascades --
def build_cascade(index: int, predicate: dict, params: list, cuts):
    """The program's executable first level of one predicate's cascade,
    built from public constructors as ``TahomaSystem.compiled_cascade``
    builds a cascade: its model, its cuts, and the kernel-foldable copy
    of its weights (float32; the int8 path is off)."""
    from repro.core.executor import Stage0
    from repro.core.transforms import Representation
    from repro.engine.scan import CompiledCascade
    from repro.models.cnn import cnn_predict_proba

    reps = [Representation(lv["resolution"], lv["color"])
            for lv in predicate["levels"]]
    lo, hi = cuts
    return CompiledCascade(
        concept=predicate["name"], cascade_id=(index,), reps=reps,
        model_fns=[partial(cnn_predict_proba, p) for p in params],
        thresholds=[(float(lo), float(hi))], selectivity=0.5,
        stage0=Stage0(params=params[0], rep=reps[0]))
