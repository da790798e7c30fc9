"""The plain reference: what the ingest-time index holds for every frame.

Written from the semantics alone, in straightforward ``jax.numpy`` and
``numpy``, importing nothing of the program.

* Scores: a level pools the raw frame straight from the base by a box
  filter, projects its colour, runs [conv3x3 SAME -> relu -> maxpool2] x L
  -> dense relu -> one logit, and the sigmoid gives its score (a 2x2
  max-pool of an odd size drops the last row and column).
* The skip detector: a frame's signature is the box mean, over an
  ``res`` x ``res`` grid, of its channel mean. A frame is a duplicate when
  the mean absolute difference between its signature and the previous
  frame's is at most the threshold; a duplicate points at the last frame
  that was not one, and is not scored.
* Each scored frame, for each predicate with cuts ``(p_low, p_high)``:
  decided when its score is at most ``p_low`` (label 0) or at least
  ``p_high`` (label 1); a candidate when it is decided 1, or undecided
  with a score above ``tau = p_low + margin * max(0.5 - p_low, 0)``.

Precision ``"highest"`` is what the configuration states (float32 at
``Precision.HIGHEST``). The controls: ``"high"`` makes the same products
in three bfloat16 passes (hi*hi + hi*lo + lo*hi, each exact in float32),
``"bf16"`` in one (hi*hi), both split by bit mask so that the same
numbers come out on any backend.
"""
from __future__ import annotations

from functools import partial

import numpy as np

GRAY = np.array([0.299, 0.587, 0.114], np.float32)


def pool(x, res: int):
    b, h, w, c = x.shape
    f = h // res
    return x.reshape(b, res, f, res, f, c).mean(axis=(2, 4))


def project(x, color: str):
    import jax.numpy as jnp

    if color == "rgb":
        return x
    if color == "gray":
        return (x * jnp.asarray(GRAY)).sum(-1, keepdims=True)
    i = "rgb".index(color)
    return x[..., i:i + 1]


def _bf16_part(x):
    """x cut to its top 16 bits: a bfloat16 value held in float32. Cut by
    bit mask, so no compiler may fold the rounding away."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _split(x):
    """x = hi + lo + O(2**-15 x), both parts bfloat16 values in float32."""
    hi = _bf16_part(x)
    return hi, _bf16_part(x - hi)


def _product(op, a, b, precision: str):
    import jax

    exact = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return op(a, b, exact)
    if precision == "bf16":
        return op(_bf16_part(a), _bf16_part(b), exact)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    ah, al = _split(a)
    bh, bl = _split(b)
    return op(ah, bh, exact) + op(ah, bl, exact) + op(al, bh, exact)


def _conv(x, w, p):
    import jax

    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=p)


def _dot(a, b, p):
    import jax.numpy as jnp

    return jnp.dot(a, b, precision=p)


def forward(params, x, precision: str = "highest"):
    """Logits (B,) of one CNN level on its input representation."""
    import jax

    h = x
    for layer in params["conv"]:
        h = jax.nn.relu(_product(_conv, h, layer["w"], precision)
                        + layer["b"])
        # 2x2 windows that do not overlap; an odd last row or column
        # falls outside every window
        b, hh, ww, c = h.shape
        h = h[:, :hh // 2 * 2, :ww // 2 * 2]
        h = h.reshape(b, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(_product(_dot, h, params["dense_w"], precision)
                    + params["dense_b"])
    return (_product(_dot, h, params["out_w"], precision)
            + params["out_b"])[:, 0]


def _first_levels(params, frames, *, predicates, precision, sig_res):
    import jax

    scores = [jax.nn.sigmoid(forward(
        pp[0], project(pool(frames, p["levels"][0]["resolution"]),
                       p["levels"][0]["color"]), precision))
        for p, pp in zip(predicates, params)]
    return scores, pool(frames.mean(-1, keepdims=True), sig_res)[..., 0]


_FNS: dict = {}


def _levels_fn(predicates: list, precision: str, sig_res: int):
    import jax

    key = (repr(predicates), precision, sig_res)
    if key not in _FNS:
        _FNS[key] = jax.jit(partial(_first_levels, predicates=predicates,
                                    precision=precision, sig_res=sig_res))
    return _FNS[key]


def score_frames(frames_of, n: int, block: int, predicates: list, params,
                 precision: str = "highest", sig_res: int = 8):
    """First-level scores (P, n) of frames 0..n for every predicate, and
    their detector signatures (n, sig_res, sig_res). ``frames_of(lo, hi)``
    gives those frames, padded to ``block``, on the device."""
    fn = _levels_fn(predicates, precision, sig_res)
    scores = [[] for _ in predicates]
    sigs = []
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        s, g = fn(params, frames_of(lo, hi))
        for acc, v in zip(scores, s):
            acc.append(np.asarray(v)[:hi - lo])
        sigs.append(np.asarray(g)[:hi - lo])
    return (np.stack([np.concatenate(a) for a in scores]),
            np.concatenate(sigs))


def stream_diffs(sigs: np.ndarray) -> np.ndarray:
    """Each frame's signature difference from the previous frame of the
    stream (``inf`` for the first)."""
    d = np.full(len(sigs), np.inf)
    d[1:] = np.abs(sigs[1:].astype(np.float64)
                   - sigs[:-1].astype(np.float64)).mean(axis=(1, 2))
    return d


def aliases(diffs: np.ndarray, threshold: float) -> np.ndarray:
    """The frame each stream frame points at: itself, or for a duplicate
    the last frame that was not one."""
    out = np.arange(len(diffs), dtype=np.int64)
    last = 0
    for i in np.flatnonzero(diffs > threshold):
        out[last:i] = last
        last = i
    out[last:] = last
    return out


def tau(p_low: float, margin: float) -> float:
    return p_low + margin * max(0.5 - p_low, 0.0)


def index_answers(alias: np.ndarray, scores: np.ndarray, cuts: list,
                  margin: float) -> dict:
    """What the index holds for each stream frame: ``scores`` (P, F) of
    each frame's own pixels are kept for the frames that point at
    themselves (NaN for duplicates), with each predicate's decided label
    (-1 undecided or duplicate) and candidate flag."""
    own = alias == np.arange(len(alias))
    s = np.where(own[None, :], scores, np.nan).astype(np.float32)
    decided = np.full(s.shape, -1, np.int8)
    cand = np.zeros(s.shape, bool)
    for k, (lo, hi) in enumerate(cuts):
        x = s[k]
        zero, one = own & (x <= lo), own & (x >= hi)
        decided[k, zero] = 0
        decided[k, one] = 1
        cand[k] = (own & (x > tau(lo, margin)) & ~zero) | one
    return {"alias": alias, "scores": s, "decided": decided,
            "candidate": cand}


def answer_gaps(got: dict, want: dict, diffs: np.ndarray, threshold: float,
                cuts: list, margin: float) -> np.ndarray:
    """Per stream frame, the widest gap of any answer the index holds from
    the reference's: a score's distance from the reference's score; a
    decided label or candidate flag that differs, the distance from the
    reference's score to the cut that decides it (the least score error
    that could explain it); a skip decision that differs, the distance of
    the reference's signature difference from the threshold. A frame the
    index never saw, or a score that should be there and is not, is 1."""
    n = len(want["alias"])
    gap = np.zeros(n)
    own = want["alias"] == np.arange(n)
    skip = got["alias"] != want["alias"]
    gap[skip] = np.minimum(np.abs(diffs[skip] - threshold), 1.0)
    s = want["scores"]
    with np.errstate(invalid="ignore"):
        for k, (lo, hi) in enumerate(cuts):
            near = np.minimum(np.abs(s[k] - lo), np.abs(s[k] - hi))
            near_c = np.minimum(near, np.abs(s[k] - tau(lo, margin)))
            d = np.abs(got["scores"][k].astype(np.float64) - s[k])
            d = np.where(own, np.where(np.isnan(d), 1.0, d),
                         np.where(np.isnan(got["scores"][k]), 0.0, 1.0))
            d = np.where(own & (got["decided"][k] != want["decided"][k]),
                         np.maximum(d, np.where(own, near, 1.0)), d)
            bad_c = got["candidate"][k] != want["candidate"][k]
            d = np.where(bad_c, np.maximum(d, np.where(own, near_c, 1.0)),
                         d)
            d = np.where(~own & (got["decided"][k] != -1), 1.0, d)
            gap = np.where(skip, gap, np.maximum(gap, d))
    if "indexed" in got:
        gap[~got["indexed"]] = 1.0
    return gap
