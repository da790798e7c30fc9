"""MoonViT, Kimi-VL's vision tower (configs/kimi_vl_a3b.MoonViTConfig).

Images (B, H, W, 3) in [-1, 1], H = W = ``image_hw``, become the
decoder's image tokens (B, (H/patch/merge)^2, d_text):

* patch embedding: a patch x patch stride-patch conv, as one matmul of
  each patch's (row, column, channel) values, with bias;
* plus the learned pos_grid x pos_grid position table, interpolated to
  the patch grid (bicubic, PyTorch's ``F.interpolate`` convention:
  a = -0.75, half-pixel centres, edges clamped, no antialiasing);
* ``n_layers`` pre-LayerNorm blocks under ``lax.scan``: attention with
  qkv bias and 2D RoPE on q and k, bidirectional within each image, then
  a tanh-GELU MLP;
* a final LayerNorm, the merge x merge patch merge, and the projector:
  LayerNorm over each patch, Linear 4d -> 4d, GELU, Linear 4d -> d_text.

2D RoPE: each head's dimensions rotate in adjacent pairs; pair j turns by
``x * f[j // 2]`` for even j and ``y * f[j // 2]`` for odd j (x the
patch's column, y its row), ``f[i] = theta ** (-4 i / head_dim)``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import dense_init


def _layernorm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _ln_params(d):
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def init_moonvit(key, cfg, d_text: int):
    d, f, p = cfg.d_model, cfg.d_ff, cfg.patch
    ks = jax.random.split(key, 8)

    def layer(k):
        kk = jax.random.split(k, 4)
        return {"ln1": _ln_params(d), "ln2": _ln_params(d),
                "wqkv": dense_init(kk[0], (d, 3 * d), 0, jnp.float32),
                "bqkv": jnp.zeros((3 * d,), jnp.float32),
                "wo": dense_init(kk[1], (d, d), 0, jnp.float32),
                "bo": jnp.zeros((d,), jnp.float32),
                "w1": dense_init(kk[2], (d, f), 0, jnp.float32),
                "b1": jnp.zeros((f,), jnp.float32),
                "w2": dense_init(kk[3], (f, d), 0, jnp.float32),
                "b2": jnp.zeros((d,), jnp.float32)}

    m = cfg.merge * cfg.merge * d
    return {"patch_w": dense_init(ks[0], (p * p * 3, d), 0, jnp.float32),
            "patch_b": jnp.zeros((d,), jnp.float32),
            "pos": 0.02 * jax.random.normal(
                ks[1], (cfg.pos_grid, cfg.pos_grid, d), jnp.float32),
            "layers": jax.vmap(layer)(jax.random.split(ks[2], cfg.n_layers)),
            "final_ln": _ln_params(d),
            "proj": {"ln": _ln_params(d),
                     "w1": dense_init(ks[3], (m, m), 0, jnp.float32),
                     "b1": jnp.zeros((m,), jnp.float32),
                     "w2": dense_init(ks[4], (m, d_text), 0, jnp.float32),
                     "b2": jnp.zeros((d_text,), jnp.float32)}}


def bicubic_matrix(n_out: int, n_in: int, a: float = -0.75) -> np.ndarray:
    """(n_out, n_in) rows of 1-D bicubic interpolation weights."""
    out = np.zeros((n_out, n_in), np.float64)
    scale = n_in / n_out
    for i in range(n_out):
        src = (i + 0.5) * scale - 0.5
        lo = math.floor(src)
        t = src - lo
        for j, dist in zip(range(lo - 1, lo + 3), (t + 1, t, 1 - t, 2 - t)):
            dist = abs(dist)
            if dist <= 1:
                w = ((a + 2) * dist - (a + 3)) * dist * dist + 1
            else:
                w = ((a * dist - 5 * a) * dist + 8 * a) * dist - 4 * a
            out[i, min(max(j, 0), n_in - 1)] += w
    return out.astype(np.float32)


def rope_2d(grid: int, head_dim: int, theta: float):
    """cos, sin (grid*grid, head_dim//2) for row-major patches."""
    freqs = theta ** (-np.arange(0, head_dim, 4)[: head_dim // 4]
                      / head_dim)
    y, x = np.divmod(np.arange(grid * grid), grid)
    ang = np.stack([np.outer(x, freqs), np.outer(y, freqs)], -1).reshape(
        grid * grid, head_dim // 2)
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def rotate_pairs(x, cos, sin):
    """x (..., N, H, D): adjacent pairs (2j, 2j+1) turned by angle j."""
    x0, x1 = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([x0 * c - x1 * s, x1 * c + x0 * s], -1).reshape(x.shape)


def patchify(images, patch: int):
    """(B, H, W, C) -> (B, (H/p)*(W/p), p*p*C), row-major patches."""
    b, h, w, c = images.shape
    x = images.reshape(b, h // patch, patch, w // patch, patch, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        b, (h // patch) * (w // patch), patch * patch * c)


def _block(lp, x, cfg, cos, sin):
    b, n, d = x.shape
    nh, dh = cfg.n_heads, cfg.head_dim
    h = _layernorm(x, lp["ln1"], cfg.norm_eps)
    qkv = (h @ lp["wqkv"] + lp["bqkv"]).reshape(b, n, 3, nh, dh)
    q = rotate_pairs(qkv[:, :, 0], cos, sin)
    k = rotate_pairs(qkv[:, :, 1], cos, sin)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * dh ** -0.5
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), qkv[:, :, 2])
    x = x + ctx.reshape(b, n, d) @ lp["wo"] + lp["bo"]
    h = _layernorm(x, lp["ln2"], cfg.norm_eps)
    h = jax.nn.gelu(h @ lp["w1"] + lp["b1"], approximate=True)
    return x + h @ lp["w2"] + lp["b2"]


def moonvit(params, images, cfg):
    """Images (B, H, W, 3) in [-1, 1] -> image tokens (B, T, d_text)."""
    g = cfg.grid
    x = patchify(images, cfg.patch) @ params["patch_w"] + params["patch_b"]
    m = jnp.asarray(bicubic_matrix(g, cfg.pos_grid))
    pos = jnp.einsum("yi,xj,ijd->yxd", m, m, params["pos"])
    x = x + pos.reshape(g * g, cfg.d_model)
    cos, sin = rope_2d(g, cfg.head_dim, cfg.rope_theta)

    def step(h, lp):
        return _block(lp, h, cfg, cos, sin), None

    x, _ = jax.lax.scan(step, x, params["layers"])
    x = _layernorm(x, params["final_ln"], cfg.norm_eps)
    b, r = x.shape[0], cfg.merge
    gm = g // r
    x = x.reshape(b, gm, r, gm, r, cfg.d_model).transpose(0, 1, 3, 2, 4, 5)
    pp = params["proj"]
    x = _layernorm(x, pp["ln"], cfg.norm_eps).reshape(
        b, gm * gm, r * r * cfg.d_model)
    x = jax.nn.gelu(x @ pp["w1"] + pp["b1"], approximate=False)
    return x @ pp["w2"] + pp["b2"]
