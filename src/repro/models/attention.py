"""Attention: GQA/MQA (optional QKV bias), MLA (DeepSeek-V2), M-RoPE,
cross-attention, chunked (jnp-flash) prefill, cache decode.

Tensor-parallel head padding
----------------------------
The production mesh has a 16-wide 'model' axis, but several assigned archs
have head counts not divisible by 16 (qwen2.5: 40, minitron: 24, whisper: 6).
We pad the *q-head* axis per KV group so (a) the padded head count shards,
(b) the original q->kv group mapping is preserved, and (c) numerics are
exactly preserved by zero-masking padded heads' outputs before w_o (so their
grads are exactly zero too). MHA (group size 1) pads q and kv together.
If no layout is found the layout degrades to no padding (replicated heads).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.common import (apply_rope, dense_init, pdtype, rmsnorm_vec)
from repro.sharding import policy as _policy


class HeadLayout(NamedTuple):
    n_q: int          # true q heads
    n_kv: int         # true kv heads
    hp: int           # padded q heads
    khp: int          # padded kv heads
    gp: int           # padded group size (hp // khp)

    @property
    def q_mask(self):
        """(hp,) 1.0 for real q heads."""
        if self.khp == self.n_kv:     # per-group padding
            g = self.n_q // self.n_kv
            return ((jnp.arange(self.hp) % self.gp) < g).astype(jnp.float32)
        return (jnp.arange(self.hp) < self.n_q).astype(jnp.float32)

    def q_head_is_real(self, i: int) -> bool:
        if self.khp == self.n_kv:
            g = self.n_q // self.n_kv
            return (i % self.gp) < g
        return i < self.n_q


def head_layout(n_q: int, n_kv: int, pad_to: int) -> HeadLayout:
    if pad_to <= 1 or n_q % pad_to == 0:
        return HeadLayout(n_q, n_kv, n_q, n_kv, n_q // max(n_kv, 1))
    g = n_q // n_kv
    if g == 1:  # MHA: pad q and kv in lockstep (mapping i -> i preserved)
        hp = ((n_q + pad_to - 1) // pad_to) * pad_to
        return HeadLayout(n_q, n_kv, hp, hp, 1)
    for gp in range(g, 64 * g):
        if (n_kv * gp) % pad_to == 0:
            return HeadLayout(n_q, n_kv, n_kv * gp, n_kv, gp)
    return HeadLayout(n_q, n_kv, n_q, n_kv, g)  # fallback: no padding


def layout_from_cfg(cfg) -> HeadLayout:
    return head_layout(cfg.n_heads, cfg.n_kv_heads, cfg.head_pad_to)


# ------------------------------------------------------------------ GQA ----
def init_gqa(key, cfg, cross: bool = False):
    lo = layout_from_cfg(cfg)
    d, dh = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 4)
    dt = pdtype(cfg)
    p = {
        "wq": dense_init(ks[0], (d, lo.hp * dh), 0, dt),
        "wk": dense_init(ks[1], (d, lo.khp * dh), 0, dt),
        "wv": dense_init(ks[2], (d, lo.khp * dh), 0, dt),
        "wo": dense_init(ks[3], (lo.hp * dh, d), 0, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((lo.hp * dh,), dt)
        p["bk"] = jnp.zeros((lo.khp * dh,), dt)
        p["bv"] = jnp.zeros((lo.khp * dh,), dt)
    return p


def gqa_qkv(p, x, cfg, rope=None, kv_x=None):
    """Project to q (B,S,hp,dh) and k,v (B,T,khp,dh); apply rope if given.
    kv_x: source for k/v (cross-attention uses encoder states)."""
    lo = layout_from_cfg(cfg)
    b, s, _ = x.shape
    src = x if kv_x is None else kv_x
    t = src.shape[1]
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", src, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", src, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, lo.hp, cfg.head_dim)
    k = k.reshape(b, t, lo.khp, cfg.head_dim)
    v = v.reshape(b, t, lo.khp, cfg.head_dim)
    # NOTE (EXPERIMENTS.md §Perf cell C, iter C3 — refuted): re-sharding
    # K/V to batch-only here to avoid sub-head partial-score reduces was
    # measured WORSE (+0.7s collective) than letting SPMD keep half-head
    # shards; the constraint was removed again.
    if rope is not None:
        cos_q, sin_q, cos_k, sin_k = rope
        q = apply_rope(q, cos_q, sin_q)
        k = apply_rope(k, cos_k, sin_k)
    return q, k, v


def repeat_kv(k, gp: int):
    """(B,T,khp,dh) -> (B,T,khp*gp,dh) by broadcast (no copy until use)."""
    if gp == 1:
        return k
    b, t, kh, dh = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, t, kh, gp, dh)) \
              .reshape(b, t, kh * gp, dh)


def sdpa(q, k, v, *, causal: bool, q_positions=None, k_positions=None,
         k_valid=None, visible=None, gp: int = 1):
    """GQA-grouped scaled-dot-product attention.
    q (B,S,H,dh); k/v (B,T,KH,dh) with H = KH*gp -> out (B,S,H,dh).
    ``visible`` (S,T) bool, if given, hides from query s each key t it
    holds False for, on top of ``causal`` and ``k_valid``.

    KV heads are NEVER materialized repeated: q is regrouped to
    (B,S,KH,gp,dh) and contracted against k/v directly. Besides avoiding
    the gp x KV copy, this keeps SPMD sharding propagation intact when the
    cache is sequence-sharded (a broadcast+reshape here forced XLA into
    'involuntary full rematerialization' = a full cache all-gather per
    layer — EXPERIMENTS.md §Perf cell A, iteration A2)."""
    b, s, h, dh = q.shape
    kh = k.shape[2]
    assert h == kh * gp, (h, kh, gp)
    qg = q.reshape(b, s, kh, gp, dh)
    scale = dh ** -0.5
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) \
        * scale
    neg = jnp.finfo(jnp.float32).min
    if causal:
        qp = q_positions if q_positions is not None else jnp.arange(s)
        kp = k_positions if k_positions is not None else jnp.arange(
            k.shape[1])
        if qp.ndim == 1:
            mask = qp[:, None] < kp[None, :]
            scores = jnp.where(mask[None, None, None], neg, scores)
        else:
            mask = qp[:, None, :, None] < kp[:, None, None, :]
            scores = jnp.where(mask[:, :, None], neg, scores)
    if k_valid is not None:  # (B,T) bool: cache entries that exist
        scores = jnp.where(k_valid[:, None, None, None, :], scores, neg)
    if visible is not None:
        scores = jnp.where(visible, scores, neg)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bkgst,btkd->bskgd", probs.astype(q.dtype), v)
    return ctx.reshape(b, s, h, v.shape[-1])  # dv != dh under MLA


def chunked_sdpa(q, k, v, *, causal: bool, chunk: int, gp: int = 1):
    """jnp-flash: scan over query chunks so the (S x T) score matrix is never
    materialized at once. Used for long prefill (DESIGN.md §3). Each chunk
    step is rematerialized under grad. GQA-grouped (see sdpa)."""
    b, s, h, dh = q.shape
    assert s % chunk == 0, (s, chunk)
    kh = k.shape[2]
    assert h == kh * gp, (h, kh, gp)
    t = k.shape[1]
    scale = dh ** -0.5
    kpos = jnp.arange(t)

    def step(carry, qc_i):
        qc, i = qc_i                                 # (B,chunk,KH,gp,dh)
        scores = jnp.einsum("bskgd,btkd->bkgst", qc, k).astype(jnp.float32)
        scores = scores * scale
        if causal:
            qpos = i * chunk + jnp.arange(chunk)
            neg = jnp.finfo(jnp.float32).min
            scores = jnp.where(
                (qpos[:, None] < kpos[None, :])[None, None, None],
                neg, scores)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(q.dtype), v)
        return carry, out

    qs = q.reshape(b, s // chunk, chunk, kh, gp, dh).transpose(
        1, 0, 2, 3, 4, 5)
    _, outs = jax.lax.scan(jax.checkpoint(step), None,
                           (qs, jnp.arange(s // chunk)))
    dv = v.shape[-1]  # may differ from dh (MLA: qk=192, v=128)
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, h, dv)


def gqa_out(p, ctx, cfg):
    """Mask padded heads (exact-zero contribution + grads), then w_o."""
    lo = layout_from_cfg(cfg)
    b, s = ctx.shape[:2]
    if lo.hp != lo.n_q:
        ctx = ctx * lo.q_mask[None, None, :, None].astype(ctx.dtype)
    return jnp.einsum("bsh,hd->bsd", ctx.reshape(b, s, lo.hp * cfg.head_dim),
                      p["wo"])


# ------------------------------------------------------------------ MLA ----
def init_mla(key, cfg):
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 8)
    dt = pdtype(cfg)
    if m.q_lora_rank:
        q = {"w_dq": dense_init(ks[0], (d, m.q_lora_rank), 0, dt),
             "q_norm": jnp.ones((m.q_lora_rank,), dt),
             "w_uq": dense_init(ks[1], (m.q_lora_rank, h * qk), 0, dt)}
    else:   # no q-LoRA: q = h W_q
        q = {"w_q": dense_init(ks[0], (d, h * qk), 0, dt)}
    return {
        **q,
        "w_dkv": dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim),
                            0, dt),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dt),
        "w_uk": dense_init(ks[3], (m.kv_lora_rank, h * m.qk_nope_head_dim),
                           0, dt),
        "w_uv": dense_init(ks[4], (m.kv_lora_rank, h * m.v_head_dim), 0, dt),
        "wo": dense_init(ks[5], (h * m.v_head_dim, d), 0, dt),
    }


def mla_q(p, x, cfg, cos, sin):
    """-> q_nope (B,S,H,nope), q_rope (B,S,H,rope); q through the q-LoRA
    (w_dq, q_norm, w_uq) or, without one, straight from w_q."""
    m, h = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    if "w_q" in p:     # no q-LoRA
        q = jnp.einsum("bsd,dh->bsh", x, p["w_q"])
    else:
        cq = rmsnorm_vec(jnp.einsum("bsd,dr->bsr", x, p["w_dq"]),
                         p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rh->bsh", cq, p["w_uq"])
    q = q.reshape(b, s, h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], cos, sin)
    return q_nope, q_rope


def mla_latent_kv(p, x, cfg, cos, sin):
    """-> c_kv (B,S,r) normalized latent, k_rope (B,S,rope) (shared head,
    rope applied). This pair IS the KV cache (physical representation:
    r+rope floats per token instead of 2*H*head_dim)."""
    m = cfg.mla
    ckr = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    c_kv = rmsnorm_vec(ckr[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(ckr[:, :, None, m.kv_lora_rank:], cos, sin)[:, :, 0]
    return c_kv, k_rope


def mla_attention_full(p, x, cfg, cos, sin, *, causal=True, chunk=0,
                       visible=None):
    """Train/prefill path: reconstruct per-head K,V from the latent then run
    standard attention (flops-faithful to the naive formulation).
    ``visible`` (S,S) bool: as ``sdpa``'s, on the unchunked path only."""
    m, h = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    q_nope, q_rope = mla_q(p, x, cfg, cos, sin)
    c_kv, k_rope = mla_latent_kv(p, x, cfg, cos, sin)
    k_nope = jnp.einsum("bsr,rh->bsh", c_kv, p["w_uk"]).reshape(
        b, s, h, m.qk_nope_head_dim)
    v = jnp.einsum("bsr,rh->bsh", c_kv, p["w_uv"]).reshape(
        b, s, h, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, s, h, m.qk_rope_head_dim))], -1)
    if chunk and s > chunk:
        if visible is not None:
            raise ValueError("chunked attention takes no visibility mask")
        ctx = chunked_sdpa(q, k, v, causal=causal, chunk=chunk)
    else:
        ctx = sdpa(q, k, v, causal=causal, visible=visible)
    out = jnp.einsum("bsh,hd->bsd", ctx.reshape(b, s, h * m.v_head_dim),
                     p["wo"])
    return out, (c_kv, k_rope)


def mla_attention_decode(p, x, cfg, cos, sin, c_kv_cache, k_rope_cache,
                         k_valid):
    """Absorbed decode: score and aggregate directly in latent space —
    O(S * (r + rope)) per head instead of reconstructing K/V.
    x (B,1,d); c_kv_cache (B,T,r) (current token already written)."""
    m, h = cfg.mla, cfg.n_heads
    b = x.shape[0]
    q_nope, q_rope = mla_q(p, x, cfg, cos, sin)          # (B,1,H,*)
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk)   # absorb W_UK
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    scores = (jnp.einsum("bshr,btr->bhst", q_lat, c_kv_cache)
              + jnp.einsum("bshn,btn->bhst", q_rope, k_rope_cache))
    scores = scores.astype(jnp.float32) * scale
    neg = jnp.finfo(jnp.float32).min
    scores = jnp.where(k_valid[:, None, None, :], scores, neg)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx_lat = jnp.einsum("bhst,btr->bshr", probs, c_kv_cache)
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    ctx = jnp.einsum("bshr,rhv->bshv", ctx_lat, w_uv)    # absorb W_UV
    return jnp.einsum("bsh,hd->bsd", ctx.reshape(b, 1, h * m.v_head_dim),
                      p["wo"])
