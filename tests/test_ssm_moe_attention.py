"""Layer-level math: SSD vs naive recurrence, SSD decode vs chunked, MoE
capacity routing vs dense per-token loop, head-padding exactness."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, MoEConfig, SSMConfig
from repro.models import attention as attn
from repro.models import ffn
from repro.models.ssm import ssd_chunked, ssd_decode


def _naive_ssd(x, dt, a, bm, cm):
    """Direct O(S) recurrence: state_{t} = state_{t-1} e^{dt_t a} +
    dt_t B_t x_t^T;  y_t = C_t . state_t."""
    b, s, h, p = x.shape
    n = bm.shape[-1]
    state = np.zeros((b, h, p, n))
    ys = np.zeros((b, s, h, p))
    for t in range(s):
        da = np.exp(dt[:, t] * a[None, :])                    # (b,h)
        upd = np.einsum("bh,bn,bhp->bhpn", dt[:, t], bm[:, t], x[:, t])
        state = state * da[:, :, None, None] + upd
        ys[:, t] = np.einsum("bhpn,bn->bhp", state, cm[:, t])
    return ys, state


def test_ssd_chunked_matches_naive():
    rng = np.random.default_rng(0)
    b, s, h, p, n = 2, 64, 3, 8, 16
    x = rng.standard_normal((b, s, h, p)).astype(np.float32) * 0.5
    dt = rng.random((b, s, h)).astype(np.float32) * 0.1
    a = -rng.random(h).astype(np.float32)
    bm = rng.standard_normal((b, s, n)).astype(np.float32) * 0.3
    cm = rng.standard_normal((b, s, n)).astype(np.float32) * 0.3
    y, st = ssd_chunked(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(a),
                        jnp.asarray(bm), jnp.asarray(cm), chunk=16)
    y_ref, st_ref = _naive_ssd(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(y), y_ref, atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st), st_ref, atol=1e-4,
                               rtol=1e-3)


def test_ssd_decode_continues_chunked():
    rng = np.random.default_rng(1)
    b, s, h, p, n = 1, 32, 2, 8, 16
    x = rng.standard_normal((b, s + 1, h, p)).astype(np.float32) * 0.5
    dt = rng.random((b, s + 1, h)).astype(np.float32) * 0.1
    a = -rng.random(h).astype(np.float32)
    bm = rng.standard_normal((b, s + 1, n)).astype(np.float32) * 0.3
    cm = rng.standard_normal((b, s + 1, n)).astype(np.float32) * 0.3
    y_all, _ = ssd_chunked(*(jnp.asarray(v) for v in
                             (x, dt, a, bm, cm)), chunk=11 if False else 33)
    _, st = ssd_chunked(jnp.asarray(x[:, :s]), jnp.asarray(dt[:, :s]),
                        jnp.asarray(a), jnp.asarray(bm[:, :s]),
                        jnp.asarray(cm[:, :s]), chunk=8)
    y1, _ = ssd_decode(jnp.asarray(x[:, s:]), jnp.asarray(dt[:, s:]),
                       jnp.asarray(a), jnp.asarray(bm[:, s:]),
                       jnp.asarray(cm[:, s:]), st)
    np.testing.assert_allclose(np.asarray(y1)[:, 0],
                               np.asarray(y_all)[:, s], atol=1e-4,
                               rtol=1e-3)


# ----------------------------------------------------------------- MoE -----
def _dense_moe_ref(p, x, cfg):
    """Per-token loop over its top-k experts (no capacity limits)."""
    moe = cfg.moe
    b, s, d = x.shape
    xt = np.asarray(x).reshape(-1, d)
    logits = xt @ np.asarray(p["w_router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(xt)
    for t in range(xt.shape[0]):
        top = np.argsort(-probs[t])[: moe.top_k]
        w = probs[t][top] / probs[t][top].sum()
        for e, wi in zip(top, w):
            g = xt[t] @ np.asarray(p["w_gate_e"][e], np.float64)
            u = xt[t] @ np.asarray(p["w_up_e"][e], np.float64)
            h = (g / (1 + np.exp(-g))) * u
            out[t] += wi * (h @ np.asarray(p["w_down_e"][e], np.float64))
    if "shared" in p:
        from repro.models.ffn import apply_mlp
        out += np.asarray(apply_mlp(p["shared"], x, cfg)).reshape(-1, d)
    return out.reshape(b, s, d)


@pytest.mark.parametrize("shared", [0, 2])
def test_moe_matches_dense_reference(shared):
    cfg = ArchConfig(
        name="t", family="moe", n_layers=1, d_model=16, n_heads=2,
        n_kv_heads=2, d_ff=32, vocab_size=64, head_dim=8, dtype="float32",
        moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=32,
                      num_shared_experts=shared, d_ff_shared=16,
                      capacity_factor=8.0))  # no dropping
    p = ffn.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16), jnp.float32)
    out, aux = ffn.apply_moe(p, x, cfg, n_groups=1)
    ref = _dense_moe_ref(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4, rtol=1e-3)
    assert float(aux) >= 0.99  # balance loss >= 1 at perfect balance


def test_moe_capacity_drops_tokens():
    cfg = ArchConfig(
        name="t", family="moe", n_layers=1, d_model=8, n_heads=2,
        n_kv_heads=2, d_ff=16, vocab_size=64, head_dim=4, dtype="float32",
        moe=MoEConfig(num_experts=2, top_k=1, d_ff_expert=16,
                      capacity_factor=0.5))
    p = ffn.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 8), jnp.float32)
    out, _ = ffn.apply_moe(p, x, cfg, n_groups=1)
    # with capacity 0.5 some tokens get zero expert output (dropped)
    norms = np.linalg.norm(np.asarray(out).reshape(16, 8), axis=-1)
    assert (norms < 1e-6).any()


# -------------------------------------------------------- head padding -----
def _embed_padded(p1, cfg1, cfg2):
    """Map unpadded GQA weights into the padded per-group layout."""
    lo1 = attn.layout_from_cfg(cfg1)
    lo2 = attn.layout_from_cfg(cfg2)
    dh = cfg1.head_dim
    p2 = jax.tree.map(jnp.zeros_like,
                      attn.init_gqa(jax.random.PRNGKey(9), cfg2))
    wq1 = p1["wq"].reshape(cfg1.d_model, lo1.n_q, dh)
    wq2 = np.zeros((cfg1.d_model, lo2.hp, dh), np.float32)
    g1 = lo1.n_q // lo1.n_kv
    for i in range(lo1.n_q):
        kv, j = divmod(i, g1)
        wq2[:, kv * lo2.gp + j] = np.asarray(wq1[:, i])
    wo1 = p1["wo"].reshape(lo1.n_q, dh, cfg1.d_model)
    wo2 = np.zeros((lo2.hp, dh, cfg1.d_model), np.float32)
    for i in range(lo1.n_q):
        kv, j = divmod(i, g1)
        wo2[kv * lo2.gp + j] = np.asarray(wo1[i])
    p2 = dict(p2)
    p2["wq"] = jnp.asarray(wq2.reshape(cfg1.d_model, lo2.hp * dh))
    p2["wo"] = jnp.asarray(wo2.reshape(lo2.hp * dh, cfg1.d_model))
    p2["wk"], p2["wv"] = p1["wk"], p1["wv"]
    return p2


def test_head_padding_exact():
    """Padded-TP attention == unpadded attention bit-for-bit-ish (the
    numerics-preservation claim in DESIGN.md §6)."""
    base = dict(name="t", family="dense", n_layers=1, d_model=24,
                n_heads=6, n_kv_heads=2, d_ff=32, vocab_size=64,
                head_dim=4, dtype="float32")
    cfg1 = ArchConfig(**base, head_pad_to=1)
    cfg2 = ArchConfig(**base, head_pad_to=4)   # 6 q heads -> gp 4 -> hp 8
    lo2 = attn.layout_from_cfg(cfg2)
    assert lo2.hp % 4 == 0 and lo2.hp > cfg2.n_heads
    p1 = attn.init_gqa(jax.random.PRNGKey(0), cfg1)
    p2 = _embed_padded(p1, cfg1, cfg2)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 24), jnp.float32)
    from repro.models.common import rope_for_heads
    pos = jnp.broadcast_to(jnp.arange(8)[None], (2, 8))
    cos, sin = rope_for_heads(pos, 4, 1e4)
    def run(p, cfg):
        lo = attn.layout_from_cfg(cfg)
        q, k, v = attn.gqa_qkv(p, x, cfg, rope=(cos, sin, cos, sin))
        ctx = attn.sdpa(q, k, v, causal=True, gp=lo.gp)
        return attn.gqa_out(p, ctx, cfg)
    np.testing.assert_allclose(np.asarray(run(p1, cfg1)),
                               np.asarray(run(p2, cfg2)),
                               atol=1e-5, rtol=1e-5)


def _plain_attention(q, k, v, causal):
    """Per-head softmax(q k^T / sqrt(dh)) v in numpy, GQA by repeat."""
    gp = q.shape[2] // k.shape[2]
    k, v = np.repeat(k, gp, axis=2), np.repeat(v, gp, axis=2)
    s = np.einsum("bshd,bthd->bhst", q, k) * q.shape[-1] ** -0.5
    if causal:
        s = np.where(np.tril(np.ones(s.shape[-2:], bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhst,bthd->bshd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("causal", [True, False])
def test_sdpa_without_a_mask_is_plain_attention(causal):
    """``visible=None`` leaves ``sdpa`` the attention it was, causal or
    not; an explicit mask of the same keys gives the same result."""
    rng = np.random.default_rng(4)
    q = rng.standard_normal((2, 6, 4, 8)).astype(np.float32)
    k = rng.standard_normal((2, 6, 2, 8)).astype(np.float32)
    v = rng.standard_normal((2, 6, 2, 8)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got = attn.sdpa(q, k, v, causal=causal, gp=2)
        shown = np.tril(np.ones((6, 6), bool)) if causal else \
            np.ones((6, 6), bool)
        masked = attn.sdpa(q, k, v, causal=False, visible=shown, gp=2)
    want = _plain_attention(q, k, v, causal)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(masked, got)


def test_head_layout_assignments():
    """The production (pad_to=16) layouts for every assigned arch."""
    cases = {(40, 8): (48, 8, 6), (24, 8): (32, 8, 4), (6, 6): (16, 16, 1),
             (48, 1): (48, 1, 48), (32, 32): (32, 32, 1),
             (128, 128): (128, 128, 1), (64, 8): (64, 8, 8)}
    for (h, kv), (hp, khp, gp) in cases.items():
        lo = attn.head_layout(h, kv, 16)
        assert (lo.hp, lo.khp, lo.gp) == (hp, khp, gp), (h, kv, lo)
        assert lo.hp % 16 == 0 or lo.hp == h
        # real q heads count preserved by the mask
        assert int(lo.q_mask.sum()) == h
