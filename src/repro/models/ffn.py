"""MLPs and Mixture-of-Experts.

MoE design (DESIGN.md §6): GShard-style *grouped capacity routing* written
entirely in pjit-friendly ops so XLA SPMD keeps every gather/scatter local:

* tokens are reshaped to (G, Tg, d) routing groups; the step builder picks
  G = data-parallel shard count for train/prefill (groups never cross a
  shard) and G = 1 for decode (tiny token counts; one all-gather is cheap);
* each expert takes its top-C tokens per group, C = ceil(Tg*k/E * factor)
  (over-capacity assignments are dropped — standard GShard semantics);
* expert weights are stacked (E, ...) and sharded over the 'model' axis
  (expert parallelism); the batched einsum over E runs one shard's experts
  on that shard, and the scatter-add back induces the expected
  reduce/all-reduce of activation size only.

Returns an auxiliary load-balance loss (Switch-style) for training.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import activation, dense_init, pdtype
from repro.sharding import policy


# ------------------------------------------------------------- dense MLP ---
def init_mlp(key, cfg, d_ff: int | None = None, gated: bool | None = None):
    d_ff = d_ff or cfg.d_ff
    gated = (cfg.act == "silu") if gated is None else gated
    ks = jax.random.split(key, 3)
    dt = pdtype(cfg)
    if gated:
        return {"w_gate": dense_init(ks[0], (cfg.d_model, d_ff), 0, dt),
                "w_up": dense_init(ks[1], (cfg.d_model, d_ff), 0, dt),
                "w_down": dense_init(ks[2], (d_ff, cfg.d_model), 0, dt)}
    return {"w_in": dense_init(ks[0], (cfg.d_model, d_ff), 0, dt),
            "b_in": jnp.zeros((d_ff,), dt),
            "w_out": dense_init(ks[1], (d_ff, cfg.d_model), 0, dt),
            "b_out": jnp.zeros((cfg.d_model,), dt)}


def apply_mlp(p, x, cfg):
    act = activation(cfg.act)
    if "w_gate" in p:
        h = act(jnp.einsum("...d,df->...f", x, p["w_gate"]))
        h = h * jnp.einsum("...d,df->...f", x, p["w_up"])
        return jnp.einsum("...f,fd->...d", h, p["w_down"])
    h = act(jnp.einsum("...d,df->...f", x, p["w_in"]) + p["b_in"])
    return jnp.einsum("...f,fd->...d", h, p["w_out"]) + p["b_out"]


# ------------------------------------------------------------------- MoE ---
def init_moe(key, cfg):
    moe = cfg.moe
    ks = jax.random.split(key, 5)
    dt = pdtype(cfg)
    d, f, e = cfg.d_model, moe.d_ff_expert, moe.num_experts

    def stacked(k, shape, in_axis):
        keys = jax.random.split(k, e)
        return jnp.stack([dense_init(kk, shape, in_axis, dt) for kk in keys])

    p = {
        "w_router": dense_init(ks[0], (d, e), 0, jnp.float32),
        "w_gate_e": stacked(ks[1], (d, f), 0),
        "w_up_e": stacked(ks[2], (d, f), 0),
        "w_down_e": stacked(ks[3], (f, d), 0),
    }
    if moe.num_shared_experts:
        # n shared silu-gated experts of width w are algebraically one
        # gated MLP of width n*w (outputs sum).
        p["shared"] = init_mlp(
            ks[4], cfg, d_ff=moe.num_shared_experts * moe.d_ff_shared,
            gated=True)
    return p


def moe_capacity(tokens_per_group: int, cfg) -> int:
    moe = cfg.moe
    c = math.ceil(tokens_per_group * moe.top_k / moe.num_experts
                  * moe.capacity_factor)
    return max(1, min(c, tokens_per_group))


def apply_moe(p, x, cfg, n_groups: int):
    """x (B, S, d) -> (out (B,S,d), aux_loss scalar)."""
    moe = cfg.moe
    b, s, d = x.shape
    t = b * s
    g = max(1, min(n_groups, t))
    while t % g:            # always divisible in practice; safe fallback
        g -= 1
    tg = t // g
    cap = moe_capacity(tg, cfg)
    xg = x.reshape(g, tg, d)

    dp = policy.ctx_dp_axes() or None

    # bf16 inputs + f32 accumulation: casting xg itself to f32 makes its
    # cotangent an f32 (G,Tg,d) tensor whose cross-shard reductions double
    # the dominant collective volume (EXPERIMENTS.md §Perf cell B, iter B5).
    logits = jnp.einsum("gtd,de->gte", xg,
                        p["w_router"].astype(xg.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # (G,Tg,E)
    topv, topi = jax.lax.top_k(probs, moe.top_k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)        # renorm
    # gate[g,t,e] = routing weight of expert e for token t (0 if unrouted)
    gate = jnp.sum(jax.nn.one_hot(topi, moe.num_experts, dtype=jnp.float32)
                   * topv[..., None], axis=2)                  # (G,Tg,E)
    gate = policy.ctx_constrain(gate, dp, None, "model")

    # per-expert top-C token selection (per group). Keeping E sharded over
    # 'model' makes each shard gather ONLY its own experts' tokens (EP).
    sel_gate, sel_idx = jax.lax.top_k(gate.transpose(0, 2, 1), cap)  # (G,E,C)
    sel_gate = policy.ctx_constrain(sel_gate, dp, "model", None)
    sel_idx = policy.ctx_constrain(sel_idx, dp, "model", None)
    xe = jnp.take_along_axis(
        xg, sel_idx.reshape(g, moe.num_experts * cap)[..., None],
        axis=1).reshape(g, moe.num_experts, cap, d)
    xe = policy.ctx_constrain(xe, dp, "model", None, None)

    act = activation(cfg.act)
    h = act(jnp.einsum("gecd,edf->gecf", xe, p["w_gate_e"]))
    h = h * jnp.einsum("gecd,edf->gecf", xe, p["w_up_e"])
    h = policy.ctx_constrain(h, dp, "model", None, None)
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down_e"])
    ye = ye * sel_gate[..., None].astype(ye.dtype)             # weight+mask
    ye = policy.ctx_constrain(ye, dp, "model", None, None)

    out = jnp.zeros_like(xg)
    gidx = jnp.arange(g)[:, None, None]
    out = out.at[gidx, sel_idx].add(ye)
    out = policy.ctx_constrain(out, dp, None, None)

    if "shared" in p:
        out = out + apply_mlp(p["shared"], xg, cfg)

    # Switch-style load-balance aux loss
    token_frac = jnp.mean(
        jnp.sum(jax.nn.one_hot(topi, moe.num_experts, dtype=jnp.float32),
                axis=2), axis=(0, 1))                          # (E,)
    prob_frac = jnp.mean(probs, axis=(0, 1))
    aux = moe.num_experts * jnp.sum(token_frac * prob_frac) / moe.top_k
    return out.reshape(b, s, d), aux


# ------------------------------------------------- held experts (EP) ---
def init_held_moe(key, cfg, held):
    """Router over all ``cfg.moe.num_experts`` experts (and its noaux_tc
    correction bias), the weights of the experts in ``held`` only, and
    the shared experts: one expert-parallel rank's share of a layer."""
    moe = cfg.moe
    ks = jax.random.split(key, 5)
    dt = pdtype(cfg)
    d, f, n = cfg.d_model, moe.d_ff_expert, len(held)

    def stacked(k, shape):
        return jnp.stack([dense_init(kk, shape, 0, dt)
                          for kk in jax.random.split(k, n)])

    p = {"w_router": dense_init(ks[0], (d, moe.num_experts), 0, jnp.float32),
         "router_bias": jnp.zeros((moe.num_experts,), jnp.float32),
         "w_gate_e": stacked(ks[1], (d, f)),
         "w_up_e": stacked(ks[2], (d, f)),
         "w_down_e": stacked(ks[3], (f, d))}
    if moe.num_shared_experts:
        p["shared"] = init_mlp(
            ks[4], cfg, d_ff=moe.num_shared_experts * moe.d_ff_shared,
            gated=True)
    return p


def router_scores(p, x):
    """x (T, d) -> (sigmoid scores (T, E), scores + correction bias
    (T, E), by which the experts are chosen)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), p["w_router"])
    scores = jax.nn.sigmoid(logits)
    return scores, scores + p["router_bias"]


def route(p, x, cfg):
    """x (T, d) -> (experts (T, k), weights (T, k)) over all experts, as
    DeepSeek-V3's sigmoid router (noaux_tc) routes: the top k of score +
    correction bias, weighted by their scores normalised to 1 and scaled
    by ``routed_scaling_factor``."""
    moe = cfg.moe
    scores, biased = router_scores(p, x)
    _, experts = jax.lax.top_k(biased, moe.top_k)
    weights = jnp.take_along_axis(scores, experts, axis=1)
    weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return experts, weights * moe.routed_scaling_factor


def apply_held_moe(p, x, cfg, held):
    """x (..., d) -> (out (..., d), rows (len(held),) int32): the held
    experts' part of an MoE layer plus the shared experts, with no
    capacity: every token-expert assignment that lands on a held expert
    is computed. Assignments are sorted by held expert (the rest last)
    and run as one grouped product per weight (``lax.ragged_dot``), then
    summed back per token; ``rows`` counts each held expert's
    assignments."""
    moe = cfg.moe
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    n = len(held)
    experts, weights = route(p, x, cfg)
    local = np.full((moe.num_experts,), n, np.int32)
    local[list(held)] = np.arange(n)
    slot = jnp.asarray(local)[experts].reshape(-1)      # (T*k,)
    order = jnp.argsort(slot, stable=True)
    rows = jnp.sum(slot[:, None] == jnp.arange(n)[None], 0, jnp.int32)
    tok = order // moe.top_k
    xs = x[tok]
    act = activation(cfg.act)
    h = act(jax.lax.ragged_dot(xs, p["w_gate_e"], rows)) \
        * jax.lax.ragged_dot(xs, p["w_up_e"], rows)
    y = jax.lax.ragged_dot(h, p["w_down_e"], rows)
    w = weights.reshape(-1)[order]
    # rows past the held groups belong to no group: masked, not weighted
    y = jnp.where((slot[order] < n)[:, None], y * w[:, None], 0.0)
    # back to (token, choice) order by the inverse permutation, a gather
    # (a scatter-add here is refused by the TPU compiler's fusion pass)
    out = y[jnp.argsort(order)].reshape(-1, moe.top_k, shape[-1]).sum(1)
    out = out.astype(x.dtype)
    if "shared" in p:
        out = out + apply_mlp(p["shared"], x, cfg)
    return out.reshape(shape), rows
