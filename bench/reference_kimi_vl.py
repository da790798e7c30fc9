"""The plain reference for the Kimi-VL predicates: P(yes) of each question
about each frame, written from the published description in
straightforward ``jax.numpy``, importing nothing of the program.

* The frame (H, W, 3) in [0, 1) is cut to its centred ``image_hw``
  square and mapped to [-1, 1] by (x - 0.5) / 0.5.
* MoonViT: 14 x 14 patches (row, column, channel) times the patch weight
  plus bias; plus the 64 x 64 position table resized to the patch grid by
  bicubic interpolation (PyTorch's convention: a = -0.75, half-pixel
  centres, edges clamped); 27 pre-LayerNorm blocks: qkv with bias, 2D
  RoPE (adjacent pairs; pair j turns by col * f[j // 2] for even j and
  row * f[j // 2] for odd j, f[i] = 10000 ** (-4 i / head_dim)),
  attention over the image's patches, output projection with bias;
  LayerNorm, tanh-GELU MLP; then a final LayerNorm, 2 x 2 merge
  (row-major within the window), LayerNorm per patch, Linear, exact
  GELU, Linear.
* The decoder reads the 64 image tokens and then the question's 16 token
  embeddings. Each layer: RMSNorm, MLA (q = h W_q; c = RMSNorm of the
  first 512 of h W_dkv, the last 64 its shared RoPE key; keys are c W_uk
  beside the RoPE key, values c W_uv; causal softmax at 192 ** -0.5;
  RoPE on the halves of each 64-wide part, theta 800000), residual;
  RMSNorm, then a SwiGLU MLP (the first layer) or the MoE (the others),
  residual. MoE: sigmoid of h W_router; the 6 experts of highest score +
  correction bias; weights their scores over the sum of the 6, times
  2.446; each expert this chip holds adds weight * SwiGLU_e(h) for the
  tokens routed to it, computed here for every token and weighted by 0
  where it was not chosen; the shared SwiGLU adds once.
* The score: RMSNorm of the last position, the yes and no columns of the
  output head, the softmax's yes share.

Routing is discontinuous where the 6th and 7th expert tie. Beside each
score the reference gives the least distance, over the sequence's tokens
and MoE layers, between the 6th and 7th score + bias, whichever experts
they are: a swap there changes the chosen set, so the normaliser of the
held experts' weights, or their assignments.

Precisions: ``"highest"`` (float32 at ``Precision.HIGHEST``, what the
configuration states), and the controls ``"high"`` (three bfloat16
passes) and ``"bf16"`` (one), split by bit mask as ``bench/reference.py``
does.
"""
from __future__ import annotations

import math
from functools import partial

import numpy as np

from bench.reference import _bf16_part, _split


# ------------------------------------------------------------ config --
def model_of(config: dict) -> dict:
    """The widths the reference reads, from the cell's configuration."""
    v = config["vision_config"]
    dep = config["deployment"]
    return {"d": config["hidden_size"], "heads": config["num_attention_heads"],
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"], "vdim": config["v_head_dim"],
            "kv_rank": config["kv_lora_rank"],
            "dense_ff": config["intermediate_size"],
            "expert_ff": config["moe_intermediate_size"],
            "shared_ff": config["n_shared_experts"]
            * config["moe_intermediate_size"],
            "layers": config["num_hidden_layers"],
            "dense_layers": config["first_k_dense_replace"],
            "experts": dep["router_experts"], "held": list(dep["held_experts"]),
            "top_k": config["num_experts_per_tok"],
            "scale": config["routed_scaling_factor"],
            "eps": config["rms_norm_eps"], "theta": config["rope_theta"],
            "vocab": config["vocab_size"],
            "patch": v["patch"], "vd": v["d_model"], "vlayers": v["n_layers"],
            "vheads": v["n_heads"], "vff": v["d_ff"], "grid": v["pos_grid"],
            "merge": v["merge"], "vtheta": v["rope_theta"],
            "veps": v["norm_eps"], "image_hw": v["image_hw"]}


# ----------------------------------------------------------- weights --
def init_params(key, m: dict, w: dict):
    """Seeded float32 weights in the program's layout
    (``repro.models.kimi_vl.init_kimi_vl``'s keys). Linear weights are
    normal over sqrt(fan-in); ``w`` gives the rest: biases' and the
    position table's std, the embedding's, the router bias's, and the
    output head's scale."""
    import jax
    import jax.numpy as jnp

    keys = iter(jax.random.split(key, 64))

    def lin(shape, lead=()):
        return jax.random.normal(next(keys), lead + shape) \
            * shape[0] ** -0.5

    def rnd(shape, std, lead=()):
        return jax.random.normal(next(keys), lead + shape) * std

    def ln(d, lead=(), bias=True):
        p = {"scale": jnp.ones(lead + (d,))}
        if bias:
            p["bias"] = jnp.zeros(lead + (d,))
        return p

    vd, vf, L = m["vd"], m["vff"], (m["vlayers"],)
    b = w["bias_std"]
    tower = {
        "patch_w": lin((m["patch"] ** 2 * 3, vd)), "patch_b": rnd((vd,), b),
        "pos": rnd((m["grid"], m["grid"], vd), w["pos_std"]),
        "layers": {"ln1": ln(vd, L), "ln2": ln(vd, L),
                   "wqkv": lin((vd, 3 * vd), L), "bqkv": rnd((3 * vd,), b, L),
                   "wo": lin((vd, vd), L), "bo": rnd((vd,), b, L),
                   "w1": lin((vd, vf), L), "b1": rnd((vf,), b, L),
                   "w2": lin((vf, vd), L), "b2": rnd((vd,), b, L)},
        "final_ln": ln(vd)}
    mm = m["merge"] ** 2 * vd
    tower["proj"] = {"ln": ln(vd), "w1": lin((mm, mm)), "b1": rnd((mm,), b),
                     "w2": lin((mm, m["d"])), "b2": rnd((m["d"],), b)}

    d, h = m["d"], m["heads"]

    def attn(lead):
        return {"w_q": lin((d, h * (m["nope"] + m["rope"])), lead),
                "w_dkv": lin((d, m["kv_rank"] + m["rope"]), lead),
                "kv_norm": jnp.ones(lead + (m["kv_rank"],)),
                "w_uk": lin((m["kv_rank"], h * m["nope"]), lead),
                "w_uv": lin((m["kv_rank"], h * m["vdim"]), lead),
                "wo": lin((h * m["vdim"], d), lead)}

    def swiglu(f, lead):
        return {"w_gate": lin((d, f), lead), "w_up": lin((d, f), lead),
                "w_down": lin((f, d), lead)}

    n_moe = m["layers"] - m["dense_layers"]
    E, n = (n_moe,), (n_moe, len(m["held"]))
    decoder = {
        "embed": rnd((m["vocab"], d), w["embed_std"]),
        "dense": [{"ln1": ln(d, bias=False), "ln2": ln(d, bias=False),
                   "attn": attn(()), "mlp": swiglu(m["dense_ff"], ())}
                  for _ in range(m["dense_layers"])],
        "moe": {"ln1": ln(d, E, False), "ln2": ln(d, E, False),
                "attn": attn(E),
                "moe": {"w_router": lin((d, m["experts"]), E),
                        "router_bias": rnd((m["experts"],),
                                           w["router_bias_std"], E),
                        "w_gate_e": lin((d, m["expert_ff"]), n),
                        "w_up_e": lin((d, m["expert_ff"]), n),
                        "w_down_e": lin((m["expert_ff"], d), n),
                        "shared": swiglu(m["shared_ff"], E)}},
        "final_norm": ln(d, bias=False),
        "head": rnd((d, m["vocab"]), w["head_scale"] * d ** -0.5)}
    return {"tower": tower, "decoder": decoder}


# ---------------------------------------------------------- products --
def _mm(spec, a, b, precision):
    import jax
    import jax.numpy as jnp

    exact = jax.lax.Precision.HIGHEST
    op = partial(jnp.einsum, spec, precision=exact)
    if precision == "highest":
        return op(a, b)
    if precision == "bf16":
        return op(_bf16_part(a), _bf16_part(b))
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    ah, al = _split(a)
    bh, bl = _split(b)
    return op(ah, bh) + op(ah, bl) + op(al, bh)


def _layernorm(x, p, eps):
    import jax.numpy as jnp

    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _rmsnorm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _bicubic(n_out, n_in, a=-0.75):
    def w(t):
        t = abs(t)
        if t <= 1:
            return (a + 2) * t ** 3 - (a + 3) * t ** 2 + 1
        if t < 2:
            return a * t ** 3 - 5 * a * t ** 2 + 8 * a * t - 4 * a
        return 0.0
    out = np.zeros((n_out, n_in))
    for i in range(n_out):
        src = (i + 0.5) * n_in / n_out - 0.5
        base = math.floor(src)
        for j in range(base - 1, base + 3):
            out[i, min(max(j, 0), n_in - 1)] += w(src - j)
    return out.astype(np.float32)


# ------------------------------------------------------------- tower --
def tower(p, frames, m, precision):
    import jax
    import jax.numpy as jnp

    mmul = partial(_mm, precision=precision)
    s, top = m["image_hw"], (frames.shape[1] - m["image_hw"]) // 2
    x = (frames[:, top:top + s, top:top + s] - 0.5) / 0.5
    g, pt, d = s // m["patch"], m["patch"], m["vd"]
    b = x.shape[0]
    x = x.reshape(b, g, pt, g, pt, 3).transpose(0, 1, 3, 2, 4, 5).reshape(
        b, g * g, pt * pt * 3)
    x = mmul("bnp,pd->bnd", x, p["patch_w"]) + p["patch_b"]
    r = jnp.asarray(_bicubic(g, m["grid"]))
    pos = mmul("yi,ixd->yxd", r, p["pos"])
    pos = mmul("xj,yjd->yxd", r, pos)
    x = x + pos.reshape(g * g, d)

    nh = m["vheads"]
    dh = d // nh
    f = m["vtheta"] ** (-np.arange(0, dh, 4)[: dh // 4] / dh)
    row, col = np.divmod(np.arange(g * g), g)
    ang = np.zeros((g * g, dh // 2))
    ang[:, 0::2] = np.outer(col, f)
    ang[:, 1::2] = np.outer(row, f)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None]

    def rot(t):
        a, c = t[..., 0::2], t[..., 1::2]
        return jnp.stack([a * cos - c * sin, c * cos + a * sin],
                         -1).reshape(t.shape)

    def block(x, lp):
        h = _layernorm(x, lp["ln1"], m["veps"])
        qkv = (mmul("bnd,de->bne", h, lp["wqkv"]) + lp["bqkv"]).reshape(
            b, g * g, 3, nh, dh)
        q, k, v = rot(qkv[:, :, 0]), rot(qkv[:, :, 1]), qkv[:, :, 2]
        att = jax.nn.softmax(mmul("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh),
                             -1)
        ctx = mmul("bhqk,bkhd->bqhd", att, v).reshape(b, g * g, d)
        x = x + mmul("bnd,de->bne", ctx, lp["wo"]) + lp["bo"]
        h = _layernorm(x, lp["ln2"], m["veps"])
        h = jax.nn.gelu(mmul("bnd,df->bnf", h, lp["w1"]) + lp["b1"],
                        approximate=True)
        return x + mmul("bnf,fd->bnd", h, lp["w2"]) + lp["b2"], None

    x, _ = jax.lax.scan(block, x, p["layers"])
    x = _layernorm(x, p["final_ln"], m["veps"])
    r, gm = m["merge"], g // m["merge"]
    x = x.reshape(b, gm, r, gm, r, d).transpose(0, 1, 3, 2, 4, 5)
    pp = p["proj"]
    x = _layernorm(x, pp["ln"], m["veps"]).reshape(b, gm * gm, r * r * d)
    x = jax.nn.gelu(mmul("bnk,kj->bnj", x, pp["w1"]) + pp["b1"],
                    approximate=False)
    return mmul("bnj,jd->bnd", x, pp["w2"]) + pp["b2"]


# ----------------------------------------------------------- decoder --
def _rope_half(x, pos, theta):
    """RoPE over the halves of the last axis (x (..., S, H, r))."""
    import jax.numpy as jnp

    r = x.shape[-1]
    inv = theta ** (-np.arange(0, r, 2) / r)
    ang = jnp.asarray(np.outer(pos, inv), jnp.float32)[:, None]
    a, c = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - c * jnp.sin(ang),
                            c * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _mla(p, x, m, mmul):
    import jax
    import jax.numpy as jnp

    n, s, _ = x.shape
    h, nope, rp = m["heads"], m["nope"], m["rope"]
    pos = np.arange(s)
    q = mmul("nsd,de->nse", x, p["w_q"]).reshape(n, s, h, nope + rp)
    q = jnp.concatenate([q[..., :nope],
                         _rope_half(q[..., nope:], pos, m["theta"])], -1)
    kv = mmul("nsd,de->nse", x, p["w_dkv"])
    c = _rmsnorm(kv[..., :m["kv_rank"]], p["kv_norm"], m["eps"])
    k_rope = _rope_half(kv[:, :, None, m["kv_rank"]:], pos, m["theta"])
    k = jnp.concatenate(
        [mmul("nsr,re->nse", c, p["w_uk"]).reshape(n, s, h, nope),
         jnp.broadcast_to(k_rope, (n, s, h, rp))], -1)
    v = mmul("nsr,re->nse", c, p["w_uv"]).reshape(n, s, h, m["vdim"])
    score = mmul("nqhd,nkhd->nhqk", q, k) / math.sqrt(nope + rp)
    score = jnp.where(np.tril(np.ones((s, s), bool)), score, -jnp.inf)
    ctx = mmul("nhqk,nkhd->nqhd", jax.nn.softmax(score, -1), v)
    return mmul("nse,ed->nsd", ctx.reshape(n, s, h * m["vdim"]), p["wo"])


def _swiglu(p, x, mmul, gate="w_gate", up="w_up", down="w_down"):
    import jax

    a = jax.nn.silu(mmul("nsd,df->nsf", x, p[gate])) \
        * mmul("nsd,df->nsf", x, p[up])
    return mmul("nsf,fd->nsd", a, p[down])


def _route(p, x, m, mmul):
    """Router scores (..., E) and the scores plus correction bias that
    choose the experts."""
    import jax

    score = jax.nn.sigmoid(mmul("nsd,de->nse", x, p["w_router"]))
    return score, score + p["router_bias"]


def _moe(p, x, m, mmul):
    """The held experts' part plus the shared experts, and each
    sequence's least distance between the k-th and (k+1)-th choice. A
    swap there changes the chosen set, and with it the normaliser of
    every chosen weight, held or not, so every token's distance counts."""
    import jax
    import jax.numpy as jnp

    held = jnp.asarray(m["held"])
    score, biased = _route(p, x, m, mmul)
    top_v, top_i = jax.lax.top_k(biased, m["top_k"] + 1)
    chosen = top_i[..., :-1]
    w = jnp.take_along_axis(score, chosen, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * m["scale"]
    out = _swiglu(p["shared"], x, mmul)
    for e in range(len(m["held"])):
        pe = {k: p[k][e] for k in ("w_gate_e", "w_up_e", "w_down_e")}
        gate = jnp.where(chosen == held[e], w, 0.0).sum(-1)
        out = out + gate[..., None] * _swiglu(pe, x, mmul, "w_gate_e",
                                              "w_up_e", "w_down_e")
    return out, (top_v[..., -2] - top_v[..., -1]).min(-1)


def decoder(p, img, questions, m, precision):
    """img (B, T, d), questions (K, L) -> (P(yes) (B, K), tie (B, K))."""
    import jax
    import jax.numpy as jnp

    mmul = partial(_mm, precision=precision)
    b, t, d = img.shape
    k, ql = questions.shape
    x = jnp.concatenate(
        [jnp.broadcast_to(img[:, None], (b, k, t, d)),
         jnp.broadcast_to(p["embed"][questions][None], (b, k, ql, d))], 2)
    x = x.reshape(b * k, t + ql, d)
    for lp in p["dense"]:
        x = x + _mla(lp["attn"], _rmsnorm(x, lp["ln1"]["scale"], m["eps"]),
                     m, mmul)
        x = x + _swiglu(lp["mlp"], _rmsnorm(x, lp["ln2"]["scale"], m["eps"]),
                        mmul)

    def moe_layer(carry, lp):
        x, tie = carry
        x = x + _mla(lp["attn"], _rmsnorm(x, lp["ln1"]["scale"], m["eps"]),
                     m, mmul)
        out, tie_l = _moe(lp["moe"], _rmsnorm(x, lp["ln2"]["scale"],
                                               m["eps"]), m, mmul)
        return (x + out, jnp.minimum(tie, tie_l)), None

    (x, tie), _ = jax.lax.scan(moe_layer, (x, jnp.full((b * k,), jnp.inf)),
                               p["moe"])
    last = _rmsnorm(x[:, -1], p["final_norm"]["scale"], m["eps"])
    yes_no = jnp.stack([p["head"][:, m["yes"]], p["head"][:, m["no"]]], 1)
    logits = mmul("nd,dc->nc", last, yes_no)
    return (jax.nn.softmax(logits, -1)[:, 0].reshape(b, k),
            tie.reshape(b, k))


def _scores(params, frames, questions, *, m, precision):
    return decoder(params["decoder"], tower(params["tower"], frames, m,
                                            precision),
                   questions, m, precision)


_FNS: dict = {}


def score_frames(frames_of, n: int, block: int, params, m: dict,
                 questions, precision: str = "highest"):
    """(P(yes) (K, n), routing tie distance (K, n)) of frames 0..n;
    ``frames_of(lo, hi)`` gives them, padded to ``block``, on the
    device."""
    import jax

    key = (repr(sorted(m.items())), precision)
    if key not in _FNS:
        _FNS[key] = jax.jit(partial(_scores, m=m, precision=precision))
    fn = _FNS[key]
    scores, ties = [], []
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        s, t = fn(params, frames_of(lo, hi), questions)
        scores.append(np.asarray(s)[:hi - lo])
        ties.append(np.asarray(t)[:hi - lo])
    return np.concatenate(scores).T, np.concatenate(ties).T
