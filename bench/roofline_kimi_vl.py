"""Operations and bytes the Kimi-VL predicates need, counted from shapes
(``bench/reference_kimi_vl.model_of``'s widths), as ``bench/roofline.py``
counts the CNNs'. Multiply-adds count 2; norms, activations, softmax,
RoPE and the position table's resize are not counted."""
from __future__ import annotations

F32 = 4


def tower_flops(m: dict) -> float:
    """MoonViT and the projector, per frame."""
    d, g = m["vd"], m["image_hw"] // m["patch"]
    n = g * g
    per_token = (2 * 3 * d * d          # qkv
                 + 2 * 2 * n * d        # scores and context, all heads
                 + 2 * d * d            # output projection
                 + 2 * 2 * d * m["vff"])
    merged = n // m["merge"] ** 2
    wide = m["merge"] ** 2 * d
    return (2.0 * n * m["patch"] ** 2 * 3 * d + m["vlayers"] * n * per_token
            + merged * (2 * wide * wide + 2 * wide * m["d"]))


def decoder_flops(m: dict, seq: int) -> float:
    """One sequence of ``seq`` tokens through the decoder, without the
    routed experts' work (``expert_row_flops`` a token-expert
    assignment): causal attention over the keys at or before each
    position, the dense layers' MLP, the router and shared experts, and
    the yes and no rows of the head at the last position."""
    d, h = m["d"], m["heads"]
    qk = m["nope"] + m["rope"]
    proj = 2 * (d * h * qk + d * (m["kv_rank"] + m["rope"])
                + m["kv_rank"] * h * (m["nope"] + m["vdim"])
                + h * m["vdim"] * d)
    keys = seq * (seq + 1) / 2
    attn = seq * proj + 2 * keys * h * (qk + m["vdim"])
    dense = seq * 2 * 3 * d * m["dense_ff"]
    moe = seq * (2 * d * m["experts"] + 2 * 3 * d * m["shared_ff"])
    n_moe = m["layers"] - m["dense_layers"]
    return (m["layers"] * attn + m["dense_layers"] * dense + n_moe * moe
            + 2 * d * 2)


def expert_row_flops(m: dict) -> float:
    """One token-expert assignment through a held expert's SwiGLU."""
    return 2.0 * 3 * m["d"] * m["expert_ff"]


def expert_work(m: dict, rows: float, layer_calls: float
                ) -> tuple[float, float]:
    """(operations, bytes) of the held experts' grouped products over
    ``rows`` assignments in ``layer_calls`` MoE layer calls: per call the
    held experts' three weights are read once; per assignment its token
    is read by the gate and up products, their two outputs are written
    and read back as one, and the down product writes one row."""
    d, f = m["d"], m["expert_ff"]
    weights = layer_calls * len(m["held"]) * 3 * d * f * F32
    rows_bytes = rows * (2 * d + 2 * f + f + d) * F32
    return rows * expert_row_flops(m), weights + rows_bytes
