"""TAHOMA's specialized classifier family (paper Fig. 3):
[conv(3x3) -> ReLU -> maxpool(2x2)] x L -> dense ReLU -> sigmoid output.

The architecture space A varies (n_conv_layers, conv_nodes, dense_nodes);
the input representation space F (resolution x color) is applied by
core/transforms.py BEFORE the model sees the image — jointly they form the
paper's model design space A x F (§IV Def. 5/6).

CNNs run in float32. Inference (``cnn_forward``'s default) runs every
conv and dense product at ``Precision.HIGHEST``: on TPU, XLA's default
f32 precision is a single bf16 pass, which would put these scores ~1e-3
away from the fused pyramid+stage-0 kernel (kernels/image_transform.py,
whose dots run at the same HIGHEST precision) and flip labels near a
threshold. Training (``bce_loss``) runs at the backend's default
precision: the TPU compiler does not finish a HIGHEST conv gradient in
reasonable time, and trained weights need no bit-level agreement with
anything. On CPU the flag changes nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import TahomaCNNConfig

HIGHEST = jax.lax.Precision.HIGHEST


def init_cnn(key, cfg: TahomaCNNConfig):
    ks = jax.random.split(key, cfg.n_conv_layers + 2)
    params = {"conv": []}
    c_in = cfg.input_channels
    hw = cfg.input_hw
    for i in range(cfg.n_conv_layers):
        w = jax.random.normal(ks[i], (cfg.kernel_size, cfg.kernel_size,
                                      c_in, cfg.conv_nodes)) * (
            2.0 / (cfg.kernel_size ** 2 * c_in)) ** 0.5
        params["conv"].append({"w": w.astype(jnp.float32),
                               "b": jnp.zeros((cfg.conv_nodes,))})
        c_in = cfg.conv_nodes
        hw = hw // 2
    flat = hw * hw * c_in
    params["dense_w"] = (jax.random.normal(ks[-2], (flat, cfg.dense_nodes))
                         * (2.0 / flat) ** 0.5).astype(jnp.float32)
    params["dense_b"] = jnp.zeros((cfg.dense_nodes,))
    params["out_w"] = (jax.random.normal(ks[-1], (cfg.dense_nodes, 1))
                       * (1.0 / cfg.dense_nodes) ** 0.5).astype(jnp.float32)
    params["out_b"] = jnp.zeros((1,))
    return params


def _maxpool2(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def cnn_forward(params, images, precision=HIGHEST):
    """images (B, H, W, C) float32 in [0,1] -> pre-sigmoid logits (B,).
    ``precision=None`` is the backend's default (training)."""
    h = images
    for layer in params["conv"]:
        h = jax.lax.conv_general_dilated(
            h, layer["w"], window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
        h = jax.nn.relu(h + layer["b"])
        h = _maxpool2(h)
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(jnp.dot(h, params["dense_w"], precision=precision)
                    + params["dense_b"])
    return (jnp.dot(h, params["out_w"], precision=precision)
            + params["out_b"])[:, 0]


def cnn_predict_proba(params, images):
    return jax.nn.sigmoid(cnn_forward(params, images))


def cnn_flops(cfg: TahomaCNNConfig) -> float:
    """Forward FLOPs per image (the cost profiler's analytic input)."""
    total = 0.0
    hw, c_in = cfg.input_hw, cfg.input_channels
    for _ in range(cfg.n_conv_layers):
        total += 2.0 * hw * hw * cfg.kernel_size ** 2 * c_in \
            * cfg.conv_nodes
        c_in = cfg.conv_nodes
        hw //= 2
    flat = hw * hw * c_in
    total += 2.0 * flat * cfg.dense_nodes + 2.0 * cfg.dense_nodes
    return total


def quantize_cnn(params):
    """Weight-only int8 quantization (per-tensor symmetric, scale =
    absmax/127). Biases stay float32 — they are tiny and additive.

    Returns a pytree mirroring ``params`` where every weight tensor is
    replaced by ``{"q": int8, "scale": f32 scalar}``. Dequantize-at-use
    (``dequantize_cnn``) keeps the arithmetic in f32, so the deviation
    from the f32 model is bounded by the weight rounding alone — the
    calibrated tolerance pinned in benchmarks/calibrated_int8_stage0.json.
    """
    def q(w):
        w = jnp.asarray(w, jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-8) / 127.0
        return {"q": jnp.clip(jnp.round(w / scale), -127, 127
                              ).astype(jnp.int8),
                "scale": scale.astype(jnp.float32)}

    return {
        "conv": [{"w": q(l["w"]), "b": jnp.asarray(l["b"], jnp.float32)}
                 for l in params["conv"]],
        "dense_w": q(params["dense_w"]),
        "dense_b": jnp.asarray(params["dense_b"], jnp.float32),
        "out_w": q(params["out_w"]),
        "out_b": jnp.asarray(params["out_b"], jnp.float32),
    }


def dequantize_cnn(qparams):
    """Inverse of ``quantize_cnn`` up to rounding: int8 weights back to
    f32 (``q * scale``), shaped exactly like ``init_cnn`` output so the
    result feeds ``cnn_forward`` unchanged."""
    def dq(t):
        return t["q"].astype(jnp.float32) * t["scale"]

    return {
        "conv": [{"w": dq(l["w"]), "b": l["b"]} for l in qparams["conv"]],
        "dense_w": dq(qparams["dense_w"]),
        "dense_b": qparams["dense_b"],
        "out_w": dq(qparams["out_w"]),
        "out_b": qparams["out_b"],
    }


def bce_loss(params, images, labels):
    """Numerically-stable binary cross-entropy (labels in {0,1}), at the
    backend's default matmul precision."""
    logits = cnn_forward(params, images, precision=None)
    z = jnp.maximum(logits, 0.0)
    loss = z - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    return jnp.mean(loss)
