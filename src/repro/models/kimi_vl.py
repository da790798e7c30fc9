"""Kimi-VL as a yes/no predicate scorer over frames
(configs/kimi_vl_a3b; DESIGN.md §5's LM cascade, with a vision tower).

A frame is centre-cropped to the tower's square and mapped from [0, 1)
to [-1, 1] as SigLIP does, and MoonViT (models/vision) turns it into
image tokens. Each question is a row of token ids; the decoder reads
the frame's image tokens followed by the question's ids, one sequence
per (frame, question), and the score is the two-way softmax of the yes
and no logits at the last position (``core/lm_cascade.yes_share``, as
``lm_predicate_score`` scores). Only those two rows of the output
head are computed.

The decoder: causal MLA (models/attention, no q-LoRA) with 1D RoPE,
``first_k_dense`` dense SwiGLU layers, then MoE layers of which this
device holds the experts ``held`` (models/ffn.apply_held_moe: routing
over all experts, no token dropped), a final RMSNorm. Everything runs
in float32 with every product at ``Precision.HIGHEST``.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lm_cascade import yes_share
from repro.models import attention as attn
from repro.models import ffn
from repro.models.common import apply_norm, rope_for_heads
from repro.models.vision import init_moonvit, moonvit


def init_decoder(key, cfg, held):
    """Decoder weights: embedding, layers (dense, then MoE stacked for
    ``lax.scan``), final norm and output head, float32."""
    t = cfg.text
    n_moe = t.n_layers - cfg.first_k_dense
    ks = jax.random.split(key, 5)

    def norm():
        return {"scale": jnp.ones((t.d_model,), jnp.float32)}

    def dense(k):
        k1, k2 = jax.random.split(k)
        return {"ln1": norm(), "ln2": norm(), "attn": attn.init_mla(k1, t),
                "mlp": ffn.init_mlp(k2, t, d_ff=t.d_ff, gated=True)}

    def moe(k):
        k1, k2 = jax.random.split(k)
        return {"ln1": norm(), "ln2": norm(), "attn": attn.init_mla(k1, t),
                "moe": ffn.init_held_moe(k2, t, held)}

    return {"embed": jax.random.normal(ks[0], (t.vocab_size, t.d_model),
                                       jnp.float32),
            "dense": [dense(k) for k in
                      jax.random.split(ks[1], cfg.first_k_dense)],
            "moe": jax.vmap(moe)(jax.random.split(ks[2], n_moe)),
            "final_norm": norm(),
            "head": jax.random.normal(ks[3], (t.d_model, t.vocab_size),
                                      jnp.float32) * t.d_model ** -0.5}


def _layer(lp, h, t, rope, held):
    x = apply_norm(lp["ln1"], h, t, "rmsnorm")
    out, _ = attn.mla_attention_full(lp["attn"], x, t, *rope, causal=True)
    h = h + out
    x = apply_norm(lp["ln2"], h, t, "rmsnorm")
    if "moe" in lp:
        out, rows = ffn.apply_held_moe(lp["moe"], x, t, held)
        return h + out, rows
    return h + ffn.apply_mlp(lp["mlp"], x, t), None


def decoder_scores(params, image_tokens, questions, cfg, held, yes: int,
                   no: int):
    """image_tokens (B, T, d), questions (K, L) int -> (P(yes) (B, K),
    held experts' assignments per MoE layer (n_moe, len(held)) int32)."""
    t = cfg.text
    b, n_img, d = image_tokens.shape
    k, q_len = questions.shape
    q_emb = jnp.take(params["embed"], questions, axis=0)
    h = jnp.concatenate(
        [jnp.broadcast_to(image_tokens[:, None], (b, k, n_img, d)),
         jnp.broadcast_to(q_emb[None], (b, k, q_len, d))], axis=2)
    h = h.reshape(b * k, n_img + q_len, d)
    pos = jnp.broadcast_to(jnp.arange(n_img + q_len), h.shape[:2])
    rope = rope_for_heads(pos, t.mla.qk_rope_head_dim, t.rope_theta)
    for lp in params["dense"]:
        h, _ = _layer(lp, h, t, rope, held)

    def step(h, lp):
        return _layer(lp, h, t, rope, held)

    h, rows = jax.lax.scan(step, h, params["moe"])
    last = apply_norm(params["final_norm"], h[:, -1], t, "rmsnorm")
    logits = last @ params["head"][:, jnp.asarray([yes, no])]
    return yes_share(logits).reshape(b, k), rows


def frames_to_images(frames, image_hw: int):
    """Frames (B, H, W, 3) in [0, 1) -> the centred image_hw square, in
    [-1, 1] (SigLIP's (x - 0.5) / 0.5)."""
    top = (frames.shape[1] - image_hw) // 2
    left = (frames.shape[2] - image_hw) // 2
    crop = frames[:, top:top + image_hw, left:left + image_hw]
    return (crop - 0.5) / 0.5


def vlm_tower(params, frames, *, cfg):
    with jax.default_matmul_precision("highest"):
        return moonvit(params, frames_to_images(frames, cfg.vision.image_hw),
                       cfg.vision)


def vlm_decoder(params, image_tokens, questions, *, cfg, held, yes, no):
    with jax.default_matmul_precision("highest"):
        return decoder_scores(params, image_tokens, questions, cfg, held,
                              yes, no)


def init_kimi_vl(key, cfg, held):
    k1, k2 = jax.random.split(key)
    return {"tower": init_moonvit(k1, cfg.vision, cfg.text.d_model),
            "decoder": init_decoder(k2, cfg, held)}


@dataclass(eq=False)
class KimiVL:
    """One deployed model: weights (``init_kimi_vl``'s layout), the
    experts this device holds, and the yes/no token ids. It scores in
    two programs, ``jit_vlm_tower`` and ``jit_vlm_decoder``, which take
    the weights as arguments."""
    cfg: object
    params: dict
    held: tuple
    yes: int
    no: int

    def __post_init__(self):
        def tower(params, frames):
            return vlm_tower(params, frames, cfg=self.cfg)

        def decoder(params, tokens, questions):
            return vlm_decoder(params, tokens, questions, cfg=self.cfg,
                               held=self.held, yes=self.yes, no=self.no)
        tower.__name__ = tower.__qualname__ = "vlm_tower"
        decoder.__name__ = decoder.__qualname__ = "vlm_decoder"
        self._tower, self._decoder = jax.jit(tower), jax.jit(decoder)

    def tower(self, frames):
        """Frames (B, H, W, 3) in [0, 1) -> image tokens (B, T, d)."""
        return self._tower(self.params["tower"], frames)

    def scores(self, tokens, questions):
        """Image tokens (B, T, d), questions (K, L) int -> (P(yes) (B, K),
        held experts' assignments per MoE layer (n_moe, len(held)))."""
        return self._decoder(self.params["decoder"], tokens, questions)


@dataclass(eq=False)
class VLMQuestion:
    """A predicate's level: one question (token ids) put to a model. As a
    cascade ``model_fn`` it scores frames (B, H, W, 3) -> P(yes) (B,);
    ``engine/ingest`` batches the questions that share a model."""
    model: KimiVL
    ids: np.ndarray

    def __call__(self, frames):
        scores, _ = self.model.scores(self.model.tower(frames),
                                      jnp.asarray(self.ids)[None])
        return scores[:, 0]
