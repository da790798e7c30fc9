"""kimi-vl-a3b [vlm, moe]: a MoonViT vision tower and a DeepSeek-V3-style
decoder. [hf moonshotai/Kimi-VL-A3B-Instruct config.json; arXiv:2504.07491]

Decoder (the model's ``text_config``): hidden 2048, 27 layers, the first
dense (width 11264), the rest MoE with 64 routed experts of width 1408,
top-6 by sigmoid score plus a per-expert correction bias (noaux_tc), the
chosen scores normalised to 1 and scaled by 2.446, and 2 shared experts;
MLA without q-LoRA (kv_lora 512, qk nope 128 + rope 64, v 128, 16 heads,
rope_theta 800000); RMSNorm eps 1e-5; vocabulary 163840.

Tower (the model's ``vision_config``; SigLIP-so400m widths): 14 px
patches, hidden 1152, 27 pre-LayerNorm layers of 16 heads with qkv bias
and a tanh-GELU MLP of width 4304, a learned 64x64 absolute position
table interpolated to the patch grid, 2D RoPE on q and k; then a final
LayerNorm, a 2x2 patch merge and the projector (LayerNorm 1152, Linear
4608->4608, GELU, Linear 4608->2048) into the decoder's token stream.
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.configs.base import ArchConfig, MLAConfig, MoEConfig


@dataclass(frozen=True)
class MoonViTConfig:
    patch: int = 14
    d_model: int = 1152
    n_layers: int = 27
    n_heads: int = 16
    d_ff: int = 4304
    pos_grid: int = 64          # learned absolute table, pos_grid^2 rows
    merge: int = 2              # merge x merge patches per image token
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    image_hw: int = 224         # the square the tower reads

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def grid(self) -> int:
        return self.image_hw // self.patch

    @property
    def image_tokens(self) -> int:
        return (self.grid // self.merge) ** 2


@dataclass(frozen=True)
class KimiVLConfig:
    text: ArchConfig
    vision: MoonViTConfig
    first_k_dense: int = 1      # leading dense layers of the decoder


def from_hf(text: dict, vision: dict | None = None,
            router_experts: int | None = None) -> KimiVLConfig:
    """A config from the model's HF-named keys (``text``: its
    ``text_config`` as the catalog gives it; ``vision``: MoonViTConfig's
    fields). ``router_experts`` is the router's width where
    ``n_routed_experts`` counts the experts one chip holds. The router is
    the published one (sigmoid scores, noaux_tc top-k, weights normalised
    to 1); another is refused."""
    router = (text["scoring_func"], text["topk_method"],
              text["norm_topk_prob"])
    if router != ("sigmoid", "noaux_tc", True):
        raise ValueError(f"router {router} is not Kimi-VL's "
                         f"('sigmoid', 'noaux_tc', True)")
    experts = int(router_experts or text["n_routed_experts"])
    arch = ArchConfig(
        name="kimi-vl-a3b", family="vlm",
        n_layers=int(text["num_hidden_layers"]),
        d_model=int(text["hidden_size"]),
        n_heads=int(text["num_attention_heads"]),
        n_kv_heads=int(text["num_key_value_heads"]),
        d_ff=int(text["intermediate_size"]),
        vocab_size=int(text["vocab_size"]),
        head_dim=int(text["qk_nope_head_dim"]) + int(text["qk_rope_head_dim"]),
        rope_theta=float(text["rope_theta"]),
        norm_eps=float(text["rms_norm_eps"]),
        max_seq_len=int(text["max_position_embeddings"]),
        mla=MLAConfig(q_lora_rank=text["q_lora_rank"],
                      kv_lora_rank=int(text["kv_lora_rank"]),
                      qk_nope_head_dim=int(text["qk_nope_head_dim"]),
                      qk_rope_head_dim=int(text["qk_rope_head_dim"]),
                      v_head_dim=int(text["v_head_dim"])),
        moe=MoEConfig(num_experts=experts,
                      top_k=int(text["num_experts_per_tok"]),
                      d_ff_expert=int(text["moe_intermediate_size"]),
                      num_shared_experts=int(text["n_shared_experts"]),
                      d_ff_shared=int(text["moe_intermediate_size"]),
                      routed_scaling_factor=float(
                          text["routed_scaling_factor"])),
        dtype="float32",
        source="[hf moonshotai/Kimi-VL-A3B-Instruct; arXiv:2504.07491]")
    return KimiVLConfig(text=arch, vision=MoonViTConfig(**(vision or {})),
                        first_k_dense=int(text["first_k_dense_replace"]))

