"""Kimi-VL as an ingest predicate head (configs/kimi_vl_a3b,
models/vision, models/kimi_vl, models/ffn.apply_held_moe, engine/ingest)
against the plain reference (bench/reference_kimi_vl.py), at a tiny size
with seeded weights: the tower, MLA without q-LoRA, the decoder's yes/no
scores, the expert-parallel share, dropless routing, and the pipeline's
index."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = str(Path(__file__).resolve().parents[1])
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import reference_kimi_vl as ref  # noqa: E402
from repro.configs import kimi_vl_a3b  # noqa: E402
from repro.models import attention as attn  # noqa: E402
from repro.models import ffn  # noqa: E402
from repro.models.kimi_vl import (KimiVL, VLMQuestion, init_kimi_vl,  # noqa: E402
                                  vlm_decoder, vlm_tower)
from repro.models.vision import bicubic_matrix  # noqa: E402

HW = 20            # stored frames; the tower reads the centred 16 px
# the benchmark's configuration: the published text_config but for the
# keys its ``reduced`` names
SERVED = json.loads((Path(ROOT) / "bench" / "configs" /
                     "kimi-vl-a3b-ingest-240.json").read_text())
TEXT = dict(SERVED, vocab_size=97, hidden_size=64,
            intermediate_size=96, moe_intermediate_size=24,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, num_experts_per_tok=3)
VISION = dict(patch=4, d_model=32, n_layers=2, n_heads=4, d_ff=40,
              pos_grid=8, merge=2, rope_theta=10000.0, norm_eps=1e-5,
              image_hw=16)
EXPERTS, HELD = 16, (0, 1)
WEIGHTS = {"bias_std": 0.02, "pos_std": 0.02, "embed_std": 1.0,
           "router_bias_std": 0.01, "head_scale": 1.0}
TOL = 2e-5


def config(held=HELD, experts=EXPERTS):
    return dict(TEXT, n_routed_experts=len(held), vision_config=VISION,
                deployment={"router_experts": experts,
                            "held_experts": list(held)})


def program_cfg(held=HELD, experts=EXPERTS):
    return kimi_vl_a3b.from_hf(TEXT, VISION, experts)


def model_of(held=HELD, experts=EXPERTS):
    return dict(ref.model_of(config(held, experts)), yes=5, no=11)


def frames(n, seed=0):
    u8 = np.random.default_rng(seed).integers(0, 256, (n, HW, HW, 3))
    return jnp.asarray(u8 / 256.0, jnp.float32)


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: ref.init_params(k, model_of(), WEIGHTS))(
        jax.random.PRNGKey(7))


QUESTIONS = jnp.asarray(np.random.default_rng(3).integers(0, 97, (3, 5)),
                        jnp.int32)


def test_published_widths():
    """``from_hf`` maps the served configuration to the published
    widths; only the depth and the vocabulary are cut, and the router
    keeps all 64 experts."""
    dep = SERVED["deployment"]
    c = kimi_vl_a3b.from_hf(SERVED, SERVED["vision_config"],
                            dep["router_experts"])
    t, v = c.text, c.vision
    assert (t.d_model, t.d_ff, t.n_heads) == (2048, 11264, 16)
    assert (t.n_layers, t.vocab_size) == (5, 163840 // 8) and \
        c.first_k_dense == 1
    assert t.mla.q_lora_rank is None and t.mla.kv_lora_rank == 512
    assert (t.mla.qk_nope_head_dim, t.mla.qk_rope_head_dim,
            t.mla.v_head_dim) == (128, 64, 128)
    assert (t.moe.num_experts, t.moe.top_k, t.moe.d_ff_expert,
            t.moe.num_shared_experts) == (64, 6, 1408, 2)
    assert t.moe.routed_scaling_factor == 2.446
    assert (t.rope_theta, t.norm_eps) == (800000, 1e-5)
    assert (v.d_model, v.n_layers, v.d_ff, v.patch, v.image_tokens) == \
        (1152, 27, 4304, 14, 64)


@pytest.mark.parametrize("key,value", [("scoring_func", "softmax"),
                                       ("topk_method", "greedy"),
                                       ("norm_topk_prob", False)])
def test_another_router_is_refused(key, value):
    with pytest.raises(ValueError, match="router"):
        kimi_vl_a3b.from_hf(dict(TEXT, **{key: value}), VISION)


def test_program_and_reference_weights_share_one_layout(params):
    mine = jax.eval_shape(lambda k: init_kimi_vl(k, program_cfg(), HELD),
                          jax.random.PRNGKey(0))
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(mine)] == \
        [a.shape for a in jax.tree.leaves(params)]


def test_bicubic_rows_sum_to_one_and_identity_at_same_size():
    m = bicubic_matrix(4, 8)
    np.testing.assert_allclose(m.sum(1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(bicubic_matrix(8, 8), np.eye(8), atol=1e-6)
    np.testing.assert_allclose(m, ref._bicubic(4, 8), atol=1e-6)


def test_tower_matches_reference(params):
    cfg, m = program_cfg(), model_of()
    x = frames(3)
    got = jax.jit(lambda p, f: vlm_tower(p, f, cfg=cfg))(params["tower"], x)
    want = ref.tower(params["tower"], x, m, "highest")
    assert got.shape == (3, 4, 64)
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)


def test_mla_without_q_lora_matches_reference(params):
    cfg, m = program_cfg(), model_of()
    lp = params["decoder"]["dense"][0]["attn"]
    assert "w_q" in lp and "w_dq" not in lp
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 64))
    pos = jnp.broadcast_to(jnp.arange(9), (2, 9))
    from repro.models.common import rope_for_heads
    cos, sin = rope_for_heads(pos, 8, cfg.text.rope_theta)
    with jax.default_matmul_precision("highest"):
        got, _ = attn.mla_attention_full(lp, x, cfg.text, cos, sin)
    want = ref._mla(lp, x, m, lambda s, a, b: ref._mm(s, a, b, "highest"))
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)


def test_mla_with_q_lora_keeps_its_layout():
    from repro.configs.registry import smoke_config
    p = attn.init_mla(jax.random.PRNGKey(0), smoke_config("deepseek-v2-236b"))
    assert {"w_dq", "q_norm", "w_uq"} <= set(p) and "w_q" not in p


def test_decoder_scores_match_reference(params):
    cfg, m = program_cfg(), model_of()
    img = ref.tower(params["tower"], frames(4, 1), m, "highest")
    got, rows = jax.jit(lambda p, i, q: vlm_decoder(
        p, i, q, cfg=cfg, held=HELD, yes=5, no=11))(params["decoder"], img,
                                                   QUESTIONS)
    want, tie = ref.decoder(params["decoder"], img, QUESTIONS, m, "highest")
    assert got.shape == (4, 3) and rows.shape == (2, 2)
    assert np.all(np.asarray(tie) > 1e-4)     # no routing near a tie
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)
    assert np.all((np.asarray(got) > 0) & (np.asarray(got) < 1))


def _moe_layer(params):
    lp = jax.tree.map(lambda a: a[0], params["decoder"]["moe"])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 7, 64))
    return lp, x


def test_expert_parallel_shares_add_up_to_the_uncut_layer():
    """8 ranks of 2 experts each: their routed parts, with the shared
    experts counted once, add up to the whole layer of 16 experts."""
    whole = tuple(range(EXPERTS))
    full = jax.jit(lambda k: ref.init_params(k, model_of(whole), WEIGHTS))(
        jax.random.PRNGKey(9))
    lp, x = _moe_layer(full)
    t = program_cfg(whole).text
    mmul = lambda s, a, b: ref._mm(s, a, b, "highest")  # noqa: E731
    want, _ = ref._moe(lp, x, model_of(whole), mmul)
    shared = ffn.apply_mlp(lp["shared"], x, t)
    total = shared
    for r in range(8):
        held = (2 * r, 2 * r + 1)
        share = dict(lp, **{k: lp[k][np.asarray(held)] for k in
                            ("w_gate_e", "w_up_e", "w_down_e")})
        with jax.default_matmul_precision("highest"):
            out, rows = ffn.apply_held_moe(share, x, t, held)
        total = total + (out - shared)
    np.testing.assert_allclose(total, want, rtol=TOL, atol=TOL)


def test_dropless_under_a_skewed_router(params):
    """A router bias that sends every token to the held experts: every
    assignment is computed, none dropped, and the layer matches the
    reference."""
    lp, x = _moe_layer(params)
    t = program_cfg().text
    skew = dict(lp, router_bias=lp["router_bias"].at[jnp.asarray(HELD)]
                .add(10.0))
    with jax.default_matmul_precision("highest"):
        out, rows = ffn.apply_held_moe(skew, x, t, HELD)
    assert int(rows.sum()) == x.shape[0] * x.shape[1] * len(HELD)
    assert np.all(np.asarray(rows) == x.shape[0] * x.shape[1])
    want, _ = ref._moe(skew, x, model_of(),
                       lambda s, a, b: ref._mm(s, a, b, "highest"))
    np.testing.assert_allclose(out, want, rtol=TOL, atol=TOL)


# ------------------------------------------------------- on the pipeline --
def _cascades(params, with_vlm: bool):
    from repro.core.transforms import Representation
    from repro.engine.scan import CompiledCascade
    from test_query_engine import _toy_cascade

    cnn = [_toy_cascade(c, s, hw=HW) for c, s in [("a", 1), ("b", 2)]]
    if not with_vlm:
        return cnn
    model = KimiVL(cfg=program_cfg(), params=params, held=HELD, yes=5,
                   no=11)
    return cnn + [CompiledCascade(
        concept=f"q{k}", cascade_id=(10 + k,),
        reps=[Representation(HW, "rgb")],
        model_fns=[VLMQuestion(model, np.asarray(QUESTIONS[k]))],
        thresholds=[(0.3, 0.7)]) for k in range(3)]


def test_ingest_pipeline_vlm_head_matches_reference(params):
    from repro.engine.ingest import IngestPipeline

    x = np.asarray(frames(24, 4))
    pipe = IngestPipeline(_cascades(params, True), 24, chunk=16,
                          skip=False)
    pipe.run(x)
    want, _ = ref.score_frames(
        lambda lo, hi: jnp.asarray(x[lo:lo + 8]), 24, 8, params,
        model_of(), QUESTIONS)
    for k in range(3):
        np.testing.assert_allclose(pipe.index.scores[f"q{k}"], want[k],
                                   rtol=TOL, atol=TOL)
    # 2 chunks of 16 (the second padded) x 3 questions x (4 + 5) tokens
    assert pipe.stats.vlm_tokens == 2 * 16 * 3 * 9
    assert pipe.stats.vlm_expert_rows > 0
    # a VLM question is a model_fn of its own too
    one = VLMQuestion(_cascades(params, True)[2].model_fns[0].model,
                      np.asarray(QUESTIONS[0]))
    np.testing.assert_allclose(one(jnp.asarray(x[:8])), want[0, :8],
                               rtol=TOL, atol=TOL)


def test_cnn_columns_unchanged_by_a_vlm_head(params):
    from repro.engine.ingest import IngestPipeline

    x = np.asarray(frames(40, 5))
    alone = IngestPipeline(_cascades(params, False), 40, chunk=16)
    alone.run(x)
    both = IngestPipeline(_cascades(params, True), 40, chunk=16)
    both.run(x)
    for c in ("a", "b"):
        assert np.array_equal(alone.index.scores[c], both.index.scores[c])
        assert np.array_equal(alone.index.candidates[c],
                              both.index.candidates[c])
    assert np.array_equal(alone.index.alias, both.index.alias)
    assert alone.stats.vlm_tokens == 0 and alone.stats.vlm_expert_rows == 0


def test_vlm_anchor_is_refused(params):
    from repro.engine.ingest import IngestPipeline

    cas = _cascades(params, True)
    pipe = IngestPipeline([cas[2]] + cas[:2], 8, chunk=8, skip=False)
    with pytest.raises(ValueError, match="anchor"):
        pipe.run(np.asarray(frames(8)))
