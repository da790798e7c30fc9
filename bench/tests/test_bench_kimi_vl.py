"""The pieces the cells ``ingest-live`` and ``ingest-vlm-distinct`` add:
their catalog entries, their readers on synthetic records, the
plain reference's independence from the program, the fixed camera's
frames, and whole runs of both cells on the CPU at a tiny size, with a
planted fault and the lower-precision controls caught."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import harness, reference, roofline
from bench import reference_kimi_vl as ref
from bench import roofline_kimi_vl as rk
from bench.tests.tiny import tiny_catalog
from repro import tracing
from repro.tracing import Span

ROOT = harness.Path(__file__).resolve().parents[2]
SEED = 2**33 + 29
VLM_METRICS = ["vlm_ingest_mfu_pct", "vlm_tower_pct", "vlm_decoder_pct",
               "vlm_expert_roofline"]
CELLS = {"ingest-live": ("tahoma-ingest-240", "camera_stream",
                         ["ingest_chunk_fill_pct"]),
         "ingest-vlm-distinct": ("kimi-vl-a3b-ingest-240",
                                 "vlm_ingest_stream", VLM_METRICS)}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def real_catalog():
    return harness.Catalog(ROOT)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_catalog_finds_the_new_cells(cell):
    cat = real_catalog()
    config, kind, metrics = CELLS[cell]
    spec = cat.cell(cell)
    assert spec["config"] == config and spec["chips"] == 1
    cat.config(config)
    assert cat.traffic(spec["traffic"])["kind"] == kind
    assert cat.load_kind(kind).__name__ == "Load"
    assert [m["name"] for m in cat.end_to_end(cell)] == \
        ["ingest_frames_per_s", "setup_s"]
    assert [m["name"] for m in cat.per_layer(cell)] == metrics
    for m in metrics:
        cat.reader(m)


def test_published_widths_in_the_configuration():
    cfg = real_catalog().config("kimi-vl-a3b-ingest-240")
    m = ref.model_of(cfg)
    assert (m["d"], m["heads"], m["kv_rank"], m["nope"], m["rope"],
            m["vdim"]) == (2048, 16, 512, 128, 64, 128)
    assert (m["dense_ff"], m["expert_ff"], m["shared_ff"], m["top_k"],
            m["experts"]) == (11264, 1408, 2816, 6, 64)
    assert cfg["q_lora_rank"] is None
    assert len(m["held"]) == cfg["n_routed_experts"] == 8
    assert cfg["vocab_size"] * 8 == cfg["deployment"]["published"][
        "vocab_size"]
    assert (m["vd"], m["vlayers"], m["vff"], m["patch"]) == \
        (1152, 27, 4304, 14)
    assert set(cfg["check_limits"]) == set(cfg["check_limits_why"]) == \
        {"gap_max", "vlm_gap_max"}


def test_work_per_chunk_at_published_widths():
    """64 frames x 4 questions x 80 tokens: the tower ~14.3 TFLOP, the
    decoder without routed experts ~8.6, each routed assignment
    2 x 3 x 2048 x 1408 operations."""
    m = ref.model_of(real_catalog().config("kimi-vl-a3b-ingest-240"))
    assert 64 * rk.tower_flops(m) / 1e12 == pytest.approx(14.27, rel=0.01)
    assert 256 * rk.decoder_flops(m, 80) / 1e12 == \
        pytest.approx(8.55, rel=0.01)
    assert rk.expert_row_flops(m) == 2 * 3 * 2048 * 1408
    ops, nbytes = rk.expert_work(m, rows=1000, layer_calls=2)
    assert ops == 1000 * rk.expert_row_flops(m)
    assert nbytes == 2 * 8 * 3 * 2048 * 1408 * 4 \
        + 1000 * (3 * 2048 + 3 * 1408) * 4


# ------------------------------------------------------------ readers --
def chunk(first_id, t0):
    """One 100 ms chunk: the tower 40 ms, the decoder 50 ms, 10 ms of
    the chunk's own."""
    c = first_id
    return [Span("ingest.chunk", t0, t0 + 0.100, c, None),
            Span("ingest.vlm_tower", t0, t0 + 0.040, c + 1, c),
            Span("ingest.vlm_decoder", t0 + 0.040, t0 + 0.090, c + 2, c)]


# a fusion that reads a grouped product's output: its trace name (the
# op's HLO text) names the product among its operands
CONSUMER = ("%multiply_select_fusion.2 = f32[122880,2048] fusion("
            "f32[122880,2048] %ragged-dot-none.2, f32[122880] %copy-done.15)")


def record(**run):
    config = real_catalog().config("kimi-vl-a3b-ingest-240")
    base = {"window_s": 0.2, "frames": 128, "refs": 128, "chunk": 64,
            "vlm_tokens": 2 * 64 * 4 * 80, "vlm_expert_rows": 30720,
            "questions": 4, "question_tokens": 16}
    return {"kind": "vlm_ingest_stream", "traffic": {"feed_rows": 64},
            "config": config, "peaks": PEAKS,
            "trace": {"op_s": {"%ragged-dot-none.1 = f32[...]": 0.003,
                               "%ragged-dot-metadata = (s32[9])": 0.001,
                               CONSUMER: 0.3, "%fusion.7": 0.5},
                      "op_n": {"%ragged-dot-none.1 = f32[...]": 8,
                               "%ragged-dot-metadata = (s32[9])": 8,
                               CONSUMER: 8, "%fusion.7": 2}},
            "run": dict(base, **run)}


@pytest.fixture
def spans(monkeypatch):
    """A warm-up chunk, then the window's two chunks of 64 frames."""
    got = chunk(1, 0.0) + chunk(10, 1.0) + chunk(20, 1.1)
    monkeypatch.setattr(tracing, "spans", lambda: list(got))


def test_span_readers_take_self_time_over_the_window(spans):
    cat = real_catalog()
    assert cat.reader("vlm_tower_pct")(record()) == pytest.approx(40.0)
    assert cat.reader("vlm_decoder_pct")(record()) == pytest.approx(50.0)


def test_mfu_counts_required_work_over_the_window():
    cat = real_catalog()
    rec = record()
    m = ref.model_of(rec["config"])
    cnn = sum(roofline.cnn_flops(p["levels"][0])
              for p in rec["config"]["predicates"])
    want = (128 * (cnn + rk.tower_flops(m) + 4 * rk.decoder_flops(m, 80))
            + 30720 * rk.expert_row_flops(m)) / (0.2 * 197e12) * 100
    assert cat.reader("vlm_ingest_mfu_pct")(rec) == pytest.approx(want)
    # padding: half the decoder tokens were of padded rows
    assert cat.reader("vlm_ingest_mfu_pct")(record(refs=64)) == \
        pytest.approx((64 * (cnn + rk.tower_flops(m)
                             + 4 * rk.decoder_flops(m, 80))
                       + 15360 * rk.expert_row_flops(m))
                      / (0.2 * 197e12) * 100)


def test_expert_roofline_reads_the_ragged_dot_ops():
    """The grouped products and their tiling are counted by their own
    names (4 ms); the fusion that reads one of them is not."""
    rec = record()
    m = ref.model_of(rec["config"])
    ops, nbytes = rk.expert_work(m, 30720, 2 * 4)
    want = 100 * max(ops / 197e12, nbytes / 819e9) / 0.004
    assert real_catalog().reader("vlm_expert_roofline")(rec) == \
        pytest.approx(want)


@pytest.mark.parametrize("metric", VLM_METRICS)
def test_readers_read_none_from_an_empty_record(metric, monkeypatch):
    monkeypatch.setattr(tracing, "spans", lambda: [])
    read = real_catalog().reader(metric)
    assert read({}) is None
    assert read({"kind": "vlm_ingest_stream", "traffic": {"feed_rows": 64},
                 "trace": {"op_s": {}, "op_n": {}}, "peaks": PEAKS,
                 "config": record()["config"],
                 "run": {"window_s": 1.0, "frames": 0, "refs": 0,
                         "chunk": 64, "vlm_tokens": 0,
                         "vlm_expert_rows": 0}}) is None
    # the fixed camera's and the distinct frames' records are not read
    assert read(dict(record(), kind="ingest_stream")) is None


def test_chunk_fill_reads_scored_frames_over_the_rows_scored():
    """Two 64-row chunks scored 5 frames: 5 of 128 rows carried one."""
    read = real_catalog().reader("ingest_chunk_fill_pct")
    run = {"window_s": 1.0, "frames": 1024, "refs": 5, "chunks": 2,
           "chunk": 64}
    assert read({"kind": "camera_stream", "run": run}) == \
        pytest.approx(100 * 5 / 128)
    assert read({}) is None
    # no chunk scored, or a record without the count (``ingest_stream``)
    assert read({"kind": "camera_stream",
                 "run": dict(run, refs=0, chunks=0)}) is None
    assert read(dict(record(), kind="ingest_stream")) is None


# ---------------------------------------------------------- reference --
def test_reference_imports_nothing_of_the_program():
    """The reference scores frames in a process that cannot import the
    program (``src`` is not on its path) and loads none of it."""
    code = (
        "import json, sys, jax, jax.numpy as jnp\n"
        "from bench import reference_kimi_vl as ref\n"
        "m = json.loads(sys.argv[1])\n"
        "w = {'bias_std': .02, 'pos_std': .02, 'embed_std': 1.,"
        " 'router_bias_std': .01, 'head_scale': 1.}\n"
        "p = ref.init_params(jax.random.PRNGKey(0), m, w)\n"
        "f = lambda lo, hi: jnp.full((2, 28, 28, 3), .5)\n"
        "s, t = ref.score_frames(f, 2, 2, p, m, jnp.zeros((2, 3), int))\n"
        "assert s.shape == (2, 2)\n"
        "print(json.dumps(sorted(k for k in sys.modules"
        " if k.split('.')[0] == 'repro')))\n")
    res = subprocess.run(
        [sys.executable, "-c", code, json.dumps(tiny_model())], cwd=ROOT,
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)})
    assert res.returncode == 0, res.stderr[-2000:]
    assert json.loads(res.stdout.strip().splitlines()[-1]) == []


# ------------------------------------------------------------- camera --
def test_camera_frames_alias_exactly_the_held_repeats():
    """Every held repeat falls under the detector's threshold, every
    scene change far above it; repeats differ from their scene's first
    frame by at most 1/256 a pixel, and not at all in no frame."""
    from bench.traffic.camera_stream import camera_frames, scene_starts
    from repro.engine.ingest import frame_signature

    cfg = real_catalog().config("tahoma-ingest-240")
    thr, res = cfg["pipeline"]["skip_threshold"], cfg["pipeline"]["skip_res"]
    hw, n, scene = 48, 160, (16, 64)
    frames = np.asarray(camera_frames(SEED, np.arange(n), hw, scene))
    starts = scene_starts(SEED, n, *scene)
    first = starts[np.searchsorted(starts, np.arange(n), side="right") - 1]
    assert np.all(np.abs(frames - frames[first]) <= 1 / 256 + 1e-7)
    held = np.arange(n) != first
    assert np.all(np.any(frames[held] != frames[first[held]],
                         axis=(1, 2, 3)))
    diffs = reference.stream_diffs(frame_signature(frames, res))
    assert np.all(diffs[held] < thr / 4)
    assert np.all(diffs[~held] > 4 * thr)
    assert np.array_equal(reference.aliases(diffs, thr), first)
    # any row is made from the seed alone, whichever block it comes in
    again = np.asarray(camera_frames(SEED, np.arange(n)[::-7], hw, scene))
    assert np.array_equal(again, frames[::-7])


def test_live_cell_runs_correct_on_cpu(tmp_path):
    cat = harness.Catalog(tiny_catalog(tmp_path, rows=512))
    load = None

    class Keep(cat.load_kind("camera_stream")):
        def release(self):
            nonlocal load
            load = self
            self.rec = self.record()
            super().release()
    cat.load_kind = lambda kind: Keep
    out = harness.run_cell(cat, "ingest-live", SEED, 0.2, False)
    assert out["correct"], out
    assert out["checks"]["gap_max"]["value"] <= 8e-6
    idx = load.index
    fed = np.arange(load.fed)
    own = idx.alias[fed] == fed
    # roughly one frame a scene is scored (scenes of 16-64 frames)
    assert 1 / 64 <= own.mean() <= 1 / 16 + 0.01
    # the window's chunks, each padded to 32 rows around its few frames
    rec = load.rec
    assert 0 < rec["chunks"] <= min(rec["refs"], rec["frames"] // 32)
    fill = cat.reader("ingest_chunk_fill_pct")({"run": rec})
    assert fill == pytest.approx(100 * rec["refs"] / (32 * rec["chunks"]))
    assert fill < 20


# ---------------------------------------------------------- tiny VLM --
TEXT = dict(vocab_size=97, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=24, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            num_experts_per_tok=3, n_routed_experts=2)
VISION = dict(patch=4, d_model=32, n_layers=2, n_heads=4, d_ff=40,
              pos_grid=8, merge=2, rope_theta=10000.0, norm_eps=1e-5,
              image_hw=24)


def tiny_config(cfg: dict) -> dict:
    """The Kimi-VL configuration at tiny widths, 2 of 16 experts held."""
    return dict(cfg, **TEXT, vision_config=VISION,
                deployment=dict(cfg["deployment"], router_experts=16,
                                held_experts=[0, 1]),
                vlm=dict(cfg["vlm"], calibration_rows=64,
                         reference_block=16))


def tiny_model() -> dict:
    cfg = tiny_config(real_catalog().config("kimi-vl-a3b-ingest-240"))
    return dict(ref.model_of(cfg), yes=3, no=5)


@pytest.fixture(scope="module")
def vlm_load(tmp_path_factory):
    root = tiny_catalog(tmp_path_factory.mktemp("vlm"))
    path = root / "bench" / "configs" / "kimi-vl-a3b-ingest-240.json"
    path.write_text(json.dumps(tiny_config(json.loads(path.read_text()))))
    cat = harness.Catalog(root)
    spec = cat.cell("ingest-vlm-distinct")
    traffic = dict(cat.traffic(spec["traffic"]), feed_rows=32)
    drv = cat.load_kind(traffic["kind"])(cat.config(spec["config"]),
                                         traffic, SEED)
    drv.setup(0.3)
    drv.window(0.3)
    rec = drv.record()
    drv.release()
    return drv, rec


def test_vlm_cell_is_correct_and_counts_its_work(vlm_load):
    drv, rec = vlm_load
    limits = drv.config["check_limits"]
    attempted, failed, found = drv.check()
    assert attempted == drv.fed > 0 and failed == 0
    assert found["gap_max"] <= limits["gap_max"]
    assert found["vlm_gap_max"] <= limits["vlm_gap_max"]
    # chunks of 32 frames x 4 questions x (9 image + 16 question) tokens
    assert rec["vlm_tokens"] == rec["frames"] * 4 * (9 + 16)
    # 3 experts a token of 16, 2 held, over 2 MoE layers: about 3/16 of
    # the tokens' assignments land on a held expert
    share = rec["vlm_expert_rows"] / (rec["vlm_tokens"] * 3 * 2)
    assert 0.05 < share < 0.4


@pytest.mark.parametrize("where", ["score", "label", "candidate"])
def test_vlm_answer_altered_is_caught(vlm_load, where):
    drv, _ = vlm_load
    idx = drv.index
    q = drv.cascades[-1]
    row = int(np.flatnonzero(idx.alias[:drv.fed] == np.arange(drv.fed))[3])
    saved = (idx.scores[q.concept][row], idx.candidates[q.concept][row],
             idx.decided.column(q.key)[row])
    try:
        if where == "score":
            idx.scores[q.concept][row] += 1e-3
        elif where == "candidate":
            idx.candidates[q.concept][row] ^= True
        else:
            col = idx.decided.column(q.key)
            col[row] = 1 if col[row] != 1 else 0
        _, failed, found = drv.check()
    finally:
        idx.scores[q.concept][row] = saved[0]
        idx.candidates[q.concept][row] = saved[1]
        idx.decided.column(q.key)[row] = saved[2]
    assert failed > 0
    assert found["vlm_gap_max"] > drv.config["check_limits"]["vlm_gap_max"]


@pytest.mark.parametrize("control", ["high", "bf16"])
def test_vlm_lower_precision_control_is_caught(vlm_load, control):
    drv, _ = vlm_load
    _, failed, found = drv.check(control)
    assert failed > 0
    assert found["vlm_gap_max"] > drv.config["check_limits"]["vlm_gap_max"]


# ------------------------------------------------------- router reading --
@pytest.fixture
def tiny_vlm_catalog(tmp_path):
    root = tiny_catalog(tmp_path)
    path = root / "bench" / "configs" / "kimi-vl-a3b-ingest-240.json"
    path.write_text(json.dumps(tiny_config(json.loads(path.read_text()))))
    return harness.Catalog(root)


@pytest.mark.parametrize("fault", [0.0, 1e-3])
def test_route_gap_reads_the_programs_router(tiny_vlm_catalog, fault,
                                             monkeypatch):
    """The program's router values against the reference's: float32
    rounding apart, and a fault planted in the program's router read at
    its size."""
    from bench import vlm_route_gap
    from repro.models import ffn

    route = ffn.router_scores

    def planted(p, x):
        scores, biased = route(p, x)
        return scores, biased + fault
    monkeypatch.setattr(ffn, "router_scores", planted)
    found = vlm_route_gap.read(tiny_vlm_catalog, "ingest-vlm-distinct",
                               [SEED, SEED + 1], 32)
    assert ffn.router_scores is planted
    assert found["max_diff"] == pytest.approx(fault, abs=1e-5)
    assert found["least_tie"] > 0
    if not fault:
        assert found["flips"] == 0
