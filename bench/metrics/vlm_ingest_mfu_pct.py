"""Share (%) of the chip's bf16 peak that the index's required
operations make over the window: for every frame scored, the CNN first
levels, the MoonViT tower and projector, and each question's decoder
sequence, plus the held experts' work from the program's count of
token-expert assignments (``vlm_expert_rows``, scaled to the scored
frames' share of the decoder tokens, so padding is not counted); over
the window's length times the peak. Host clock over program counters."""
from bench import reference_kimi_vl, roofline, roofline_kimi_vl as rk


def read(record):
    run = record.get("run") or {}
    if (record.get("kind") != "vlm_ingest_stream" or not run.get("refs")
            or not run.get("vlm_tokens")):
        return None
    m = reference_kimi_vl.model_of(record["config"])
    seq = (m["image_hw"] // m["patch"] // m["merge"]) ** 2 \
        + run["question_tokens"]
    k = run["questions"]
    per_frame = (sum(roofline.cnn_flops(p["levels"][0])
                     for p in record["config"]["predicates"])
                 + rk.tower_flops(m) + k * rk.decoder_flops(m, seq))
    real = run["refs"] * k * seq / run["vlm_tokens"]
    flops = run["refs"] * per_frame \
        + run["vlm_expert_rows"] * real * rk.expert_row_flops(m)
    return 100.0 * flops / (run["window_s"]
                            * record["peaks"]["bf16_flops_per_s"])
