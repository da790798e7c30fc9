"""Share (%) of the chip's bf16 peak that the index's required CNN
operations make over the window: every predicate's first-level FLOPs
times the frames scored in the window (no padding), over the window's
length times the peak. Host clock over the program's count of scored
frames."""
from bench import roofline


def read(record):
    if record["kind"] != "ingest_stream" or record["run"]["refs"] == 0:
        return None
    run = record["run"]
    flops = run["refs"] * sum(roofline.cnn_flops(p["levels"][0])
                              for p in record["config"]["predicates"])
    return 100.0 * flops / (run["window_s"]
                            * record["peaks"]["bf16_flops_per_s"])
