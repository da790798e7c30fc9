"""Device time (%) of every op but the fused pyramid+stage-0 kernel over
the traced window: the other predicates' first-level CNNs (``models/cnn``
under ``jax.jit`` in ``engine/ingest``) and the rest of the anchor's
ingest program. Device trace."""
from bench import trace


def read(record):
    if record["kind"] != "ingest_stream" or record["trace"] is None:
        return None
    t = record["trace"]
    kernel, _ = trace.ops_matching(t, trace.MOSAIC)
    total = sum(t["op_s"].values())
    if total - kernel <= 0:
        return None
    return 100.0 * (total - kernel) / t["window_s"]
