"""Device time (%) of the fused pyramid+stage-0 kernel over the traced
window. Device trace."""
from bench import trace


def read(record):
    if record["kind"] != "ingest_stream" or record["trace"] is None:
        return None
    secs, calls = trace.ops_matching(record["trace"], trace.MOSAIC)
    if calls == 0:
        return None
    return 100.0 * secs / record["trace"]["window_s"]
