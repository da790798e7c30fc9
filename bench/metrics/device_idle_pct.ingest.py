"""Share (%) of the traced window in which no operation ran on the
device, while the stream was ingested. Device trace."""


def read(record):
    if record["kind"] != "ingest_stream" or record["trace"] is None:
        return None
    t = record["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
