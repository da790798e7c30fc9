"""Share (%) of the window that ``engine/ingest.IngestPipeline`` spent
in its ``ingest.dispatch`` spans, by their self time: calling the
anchor's and the heads' programs, up to their return, which comes before
the device is done. Program span."""
from bench import program_spans


def read(record):
    return program_spans.self_pct(record, "ingest.dispatch")
