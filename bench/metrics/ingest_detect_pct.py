"""Share (%) of the window that ``engine/ingest.IngestPipeline`` spent
in its ``ingest.detect`` spans, by their self time: the skip detector:
the frames' signatures and the per-row skip loop. Program span."""
from bench import program_spans


def read(record):
    return program_spans.self_pct(record, "ingest.detect")
