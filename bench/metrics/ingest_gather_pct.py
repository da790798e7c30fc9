"""Share (%) of the window that ``engine/ingest.IngestPipeline`` spent
in its ``ingest.gather`` spans, by their self time: gathering the
chunk's scored frames into one host array, padded to the chunk's shape.
Program span."""
from bench import program_spans


def read(record):
    return program_spans.self_pct(record, "ingest.gather")
