"""Share (%) of the window that ``engine/ingest.IngestPipeline`` spent
in its ``ingest.grade`` spans, by their self time: grading the scores
into the index: decided labels, the candidate margins, the top-k cap and
the index's writes. Program span."""
from bench import program_spans


def read(record):
    return program_spans.self_pct(record, "ingest.grade")
