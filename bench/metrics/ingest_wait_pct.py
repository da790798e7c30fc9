"""Share (%) of the window that ``engine/ingest.IngestPipeline`` spent
in its ``ingest.wait`` spans, by their self time: waiting on the host
(``np.asarray``) for each head program's result. The wait on the anchor
is read by ``ingest_transfer_pct``. Program span."""
from bench import program_spans


def read(record):
    return program_spans.self_pct(record, "ingest.wait")
