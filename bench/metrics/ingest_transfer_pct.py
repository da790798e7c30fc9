"""Share (%) of the window that ``engine/ingest.IngestPipeline`` spent
handing each chunk to the device, by self time: its ``ingest.transfer``
span (``jnp.asarray``, which returns before the copy ends) and its
``ingest.anchor_wait`` span (the wait on the first program, where the
copy and the runtime's host re-layout finish; it also holds the anchor
program's own device time, under a tenth of it on a v5e). Program span."""
from bench import program_spans


def read(record):
    return program_spans.self_pct(record, "ingest.transfer",
                                  "ingest.anchor_wait")
