"""Share (%) of the window that ``engine/ingest`` spent in its
``ingest.vlm_decoder`` spans, by their self time: the decoder program
over each chunk's frames x questions, from its dispatch to its scores
on the host. Program span."""
from bench import vlm_spans


def read(record):
    return vlm_spans.self_pct(record, "ingest.vlm_decoder")
