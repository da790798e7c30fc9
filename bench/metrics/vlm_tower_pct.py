"""Share (%) of the window that ``engine/ingest`` spent in its
``ingest.vlm_tower`` spans, by their self time: the MoonViT tower and
projector program over each chunk's frames, from its dispatch to its
output's readiness. Program span."""
from bench import vlm_spans


def read(record):
    return vlm_spans.self_pct(record, "ingest.vlm_tower")
