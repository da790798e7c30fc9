"""Share (%) of its roofline that the held experts' grouped products
reached: the operations and bytes of the window's token-expert
assignments (``vlm_expert_rows``; ``bench/roofline_kimi_vl.
expert_work``) over the least time the chip could take for them,
against the device time of the ``lax.ragged_dot`` ops in the trace.
On a v5e they are Mosaic custom calls named ``%ragged-dot-none``,
``%ragged-dot-none.1`` and ``%ragged-dot-none.2`` (gate, up and down
products) and ``%ragged-dot-metadata`` (the group tiling), whose grid
runs over the groups' row tiles only. An op's trace name is its whole
HLO text, operands included, so an op is counted by its own name, the
text before `` = ``: the fusions that read the products' outputs name
them among their operands and are not counted. None when the trace
holds no such op. Device trace."""
import re

from bench import reference_kimi_vl, roofline, roofline_kimi_vl as rk

OWN = re.compile(r"%ragged-dot-(none(\.\d+)?|metadata)")


def expert_ops(reduced: dict) -> tuple[float, int]:
    """Device seconds and calls of the grouped products and their
    tiling, matched by the op's own name."""
    secs, calls = 0.0, 0
    for name, s in reduced["op_s"].items():
        if OWN.fullmatch(name.split(" = ", 1)[0].strip()):
            secs += s
            calls += reduced["op_n"][name]
    return secs, calls


def read(record):
    run = record.get("run") or {}
    if (record.get("kind") != "vlm_ingest_stream"
            or record.get("trace") is None or not run.get("vlm_tokens")):
        return None
    secs, calls = expert_ops(record["trace"])
    if calls == 0 or secs <= 0:
        return None
    m = reference_kimi_vl.model_of(record["config"])
    seq = (m["image_hw"] // m["patch"] // m["merge"]) ** 2 \
        + run["question_tokens"]
    chunks = run["vlm_tokens"] / (run["chunk"] * run["questions"] * seq)
    ops, nbytes = rk.expert_work(m, run["vlm_expert_rows"],
                                 chunks * (m["layers"] - m["dense_layers"]))
    share, _ = roofline.roofline_share(ops, nbytes, secs, record["peaks"])
    return share
