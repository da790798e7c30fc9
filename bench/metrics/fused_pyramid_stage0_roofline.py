"""Share (%) of its roofline that ``kernels/image_transform.
fused_pyramid_stage0`` reached: the operations and bytes one call over a
chunk needs (``bench/roofline.stage0_kernel_work``), times the kernel's
calls in the window, over the least time the chip could take for them,
against the kernel's device time in the trace. The kernel is the ingest
programs' only ``tpu_custom_call`` op until it carries a stable
``name=``. Device trace."""
from bench import roofline, trace


def read(record):
    if record["kind"] != "ingest_stream" or record["trace"] is None:
        return None
    secs, calls = trace.ops_matching(record["trace"], trace.MOSAIC)
    if calls == 0 or secs <= 0:
        return None
    run = record["run"]
    ops, nbytes = roofline.stage0_kernel_work(
        run["chunk"], run["base"], run["kernel_levels"], run["stage0"])
    share, _ = roofline.roofline_share(calls * ops, calls * nbytes, secs,
                                       record["peaks"])
    return share
