"""ResNet-50's GEMM kernels' share of their roofline: the least time the
chip could take for the window's 54 GEMMs per call (53 convolutions as
im2col GEMMs and the fc), computed from the configuration's shapes for
whole calls whatever engine ran them (``bench/resnet_flops.py``), over
the summed device time of the GEMM programs in the trace (the name list
of ``gemm_roofline.cnn``).  Moves ``frames_per_s``."""

from bench import core, resnet_flops, tracing

GEMM_PROGRAMS = core.load_module("metrics", "gemm_roofline.cnn").GEMM_PROGRAMS


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not ctx["calls"]:
        return None
    busy = tracing.matching_ns(tr["modules"], GEMM_PROGRAMS, tr["lo"],
                               tr["hi"]) / 1e9
    if busy <= 0:
        return None
    least = ctx["calls"] * resnet_flops.call_least_time(
        ctx["config"], ctx["frames_per_call"], ctx["peaks"])
    return 100.0 * least / busy
