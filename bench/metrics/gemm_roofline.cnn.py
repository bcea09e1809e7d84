"""The CNN's GEMM kernels' share of their roofline: the least time the
chip could take for the window's CONV (as im2col GEMM) and FC GEMMs,
computed from the configuration's shapes for whole calls whatever engine
ran them (``bench/flops.py``), over the summed device time of the GEMM
executions in the trace.  The executions are the programs named below,
one per engine of the default pool.  Moves ``frames_per_s``."""

from bench import flops, tracing

#: device programs (the trace's module line) that execute a GEMM panel:
#: the pallas engine, the neon-vpu engine, the xla engine's dot and the
#: reference engine's dot
GEMM_PROGRAMS = [r"^jit_tiled_matmul\b", r"^jit_vpu_matmul\b",
                 r"^jit_dot_general\b", r"^jit_dot\b", r"^jit_matmul\b"]


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not ctx["calls"]:
        return None
    busy = tracing.matching_ns(tr["modules"], GEMM_PROGRAMS, tr["lo"],
                               tr["hi"]) / 1e9
    if busy <= 0:
        return None
    least = ctx["calls"] * flops.cnn_call_least_time(
        ctx["config"], ctx["frames_per_call"], ctx["peaks"])
    return 100.0 * least / busy
