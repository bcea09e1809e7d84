"""VPU-only Pallas tiled matmul — the TPU analog of the paper's NEON cores.

The paper's heterogeneity is real silicon diversity: FPGA tile PEs next to
NEON SIMD units that multiply-accumulate over 128-bit vector lanes.  The
TPU has the same split on one die — the 128x128 MXU systolic array next to
the 8x128-lane VPU.  This kernel is ``tiled_mm`` with the MXU taken away:
the contraction runs as ``ts_k`` rank-1 broadcast updates

    acc += A[:, kk:kk+1] * B[kk:kk+1, :]

which lower to VPU element-wise FMAs (broadcast over lanes), never to a
``dot``.  It is deliberately the *slow, always-available* engine of the
pool — exactly the role NEON plays in the paper's clusters — and shares
the tiled_mm contract: fixed-size zero-padded tiles, fp32 accumulation in
VMEM scratch, fused bias+activation epilogue.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["vpu_mm_pallas"]


def _kernel(a_ref, b_ref, bias_ref, o_ref, acc_ref, *,
            k_steps: int, ts_k: int, activation: Callable | None,
            has_bias: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)           # (ts_m, ts_k)
    b = b_ref[...].astype(jnp.float32)           # (ts_k, ts_n)
    # statically unrolled: Mosaic cannot slice a lane at a dynamic index
    acc = acc_ref[...]
    for kk in range(ts_k):
        acc = acc + a[:, kk:kk + 1] * b[kk:kk + 1, :]   # VPU broadcast FMA
    acc_ref[...] = acc

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        y = acc_ref[...]
        if has_bias:
            y = y + bias_ref[...].astype(jnp.float32)
        if activation is not None:
            y = activation(y)
        o_ref[...] = y.astype(o_ref.dtype)


def vpu_mm_pallas(a: jax.Array, b: jax.Array, *,
                  bias: jax.Array | None = None,
                  activation: Callable | None = None,
                  tile: tuple[int, int, int] = (128, 128, 128),
                  out_dtype=None,
                  interpret: bool = False) -> jax.Array:
    """C[m, n] = act(A[m, k] @ B[k, n] + bias), MXU-free.  Dims must be
    multiples of ``tile`` (ops.py pads borders, same as tiled_mm)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    ts_m, ts_n, ts_k = tile
    assert m % ts_m == 0 and n % ts_n == 0 and k % ts_k == 0, (
        f"padded dims required: {(m, n, k)} vs tile {tile}")
    gm, gn, gk = m // ts_m, n // ts_n, k // ts_k
    out_dtype = out_dtype or a.dtype

    has_bias = bias is not None
    bias2d = (bias.reshape(1, n) if has_bias
              else jnp.zeros((1, n), dtype=jnp.float32))

    kernel = functools.partial(_kernel, k_steps=gk, ts_k=ts_k,
                               activation=activation, has_bias=has_bias)
    flops = 2 * m * n * k
    bytes_accessed = (a.size * a.dtype.itemsize + b.size * b.dtype.itemsize
                      + m * n * jnp.dtype(out_dtype).itemsize)
    return pl.pallas_call(
        kernel,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((ts_m, ts_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((ts_k, ts_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, ts_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((ts_m, ts_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((ts_m, ts_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(flops=flops,
                                      bytes_accessed=bytes_accessed,
                                      transcendentals=0),
        interpret=interpret,
    )(a, b, bias2d)
