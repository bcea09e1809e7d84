"""Pallas int8×int8 tiled-MM kernel — the quantized Synergy PE.

``tiled_mm`` is the fp32 tile engine; this is its fixed-point twin, the
TPU analog of the paper's reduced-precision datapaths (§3.2: the NEON
cores run 16-bit fixed-point SIMD; embedded FPGA reproductions like
ZynqNet win their speedups with fixed-point MACs end to end).  The MXU
natively consumes int8 operand pairs at int32 accumulation, so the
faithful mapping is

  * operands          -> int8 A (per-tensor scale) and int8 W (per-output-
                         channel scale) blocks, streamed at 1 byte/elem —
                         the contraction NEVER sees an fp32 upcast.
  * accumulation      -> int32 VMEM scratch across the k grid dimension
                         (exact: no rounding until the epilogue, and the
                         partials are order-independent integers, unlike
                         fp32 accumulation).
  * dequant epilogue  -> one fused fp32 pass on the LAST k step:
                         acc * (w_scale[j] * act_scale) -> +bias -> act
                         -> cast, so the low-precision stream still pays
                         only one HBM round trip for C.

Everything else mirrors ``tiled_mm``'s contract: grid (gm, gn, gk) with
(i, j) the paper's (t1, t2) tile index, automatic double buffering from
the grid pipeline, zero-padded borders handled in ops.py (int8 zeros
contribute exactly 0 to the integer accumulator).

``fuse_dequant=False`` returns the raw int32 accumulator instead — the
SynergyRuntime splits a quantized GEMM into row panels in this mode and
applies the shared ``dequant_finish`` ONCE after the merge, so a split
never rounds twice and stolen panels stay bitwise-identical (integer
partials are exact on every engine).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["qmm_pallas"]


def _kernel(a_ref, b_ref, scale_ref, bias_ref, o_ref, acc_ref, *,
            k_steps: int, activation: Callable | None, has_bias: bool,
            fuse_dequant: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # THE point of the kernel: the contraction consumes the int8 blocks
    # directly (MXU int8 mode), accumulating exactly in int32
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        acc = acc_ref[...]
        if fuse_dequant:
            # scale_ref carries w_scale * act_scale pre-combined (a
            # traced operand — the online EMA republises a new act
            # scale per batch, and a static epilogue constant would
            # retrace the kernel every decode step)
            y = acc.astype(jnp.float32) * scale_ref[...].astype(jnp.float32)
            if has_bias:
                y = y + bias_ref[...].astype(jnp.float32)
            if activation is not None:
                y = activation(y)
            o_ref[...] = y.astype(o_ref.dtype)
        else:
            o_ref[...] = acc


def qmm_pallas(a_q: jax.Array, w_q: jax.Array, scale: jax.Array, *,
               bias: jax.Array | None = None,
               activation: Callable | None = None,
               tile: tuple[int, int, int] = (256, 256, 256),
               out_dtype=jnp.float32,
               fuse_dequant: bool = True,
               interpret: bool = False) -> jax.Array:
    """C[m, n] = act((A_q @ W_q) * scale + bias) with int8 operands and
    int32 accumulation.  ``a_q`` int8 (m, k); ``w_q`` int8 (k, n);
    ``scale`` fp32 (1, n) — the per-output-channel weight scale with the
    per-tensor activation scale already multiplied in (a TRACED operand:
    the online EMA republises a fresh activation scale per live batch,
    and baking it in as a static constant would recompile the kernel on
    every decode step).  Dims must be multiples of ``tile`` (ops.py pads
    borders with int8 zeros).

    ``fuse_dequant=False`` skips the epilogue entirely and returns the
    raw int32 accumulator (runtime split/merge mode)."""
    assert a_q.dtype == jnp.int8 and w_q.dtype == jnp.int8, (
        f"qmm consumes int8 operands, got {a_q.dtype} x {w_q.dtype}")
    m, k = a_q.shape
    k2, n = w_q.shape
    assert k == k2
    ts_m, ts_n, ts_k = tile
    assert m % ts_m == 0 and n % ts_n == 0 and k % ts_k == 0, (
        f"padded dims required: {(m, n, k)} vs tile {tile}")
    gm, gn, gk = m // ts_m, n // ts_n, k // ts_k

    has_bias = bias is not None
    bias2d = (bias.reshape(1, n) if has_bias
              else jnp.zeros((1, n), dtype=jnp.float32))
    scale2d = scale.reshape(1, n).astype(jnp.float32)

    kernel = functools.partial(
        _kernel, k_steps=gk, activation=activation, has_bias=has_bias,
        fuse_dequant=fuse_dequant)
    out_dtype = jnp.int32 if not fuse_dequant else out_dtype
    flops = 2 * m * n * k
    # the bandwidth story: both operand streams are 1 byte/element
    bytes_accessed = (a_q.size + w_q.size
                      + m * n * jnp.dtype(out_dtype).itemsize)
    return pl.pallas_call(
        kernel,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((ts_m, ts_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((ts_k, ts_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, ts_n), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, ts_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((ts_m, ts_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((ts_m, ts_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(flops=flops,
                                      bytes_accessed=bytes_accessed,
                                      transcendentals=0),
        interpret=interpret,
    )(a_q, w_q, scale2d, bias2d)
