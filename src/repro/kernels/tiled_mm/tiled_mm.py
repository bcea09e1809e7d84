"""Pallas tiled-MM kernel — the TPU-native Synergy processing engine (PE).

Paper §3.2.1: a PE is a fixed-size tiled matrix-multiplication engine with
(1) local tile buffers in BRAM, (2) double buffering overlapping fetch with
compute, (3) loop pipelining / array partitioning in the inner loops, and
(4) zero-padding border handling, so ONE engine design serves every layer of
every network.

TPU mapping:
  * BRAM tile buffers     -> VMEM blocks via BlockSpec (index_map carves the
                             job's tiles out of HBM).
  * double buffering      -> the Pallas grid pipeline (automatic prologue
                             prefetch of block k+1 during compute of block k).
  * loop pipelining / MXU -> jnp.dot on (ts_m, ts_k)x(ts_k, ts_n) blocks
                             with fp32 accumulation in a VMEM scratch.
  * border zero-padding   -> a ragged last row block (its rows are
                             independent, its stores masked); K and N
                             padded in ops.py only where no block of the
                             shape divides them.
  * jobs -> grid cells    -> grid (gm, gn, gk) of the block
                             ``repro.kernels.gemm_block`` picks from the
                             GEMM's shape: a grid cell holds many of the
                             paper's (t1, t2) job tiles, which stay the
                             unit of splitting and accounting; the TPU
                             core scheduler plays the cluster dispatcher.

Beyond the paper: a fused epilogue (bias + activation) saves one HBM round
trip per GEMM; the k dimension is marked "arbitrary" and m/n "parallel" so
Mosaic can parallelize output tiles across cores.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import GEMM_VMEM_LIMIT

__all__ = ["tiled_mm_pallas"]


def _kernel(a_ref, b_ref, bias_ref, o_ref, acc_ref, *,
            k_steps: int, activation: Callable | None, has_bias: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        y = acc_ref[...]
        if has_bias:
            y = y + bias_ref[...].astype(jnp.float32)
        if activation is not None:
            y = activation(y)
        o_ref[...] = y.astype(o_ref.dtype)


def tiled_mm_pallas(a: jax.Array, b: jax.Array, *,
                    bias: jax.Array | None = None,
                    activation: Callable | None = None,
                    tile: tuple[int, int, int] = (256, 256, 256),
                    out_dtype=None,
                    interpret: bool = False) -> jax.Array:
    """C[m, n] = act(A[m, k] @ B[k, n] + bias) by ``tile`` blocks.  N and
    K must be multiples of the tile (ops.py pads them where needed — the
    paper's zero-padding); the last row block may be ragged."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    ts_m, ts_n, ts_k = tile
    assert n % ts_n == 0 and k % ts_k == 0, (
        f"padded dims required: {(n, k)} vs tile {tile}")
    gm, gn, gk = pl.cdiv(m, ts_m), n // ts_n, k // ts_k
    out_dtype = out_dtype or a.dtype

    has_bias = bias is not None
    bias2d = (bias.reshape(1, n) if has_bias
              else jnp.zeros((1, n), dtype=jnp.float32))

    kernel = functools.partial(_kernel, k_steps=gk, activation=activation,
                               has_bias=has_bias)
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
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=GEMM_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(flops=flops,
                                      bytes_accessed=bytes_accessed,
                                      transcendentals=0),
        interpret=interpret,
    )(a, b, bias2d)
