"""Jit'd wrapper for the tiled_mm Pallas kernel: picks the GEMM's block
from its shape and zero-pads a border only where no block fits without
(paper §3.2.1 "Zero Padding in mm_tile").  This is the execution backend
of :class:`repro.engines.PallasTiledEngine`; call sites dispatch through
``synergy_matmul`` / the engine registry rather than importing this
directly."""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.kernels import gemm_block

from .tiled_mm import tiled_mm_pallas
from .ref import tiled_mm_ref

__all__ = ["tiled_matmul"]


def _pad_to(x: jax.Array, mult: tuple[int, int]) -> jax.Array:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mult)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


@functools.partial(jax.jit, static_argnames=("tile", "activation",
                                             "out_dtype", "interpret"))
def tiled_matmul(a: jax.Array, b: jax.Array, *,
                 bias: jax.Array | None = None,
                 activation: Callable | None = None,
                 tile: tuple[int, int, int] | int = (256, 256, 256),
                 out_dtype=None,
                 interpret: bool = False) -> jax.Array:
    """act(A @ B + bias) for arbitrary (m, k) x (k, n): runs the block
    :func:`~repro.kernels.gemm_block` picks for the shape, at least the
    job tile's :func:`~repro.kernels.kernel_block`.  K and N are
    zero-padded (the fixed-size PE's border jobs) and the valid columns
    sliced back out only where that block does not divide them; a ragged
    last row block needs neither."""
    m, k = a.shape
    _, n = b.shape
    bm, bn, bk = gemm_block(m, k, n, a.dtype.itemsize, tile)
    a_p = _pad_to(a, (1, bk))
    b_p = _pad_to(b, (bk, bn))
    bias_p = _pad_to(bias, (bn,)) if bias is not None else None
    y = tiled_mm_pallas(a_p, b_p, bias=bias_p, activation=activation,
                        tile=(bm, bn, bk), out_dtype=out_dtype,
                        interpret=interpret or jax.default_backend() != "tpu")
    return y if y.shape[1] == n else y[:, :n]
