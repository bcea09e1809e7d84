"""Jit'd wrapper for the tiled_mm Pallas kernel: border zero-padding
(paper §3.2.1 "Zero Padding in mm_tile").  This is the execution backend
of :class:`repro.engines.PallasTiledEngine`; call sites dispatch through
``synergy_matmul`` / the engine registry rather than importing this
directly."""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.kernels import kernel_block

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
    """act(A @ B + bias) for arbitrary (m, k) x (k, n): runs the job tile
    as its :func:`~repro.kernels.kernel_block`, pads to block multiples
    (the fixed-size PE's zero-padded border jobs) and slices the valid
    region back out."""
    tile = kernel_block(tile)
    m, k = a.shape
    _, n = b.shape
    ts_m, ts_n, ts_k = tile
    a_p = _pad_to(a, (ts_m, ts_k))
    b_p = _pad_to(b, (ts_k, ts_n))
    bias_p = _pad_to(bias, (ts_n,)) if bias is not None else None
    y = tiled_mm_pallas(a_p, b_p, bias=bias_p, activation=activation,
                        tile=tile, out_dtype=out_dtype,
                        interpret=interpret or jax.default_backend() != "tpu")
    return y[:m, :n]
