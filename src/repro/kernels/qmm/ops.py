"""Jit'd wrapper for the qmm Pallas kernel: border zero-padding plus the
off-TPU fallback.  This is the execution backend of the quantized engine
family's int8×int8 fast path (:mod:`repro.quant`); call sites dispatch
through ``quant_gemm`` / ``QuantizedEngine`` rather than importing this
directly.

Off-TPU the fallback is the int-exact oracle (``ref.py``), NOT the
Pallas interpreter: integer accumulation makes the two bitwise-identical
(there is no fp32 summation-order slack to hide behind), and the oracle's
``lax.dot_general`` keeps int8 operands all the way into the contraction
— so the jaxpr proof of "no fp32 upcast before the dot" holds on every
backend.  ``interpret=True`` still forces the kernel through the Pallas
interpreter for conformance tests."""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.kernels import kernel_block

from .qmm import qmm_pallas
from .ref import qmm_ref

__all__ = ["qmm_matmul"]


def _pad_to(x: jax.Array, mult: tuple[int, int]) -> jax.Array:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mult)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)   # int8 zeros add exactly 0 to the acc
    return x


@functools.partial(jax.jit, static_argnames=("tile", "activation",
                                             "out_dtype", "fuse_dequant",
                                             "interpret"))
def qmm_matmul(a_q: jax.Array, w_q: jax.Array, w_scale: jax.Array, *,
               act_scale: jax.Array | float = 1.0,
               bias: jax.Array | None = None,
               activation: Callable | None = None,
               tile: tuple[int, int, int] | int = (256, 256, 256),
               out_dtype=jnp.float32,
               fuse_dequant: bool = True,
               interpret: bool = False) -> jax.Array:
    """act((A_q @ W_q) * w_scale * act_scale + bias) for arbitrary
    (m, k) x (k, n) int8 operands: pads to tile multiples and slices the
    valid region back out.  ``act_scale`` is a TRACED scalar (the online
    EMA republises a fresh value per live batch; a static arg would
    recompile per decode step) folded into the (1, n) scale operand.
    ``fuse_dequant=False`` returns raw int32.  The kernel runs the job
    tile as its :func:`~repro.kernels.kernel_block`."""
    m, k = a_q.shape
    _, n = w_q.shape
    scale = (w_scale.reshape(1, n).astype(jnp.float32)
             * jnp.float32(act_scale))
    if jax.default_backend() != "tpu" and not interpret:
        return qmm_ref(a_q, w_q, scale, bias=bias,
                       activation=activation, out_dtype=out_dtype,
                       fuse_dequant=fuse_dequant)
    tile = kernel_block(tile)
    ts_m, ts_n, ts_k = tile
    a_p = _pad_to(a_q, (ts_m, ts_k))
    w_p = _pad_to(w_q, (ts_k, ts_n))
    scale_p = _pad_to(scale, (1, ts_n))
    bias_p = (_pad_to(bias.reshape(1, n), (1, ts_n)).reshape(-1)
              if bias is not None else None)
    y = qmm_pallas(a_p, w_p, scale_p, bias=bias_p,
                   activation=activation, tile=tile, out_dtype=out_dtype,
                   fuse_dequant=fuse_dequant, interpret=interpret)
    return y[:m, :n]
