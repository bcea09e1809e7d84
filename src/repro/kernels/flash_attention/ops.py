"""Jit'd flash-attention wrapper dispatched through the op-variant
registry (:mod:`repro.engines`): variants ``pallas`` (TPU target;
interpret off-TPU when named explicitly) and ``xla`` (jnp reference — the
dry-run path so HLO stays canonical).  ``auto`` resolves to the
highest-priority variant available on the current backend, and to one
with a VJP when the operands are being differentiated."""

from __future__ import annotations

import functools

import jax

from repro.engines import register_op_impl, resolve_op, under_grad_trace

from .flash_attention import flash_attention_pallas
from .ref import attention_ref

__all__ = ["flash_attention"]


def _xla_variant(q, k, v, *, causal, scale, blk_q, blk_k):
    return attention_ref(q, k, v, causal=causal, scale=scale)


def _pallas_variant(q, k, v, *, causal, scale, blk_q, blk_k):
    s, sk = q.shape[2], k.shape[2]
    return flash_attention_pallas(
        q, k, v, causal=causal, scale=scale,
        blk_q=min(blk_q, s), blk_k=min(blk_k, sk),
        interpret=jax.default_backend() != "tpu")


register_op_impl("flash_attention", "xla", _xla_variant, priority=0)
register_op_impl("flash_attention", "pallas", _pallas_variant, priority=10,
                 available=lambda: jax.default_backend() == "tpu",
                 grad=False)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    scale: float | None = None,
                    blk_q: int = 128,
                    blk_k: int = 128,
                    impl: str = "auto") -> jax.Array:
    """q (B, Hq, S, D); k/v (B, Hkv, Sk, D) -> (B, Hq, S, D).

    impl: a registered ``flash_attention`` variant name, or 'auto'.
    Whether the operands are being differentiated is decided outside the
    jit, so it keys the jit cache (see :func:`repro.kernels.ssd.ssd`)."""
    return _flash_attention(q, k, v, causal=causal, scale=scale,
                            blk_q=blk_q, blk_k=blk_k, impl=impl,
                            grad=under_grad_trace(q, k, v))


@functools.partial(jax.jit, static_argnames=("causal", "scale", "blk_q",
                                             "blk_k", "impl", "grad"))
def _flash_attention(q, k, v, *, causal, scale, blk_q, blk_k, impl, grad):
    fn = resolve_op("flash_attention", impl, grad=grad)
    return fn(q, k, v, causal=causal, scale=scale, blk_q=blk_q, blk_k=blk_k)
