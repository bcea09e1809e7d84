"""Pallas flash attention (causal, GQA) — tile-job-structured attention.

The Synergy view: attention's score/value GEMMs are decomposed into VMEM
tile jobs exactly like the CONV GEMMs — grid cell (b, h, qi) owns one query
tile and streams key/value tiles through VMEM with online softmax, so the
whole network (MLP + attention) runs on fixed-size tile engines.

Layout: q (B, Hq, S, D), k/v (B, Hkv, S, D) with Hq % Hkv == 0 (GQA: the
kv BlockSpec index_map folds q-head -> kv-head, no materialized repeat).
Causal masking by global block indices; fully-masked kv blocks are skipped
by the grid bound (lower-triangular iteration via masking — interpret mode
and Mosaic both honor the @pl.when early-out on block skip).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30
_LANES = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, kv_steps: int, blk_q: int,
            blk_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip fully-masked blocks (strictly above the diagonal)
    run = (not causal) or (ki * blk_k <= qi * blk_q + blk_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]                       # (blk_q, d)
        k = k_ref[0, 0]                       # (blk_k, d)
        v = v_ref[0, 0]                       # (blk_k, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (blk_q, blk_k)
        if causal:
            rows = qi * blk_q + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            cols = ki * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_prev = m_ref[:, :1]                 # (blk_q, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                # (blk_q, blk_k)
        alpha = jnp.exp(m_prev - m_new)       # (blk_q, 1)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == kv_steps - 1)
    def _final():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True,
                           scale: float | None = None,
                           blk_q: int = 128,
                           blk_k: int = 128,
                           interpret: bool = False) -> jax.Array:
    b, hq, s, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    assert s % blk_q == 0 and sk % blk_k == 0, (s, sk, blk_q, blk_k)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    grid = (b, hq, s // blk_q, sk // blk_k)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               kv_steps=sk // blk_k, blk_q=blk_q, blk_k=blk_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, d), lambda bb, h, qi, ki: (bb, h, qi, 0)),
            pl.BlockSpec((1, 1, blk_k, d),
                         lambda bb, h, qi, ki, g=group: (bb, h // g, ki, 0)),
            pl.BlockSpec((1, 1, blk_k, d),
                         lambda bb, h, qi, ki, g=group: (bb, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, d),
                               lambda bb, h, qi, ki: (bb, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, _LANES), jnp.float32),
            pltpu.VMEM((blk_q, _LANES), jnp.float32),
            pltpu.VMEM((blk_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
