"""Attention: GQA projections (Synergy GEMM jobs) + three score engines,
registered as ``attention_scores`` op variants in :mod:`repro.engines`:

  * 'pallas'    — the flash-attention Pallas kernel (TPU target).
  * 'flash_xla' — the same online-softmax tiling expressed as a double
                  lax.scan over (q-block, kv-block).  This is what the
                  512-device dry-run lowers: O(blk_q x blk_k) live buffers
                  instead of the O(S^2) naive score matrix.
  * 'ref'       — naive reference (small shapes / oracles only).

GQA is computed grouped — q reshaped to (B, Hkv, group, S, D) — so KV is
never materialized repeated.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.synergy_mm import synergy_matmul
from repro.engines import register_op_impl, resolve_op, under_grad_trace
from repro.kernels.flash_attention import attention_ref, flash_attention
from .layers import init_dense, rope

__all__ = ["init_attention", "attention", "decode_attention",
           "flash_attention_xla", "project_kv"]

_NEG = -1e30


def _match_vma(init: jax.Array, *refs: jax.Array) -> jax.Array:
    """Give scan-carry initializers the union of the refs' varying manual
    axes (shard_map contexts); no-op outside shard_map."""
    vma = frozenset().union(*(jax.typeof(r).vma for r in refs))
    if vma:
        return jax.lax.pcast(init, tuple(sorted(vma)), to="varying")
    return init


def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, dtype=jnp.float32) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": init_dense(k1, d_model, n_heads * head_dim, dtype),
        "wk": init_dense(k2, d_model, n_kv_heads * head_dim, dtype),
        "wv": init_dense(k3, d_model, n_kv_heads * head_dim, dtype),
        "wo": init_dense(k4, n_heads * head_dim, d_model, dtype,
                         scale=(n_heads * head_dim) ** -0.5),
    }


def flash_attention_xla(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        scale: float | None = None,
                        blk_q: int = 512,
                        blk_k: int = 1024) -> jax.Array:
    """Online-softmax attention as a double scan (XLA path).

    q (B, Hq, S, D); k/v (B, Hkv, Sk, D).  Non-divisible S/Sk are padded
    internally and masked (whisper's 1500-frame encoder etc.)."""
    b, hq, s, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    blk_q = min(blk_q, s)
    blk_k = min(blk_k, sk)
    s_orig, sk_valid = s, sk
    if s % blk_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, (-s) % blk_q), (0, 0)))
        s = q.shape[2]
    if sk % blk_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, (-sk) % blk_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, (-sk) % blk_k), (0, 0)))
        sk = k.shape[2]
    nq, nk = s // blk_q, sk // blk_k
    qg = q.reshape(b, hkv, g, nq, blk_q, d)
    kb = k.reshape(b, hkv, nk, blk_k, d)
    vb = v.reshape(b, hkv, nk, blk_k, d)

    # §Perf A1: the Synergy view of causal flash attention — enumerate the
    # VALID (q-block, kv-block) tile jobs statically and stream them
    # through ONE scan.  Fully-masked future blocks never become jobs, so
    # causal attention does ~half the block work of the naive nq x nk
    # double loop; the scan has a STATIC trip count (differentiable, and
    # the dry-run accounting is exact, unlike a dynamic-bound fori_loop).
    if causal:
        pairs = [(qi, ki) for qi in range(nq)
                 for ki in range(min(nk, (qi * blk_q + blk_q + blk_k - 1)
                                    // blk_k))]
    else:
        pairs = [(qi, ki) for qi in range(nq) for ki in range(nk)]
    qi_arr = jnp.array([p[0] for p in pairs], jnp.int32)
    ki_arr = jnp.array([p[1] for p in pairs], jnp.int32)

    def job(carry, idx):
        m, l, acc, outputs = carry
        qi, ki = qi_arr[idx], ki_arr[idx]
        reset = (ki == 0)
        m = jnp.where(reset, _NEG, m)
        l = jnp.where(reset, 0.0, l)
        acc = jnp.where(reset, 0.0, acc)
        qcur = jax.lax.dynamic_index_in_dim(qg, qi, axis=3, keepdims=False)
        kcur = jax.lax.dynamic_index_in_dim(kb, ki, axis=2, keepdims=False)
        vcur = jax.lax.dynamic_index_in_dim(vb, ki, axis=2, keepdims=False)
        sres = jnp.einsum("bhgqd,bhkd->bhgqk", qcur, kcur,
                          preferred_element_type=jnp.float32) * scale
        # §Perf A2: ADDITIVE (blk_q, blk_k) penalty — broadcast-adds and
        # fuses; a jnp.where select materialized (B,H,g,blk_q,blk_k)
        # pred+f32 buffers per job.
        cols = jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        pen = jnp.zeros((blk_q, blk_k), jnp.float32)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
            pen = jnp.where(qi * blk_q + rows >= ki * blk_k + cols,
                            pen, _NEG)
        if sk_valid != sk:
            pen = jnp.where(ki * blk_k + cols < sk_valid, pen, _NEG)
        sres = sres + pen[None, None, None]
        m_new = jnp.maximum(m, sres.max(axis=-1, keepdims=True))
        p = jnp.exp(sres - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p.astype(vcur.dtype), vcur,
            preferred_element_type=jnp.float32)
        # write the running normalized block at position qi; later jobs of
        # the same q-block overwrite it, so the final write (ki == last)
        # is the complete softmax — no masking, slice-sized traffic.
        out_blk = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
        outputs = jax.lax.dynamic_update_slice_in_dim(
            outputs, out_blk[None], qi, axis=0)
        return (m_new, l, acc, outputs), None

    m0 = jnp.full((b, hkv, g, blk_q, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, blk_q, 1), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, blk_q, d), jnp.float32)
    out0 = jnp.zeros((nq, b, hkv, g, blk_q, d), q.dtype)
    # under shard_map (e.g. the pipeline-parallel launch mode) the scan
    # body is device-varying; the zero initializers must carry the same
    # varying-axes type
    m0, l0, a0, out0 = (_match_vma(t, q, k, v) for t in (m0, l0, a0, out0))
    (_, _, _, blocks), _ = jax.lax.scan(
        job, (m0, l0, a0, out0), jnp.arange(len(pairs)))
    # blocks: (nq, B, Hkv, g, blk_q, D) -> (B, Hq, S, D)
    out = jnp.moveaxis(blocks, 0, 3)                 # (B, Hkv, g, nq, blk_q, D)
    return out.reshape(b, hq, s, d)[:, :, :s_orig, :]


register_op_impl(
    "attention_scores", "pallas",
    lambda q, k, v, *, causal, blk_q, blk_k: flash_attention(
        q, k, v, causal=causal, impl="pallas"),
    priority=10, available=lambda: jax.default_backend() == "tpu",
    grad=False)
register_op_impl(
    "attention_scores", "flash_xla",
    lambda q, k, v, *, causal, blk_q, blk_k: flash_attention_xla(
        q, k, v, causal=causal, blk_q=blk_q, blk_k=blk_k),
    priority=0)
register_op_impl(
    "attention_scores", "ref",
    lambda q, k, v, *, causal, blk_q, blk_k: attention_ref(
        q, k, v, causal=causal),
    priority=-10)


def _scores_engine(q, k, v, *, causal, impl, blk_q=512, blk_k=1024):
    fn = resolve_op("attention_scores", impl,
                    grad=under_grad_trace(q, k, v))
    return fn(q, k, v, causal=causal, blk_q=blk_q, blk_k=blk_k)


def attention(params: dict, x: jax.Array, *, n_heads: int, n_kv_heads: int,
              head_dim: int, positions: jax.Array | None = None,
              rope_theta: float = 1e4, causal: bool = True,
              kv_x: jax.Array | None = None, use_rope: bool = True,
              impl: str = "auto", name: str = "attn") -> jax.Array:
    """Full-sequence attention (train / prefill / encoder / cross).

    x (B, S, d).  kv_x: source for K/V (cross-attention); defaults to x.
    """
    b, s, _ = x.shape
    src = x if kv_x is None else kv_x
    sk = src.shape[1]
    q = synergy_matmul(x, params["wq"], name=f"{name}/wq")
    kk = synergy_matmul(src, params["wk"], name=f"{name}/wk")
    vv = synergy_matmul(src, params["wv"], name=f"{name}/wv")
    q = q.reshape(b, s, n_heads, head_dim).transpose(0, 2, 1, 3)
    kk = kk.reshape(b, sk, n_kv_heads, head_dim).transpose(0, 2, 1, 3)
    vv = vv.reshape(b, sk, n_kv_heads, head_dim).transpose(0, 2, 1, 3)
    if use_rope:
        pos_q = positions if positions is not None else jnp.arange(s)
        q = rope(q, pos_q[None, None, :], rope_theta)
        kk = rope(kk, jnp.arange(sk)[None, None, :], rope_theta)
    if n_heads != n_kv_heads:
        # TP note: under a 16-way model axis none of the GQA archs' kv-head
        # counts divide the mesh, so K/V are expanded to q-heads here (the
        # expanded tensors shard on the q-head dim; the K/V weights stay
        # replicated).  See DESIGN.md sharding fallbacks.
        g = n_heads // n_kv_heads
        kk = jnp.repeat(kk, g, axis=1)
        vv = jnp.repeat(vv, g, axis=1)
    o = _scores_engine(q, kk, vv, causal=causal, impl=impl)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, n_heads * head_dim)
    return synergy_matmul(o, params["wo"], name=f"{name}/wo")


def project_kv(params: dict, src: jax.Array, *, n_kv_heads: int,
               head_dim: int, rope_theta: float = 1e4,
               use_rope: bool = True) -> tuple[jax.Array, jax.Array]:
    """K/V projection for cache prefill (encoder output or prompt)."""
    b, sk, _ = src.shape
    kk = synergy_matmul(src, params["wk"], name="kv/wk")
    vv = synergy_matmul(src, params["wv"], name="kv/wv")
    kk = kk.reshape(b, sk, n_kv_heads, head_dim).transpose(0, 2, 1, 3)
    vv = vv.reshape(b, sk, n_kv_heads, head_dim).transpose(0, 2, 1, 3)
    if use_rope:
        kk = rope(kk, jnp.arange(sk)[None, None, :], rope_theta)
    return kk, vv


def _rope_positions(pos: jax.Array, b: int) -> jax.Array:
    """Broadcastable rope positions for one decode token: scalar ``pos`` ->
    (1, 1, 1); per-slot vector (B,) -> (B, 1, 1).  Negative entries mark
    inactive slots (continuous batching) and are clamped — their output is
    discarded and their cache writes masked."""
    p = jnp.asarray(pos)
    if p.ndim == 0:
        return jnp.full((1, 1, 1), p)
    return jnp.maximum(p, 0).reshape(b, 1, 1)


def _cache_valid_mask(pos: jax.Array, s_max: int) -> jax.Array:
    """(..., s_max) attention mask over cache positions for scalar or
    per-slot (B,) ``pos``."""
    p = jnp.asarray(pos)
    if p.ndim == 0:
        return (jnp.arange(s_max) <= p)[None, None, None, None, :]
    return (jnp.arange(s_max)[None, :]
            <= jnp.maximum(p, 0)[:, None])[:, None, None, None, :]


def decode_project_kv(params: dict, x: jax.Array, pos: jax.Array, *,
                      n_kv_heads: int, head_dim: int,
                      rope_theta: float = 1e4, use_rope: bool = True):
    """Project the new token's K/V -> (B, Hkv, 1, hd) each (for in-place
    cache insertion — §Perf D1).  ``pos``: scalar or per-slot (B,)."""
    b = x.shape[0]
    kk = synergy_matmul(x, params["wk"], name="attn/wk")
    vv = synergy_matmul(x, params["wv"], name="attn/wv")
    kk = kk.reshape(b, 1, n_kv_heads, head_dim).transpose(0, 2, 1, 3)
    vv = vv.reshape(b, 1, n_kv_heads, head_dim).transpose(0, 2, 1, 3)
    if use_rope:
        kk = rope(kk, _rope_positions(pos, b), rope_theta)
    return kk, vv


def decode_attend(params: dict, x: jax.Array, k_cache: jax.Array,
                  v_cache: jax.Array, pos: jax.Array, *, n_heads: int,
                  n_kv_heads: int, head_dim: int, rope_theta: float = 1e4,
                  use_rope: bool = True, name: str = "attn") -> jax.Array:
    """One-token attention against a READ-ONLY cache slice (the new
    token's K/V must already be inserted).  x (B,1,d) -> (B,1,d).
    ``pos``: scalar, or per-slot (B,) vector (continuous batching — each
    slot attends only to its own prefix)."""
    b = x.shape[0]
    g = n_heads // n_kv_heads
    s_max = k_cache.shape[2]
    q = synergy_matmul(x, params["wq"], name=f"{name}/wq")
    q = q.reshape(b, 1, n_heads, head_dim).transpose(0, 2, 1, 3)
    if use_rope:
        q = rope(q, _rope_positions(pos, b), rope_theta)
    qg = q.reshape(b, n_kv_heads, g, 1, head_dim)
    # read the cache at its STORAGE dtype; f32 happens in the MXU
    # accumulator (an astype here materializes an f32 copy of the cache)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(k_cache.dtype), k_cache,
                   preferred_element_type=jnp.float32) / math.sqrt(head_dim)
    s = jnp.where(_cache_valid_mask(pos, s_max), s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    o = o.reshape(b, n_heads, 1, head_dim).transpose(0, 2, 1, 3)
    o = o.reshape(b, 1, n_heads * head_dim).astype(x.dtype)
    return synergy_matmul(o, params["wo"], name=f"{name}/wo")


def decode_attention(params: dict, x: jax.Array, k_cache: jax.Array,
                     v_cache: jax.Array, pos: jax.Array, *, n_heads: int,
                     n_kv_heads: int, head_dim: int, rope_theta: float = 1e4,
                     update_cache: bool = True, use_rope: bool = True,
                     name: str = "attn"):
    """One-token decode with KV cache.

    x (B, 1, d); caches (B, Hkv, S_max, hd); pos scalar int32 (current index).
    Returns (y (B, 1, d), k_cache, v_cache).
    """
    b = x.shape[0]
    g = n_heads // n_kv_heads
    s_max = k_cache.shape[2]
    q = synergy_matmul(x, params["wq"], name=f"{name}/wq")
    q = q.reshape(b, 1, n_heads, head_dim).transpose(0, 2, 1, 3)
    if use_rope:
        q = rope(q, jnp.full((1, 1, 1), pos), rope_theta)
    if update_cache:
        kk = synergy_matmul(x, params["wk"], name=f"{name}/wk")
        vv = synergy_matmul(x, params["wv"], name=f"{name}/wv")
        kk = kk.reshape(b, 1, n_kv_heads, head_dim).transpose(0, 2, 1, 3)
        vv = vv.reshape(b, 1, n_kv_heads, head_dim).transpose(0, 2, 1, 3)
        if use_rope:
            kk = rope(kk, jnp.full((1, 1, 1), pos), rope_theta)
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, kk.astype(k_cache.dtype), pos, axis=2)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, vv.astype(v_cache.dtype), pos, axis=2)
    qg = q.reshape(b, n_kv_heads, g, 1, head_dim)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) / math.sqrt(head_dim)
    valid = jnp.arange(s_max) <= pos
    s = jnp.where(valid[None, None, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v_cache.astype(jnp.float32))
    o = o.reshape(b, n_heads, 1, head_dim).transpose(0, 2, 1, 3)
    o = o.reshape(b, 1, n_heads * head_dim).astype(x.dtype)
    return synergy_matmul(o, params["wo"], name=f"{name}/wo"), k_cache, v_cache
