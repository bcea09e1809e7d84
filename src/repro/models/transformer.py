"""LM assembly for all assigned families.

Families:
  dense   — pre-norm GQA attention + GLU MLP           (internlm2, granite,
            phi3, gemma; vlm backbone = dense over patch embeddings)
  moe     — attention + expert-choice MoE FFN          (dbrx, kimi-k2)
  ssm     — Mamba2 blocks only                         (mamba2-130m)
  hybrid  — Mamba2 backbone + ONE shared attn+MLP block applied every
            ``attn_every`` layers (zamba2 signature)
  audio   — whisper-style encoder-decoder (frontend stubbed to embeddings)

Layer stacks are parameter-stacked and iterated with ``lax.scan`` (small
HLO for the 512-device dry-run); per-layer remat via ``jax.checkpoint`` when
cfg.remat.  Decode threads per-layer caches through the same scan.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.synergy_mm import synergy_matmul
from .attention import (attention, decode_attend, decode_attention,
                        decode_project_kv, init_attention, project_kv)
from .layers import glu_mlp, init_dense, init_glu_mlp, rms_norm, softmax_xent
from .moe import init_moe, moe_ffn
from .ssm import (CONV_K, init_mamba2, init_mamba2_state, mamba2_block,
                  mamba2_decode_step)

__all__ = ["init_lm", "lm_forward", "lm_loss", "init_cache", "decode_step",
           "prefill"]


# ---------------------------------------------------------------------------
# block init / forward
# ---------------------------------------------------------------------------

def _init_attn_block(cfg: ArchConfig, key, cross: bool = False) -> dict:
    keys = jax.random.split(key, 4)
    dt = cfg.param_jdtype
    p = {
        "ln1": jnp.ones((cfg.d_model,), dt),
        "attn": init_attention(keys[0], cfg.d_model, cfg.n_heads,
                               cfg.n_kv_heads, cfg.resolved_head_dim, dt),
        "ln2": jnp.ones((cfg.d_model,), dt),
    }
    if cross:
        p["ln_x"] = jnp.ones((cfg.d_model,), dt)
        p["cross"] = init_attention(keys[1], cfg.d_model, cfg.n_heads,
                                    cfg.n_kv_heads, cfg.resolved_head_dim, dt)
    if cfg.family == "moe":
        p["moe"] = init_moe(keys[2], cfg.d_model, cfg.d_ff, cfg.n_experts, dt)
    else:
        p["mlp"] = init_glu_mlp(keys[3], cfg.d_model, cfg.d_ff, dt)
    return p


def _init_mamba_block(cfg: ArchConfig, key) -> dict:
    return {
        "ln": jnp.ones((cfg.d_model,), cfg.param_jdtype),
        "mixer": init_mamba2(key, cfg.d_model, cfg.d_inner, cfg.ssm_state,
                             cfg.ssm_head_dim, cfg.param_jdtype),
    }


def _attn_kw(cfg: ArchConfig) -> dict:
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta)


def _attn_block_fwd(cfg: ArchConfig, p: dict, x: jax.Array, *,
                    causal: bool = True, enc: jax.Array | None = None,
                    impl: str = "auto") -> jax.Array:
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    x = x + attention(p["attn"], h, causal=causal, impl=impl, **_attn_kw(cfg))
    if enc is not None:
        h = rms_norm(x, p["ln_x"], cfg.norm_eps)
        x = x + attention(p["cross"], h, kv_x=enc, causal=False,
                          use_rope=False, impl=impl, **_attn_kw(cfg))
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        x = x + moe_ffn(p["moe"], h, top_k=cfg.top_k,
                        capacity_factor=cfg.capacity_factor, act=cfg.act)
    else:
        x = x + glu_mlp(p["mlp"], h, act=cfg.act)
    return x


def _mamba_block_fwd(cfg: ArchConfig, p: dict, x: jax.Array,
                     impl: str = "auto") -> jax.Array:
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    return x + mamba2_block(p["mixer"], h, d_inner=cfg.d_inner,
                            ssm_state=cfg.ssm_state,
                            head_dim=cfg.ssm_head_dim,
                            chunk=cfg.ssm_chunk, eps=cfg.norm_eps, impl=impl)


# ---------------------------------------------------------------------------
# model init
# ---------------------------------------------------------------------------

def _stacked(init_fn, key, n: int):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def init_lm(cfg: ArchConfig, key) -> dict:
    keys = jax.random.split(key, 6)
    dt = cfg.param_jdtype
    params: dict[str, Any] = {
        "embed": (jax.random.normal(keys[0], (cfg.padded_vocab, cfg.d_model))
                  * 0.02).astype(dt),
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_dense(keys[1], cfg.d_model,
                                       cfg.padded_vocab, dt)
    if cfg.family in ("dense", "moe", "vlm"):
        params["blocks"] = _stacked(
            lambda k: _init_attn_block(cfg, k), keys[2], cfg.n_layers)
    elif cfg.family == "ssm":
        params["blocks"] = _stacked(
            lambda k: _init_mamba_block(cfg, k), keys[2], cfg.n_layers)
    elif cfg.family == "hybrid":
        params["blocks"] = _stacked(
            lambda k: _init_mamba_block(cfg, k), keys[2], cfg.n_layers)
        params["shared"] = _init_attn_block(cfg, keys[3])
    elif cfg.family == "audio":
        params["blocks"] = _stacked(
            lambda k: _init_attn_block(cfg, k, cross=True), keys[2],
            cfg.n_layers)
        params["encoder"] = _stacked(
            lambda k: _init_attn_block(cfg, k), keys[4], cfg.encoder_layers)
        params["enc_norm"] = jnp.ones((cfg.d_model,), dt)
    else:
        raise ValueError(cfg.family)
    return params


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _constrain_batch(x: jax.Array) -> jax.Array:
    """§Perf iteration: pin the residual stream to batch-sharding over the
    data axes.  Without this the partitioner flip-flops (e.g. internlm2
    prefill ran its MLP batch-REPLICATED, paying a 4.3 GB collective-permute
    3x per layer).  No-op outside a ``jax.set_mesh`` context with a model
    axis, and where the batch does not divide the data axes."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names:
        return x
    dp = tuple(a for a in mesh.axis_names if a != "model")
    if x.shape[0] % math.prod(mesh.shape[a] for a in dp):
        return x
    return jax.lax.with_sharding_constraint(
        x, P(dp, *([None] * (x.ndim - 1))))


def _scan_blocks(body, x, stacked, remat: bool):
    inner = body

    def constrained(p, h):
        return _constrain_batch(inner(p, _constrain_batch(h)))

    f = jax.checkpoint(constrained) if remat else constrained

    def step(carry, p):
        return f(p, carry), None

    x, _ = jax.lax.scan(step, x, stacked)
    return x


def _grouped(tree, groups: int):
    return jax.tree.map(
        lambda a: a.reshape((groups, a.shape[0] // groups) + a.shape[1:]),
        tree)


def _backbone(cfg: ArchConfig, params: dict, x: jax.Array, *,
              enc: jax.Array | None = None, impl: str = "auto") -> jax.Array:
    if cfg.family in ("dense", "moe", "vlm"):
        body = lambda p, h: _attn_block_fwd(cfg, p, h, impl=impl)
        x = _scan_blocks(body, x, params["blocks"], cfg.remat)
    elif cfg.family == "ssm":
        body = lambda p, h: _mamba_block_fwd(cfg, p, h, impl=impl)
        x = _scan_blocks(body, x, params["blocks"], cfg.remat)
    elif cfg.family == "hybrid":
        groups = cfg.n_layers // cfg.attn_every
        stacked = _grouped(params["blocks"], groups)
        inner = lambda p, h: _mamba_block_fwd(cfg, p, h, impl=impl)
        shared = params["shared"]

        def group_body(h, grp):
            h = _scan_blocks(inner, h, grp, cfg.remat)
            h = _attn_block_fwd(cfg, shared, h, impl=impl)
            return h, None

        x, _ = jax.lax.scan(group_body, x, stacked)
    elif cfg.family == "audio":
        body = lambda p, h: _attn_block_fwd(cfg, p, h, enc=enc, impl=impl)
        x = _scan_blocks(body, x, params["blocks"], cfg.remat)
    return x


def _encode(cfg: ArchConfig, params: dict, enc_embeds: jax.Array,
            impl: str = "auto") -> jax.Array:
    body = lambda p, h: _attn_block_fwd(cfg, p, h, causal=False, impl=impl)
    enc = _scan_blocks(body, enc_embeds.astype(cfg.compute_jdtype),
                       params["encoder"], cfg.remat)
    return rms_norm(enc, params["enc_norm"], cfg.norm_eps)


def _head(cfg: ArchConfig, params: dict, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    return synergy_matmul(x, w.astype(x.dtype), name="lm_head",
                          out_dtype=jnp.float32)


def lm_forward(cfg: ArchConfig, params: dict, *,
               tokens: jax.Array | None = None,
               embeds: jax.Array | None = None,
               enc_embeds: jax.Array | None = None,
               impl: str = "auto") -> jax.Array:
    """Full-sequence forward -> logits (B, S, padded_vocab) fp32."""
    if embeds is None:
        embeds = params["embed"][tokens]
    x = embeds.astype(cfg.compute_jdtype)
    enc = (_encode(cfg, params, enc_embeds, impl)
           if cfg.family == "audio" else None)
    x = _backbone(cfg, params, x, enc=enc, impl=impl)
    return _head(cfg, params, x)


def lm_loss(cfg: ArchConfig, params: dict, batch: dict, *,
            impl: str = "auto") -> jax.Array:
    logits = lm_forward(
        cfg, params,
        tokens=batch.get("tokens"),
        embeds=batch.get("embeds"),
        enc_embeds=batch.get("enc_embeds"),
        impl=impl)
    return softmax_xent(logits, batch["labels"], z_loss=1e-4)


# ---------------------------------------------------------------------------
# serving: prefill + single-token decode with caches
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=None) -> dict:
    dtype = dtype or (jnp.dtype(cfg.cache_dtype) if cfg.cache_dtype
                      else cfg.compute_jdtype)
    hd = cfg.resolved_head_dim if cfg.n_heads else 0
    kv = lambda n, s: jnp.zeros((n, batch, cfg.n_kv_heads, s, hd), dtype)

    def mamba_states(n):
        # SSM states stay in the compute dtype (they concatenate with live
        # activations each step); only attention K/V quantize.
        st = init_mamba2_state(batch, cfg.d_inner, cfg.ssm_state,
                               cfg.ssm_head_dim, cfg.compute_jdtype)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), st)

    if cfg.family in ("dense", "moe", "vlm"):
        return {"k": kv(cfg.n_layers, max_len), "v": kv(cfg.n_layers, max_len)}
    if cfg.family == "ssm":
        return mamba_states(cfg.n_layers)
    if cfg.family == "hybrid":
        groups = cfg.n_layers // cfg.attn_every
        return {"mamba": mamba_states(cfg.n_layers),
                "k": kv(groups, max_len), "v": kv(groups, max_len)}
    if cfg.family == "audio":
        return {"k": kv(cfg.n_layers, max_len), "v": kv(cfg.n_layers, max_len),
                "xk": kv(cfg.n_layers, cfg.encoder_len),
                "xv": kv(cfg.n_layers, cfg.encoder_len)}
    raise ValueError(cfg.family)


def prepare_cross_cache(cfg: ArchConfig, params: dict,
                        enc_embeds: jax.Array, impl: str = "auto"):
    """Whisper: run the encoder and project per-decoder-layer cross K/V."""
    enc = _encode(cfg, params, enc_embeds, impl)

    def per_layer(p):
        return project_kv(p["cross"], enc, n_kv_heads=cfg.n_kv_heads,
                          head_dim=cfg.resolved_head_dim, use_rope=False)

    xk, xv = jax.vmap(per_layer)(params["blocks"])
    return xk, xv


def _layer_slice(tree, l):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False),
        tree)


def _write_token_kv(K, V, kk, vv, l, pos):
    """§Perf D1: in-place token-slice insert into the global (L,B,H,S,hd)
    caches — a scan-ys formulation rewrites the ENTIRE cache every decode
    step (measured 10-20x the minimal decode traffic).

    ``pos`` scalar: every batch row writes at the same position.
    ``pos`` (B,) vector: each slot writes at ITS OWN position; rows with
    ``pos < 0`` are skipped entirely (inactive / non-target slots — the
    continuous-batching server relies on this to keep live requests' cache
    entries untouched during another request's prefill)."""
    if jnp.ndim(pos) == 0:
        zero = jnp.int32(0)
        K = jax.lax.dynamic_update_slice(K, kk[None].astype(K.dtype),
                                         (l, zero, zero, pos, zero))
        V = jax.lax.dynamic_update_slice(V, vv[None].astype(V.dtype),
                                         (l, zero, zero, pos, zero))
        return K, V

    def write(full, new):
        layer = jax.lax.dynamic_index_in_dim(full, l, 0, keepdims=False)

        def one_row(row, tok, p):        # row (H,S,hd); tok (H,1,hd)
            # masked rows re-write their CURRENT slice (token-sized no-op)
            # instead of selecting over the whole layer — keeps the D1
            # token-slice traffic profile for the vector-pos path too
            p0 = jnp.maximum(p, 0)
            cur = jax.lax.dynamic_slice(
                row, (0, p0, 0), (row.shape[0], 1, row.shape[2]))
            tok = jnp.where(p >= 0, tok.astype(row.dtype), cur)
            return jax.lax.dynamic_update_slice(row, tok, (0, p0, 0))

        layer = jax.vmap(one_row)(layer, new, pos)
        return jax.lax.dynamic_update_index_in_dim(full, layer, l, 0)

    return write(K, kk), write(V, vv)


def _decode_attn_block_inplace(cfg, p, x, K, V, l, pos, xk=None, xv=None):
    """One decoder block; K/V are the GLOBAL stacked caches."""
    kw = _attn_kw(cfg)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    kk, vv = decode_project_kv(p["attn"], h, pos,
                               n_kv_heads=cfg.n_kv_heads,
                               head_dim=cfg.resolved_head_dim,
                               rope_theta=cfg.rope_theta)
    K, V = _write_token_kv(K, V, kk, vv, l, pos)
    x = x + decode_attend(p["attn"], h, _layer_slice(K, l),
                          _layer_slice(V, l), pos, **kw)
    if xk is not None:
        h = rms_norm(x, p["ln_x"], cfg.norm_eps)
        x = x + decode_attend(p["cross"], h, xk, xv,
                              jnp.int32(cfg.encoder_len - 1),
                              use_rope=False, **kw)
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        b = h.shape[0]
        y = moe_ffn(p["moe"], h.reshape(1, b, cfg.d_model), top_k=cfg.top_k,
                    capacity_factor=cfg.capacity_factor, act=cfg.act)
        x = x + y.reshape(b, 1, cfg.d_model)
    else:
        x = x + glu_mlp(p["mlp"], h, act=cfg.act)
    return x, K, V


def _decode_mamba_inplace(cfg, p, x, mcache, l, pos=None):
    """Mamba block with in-place state update into the stacked caches.

    Per-slot ``pos`` (B,) vectors mask the recurrent-state update the same
    way ``_write_token_kv`` masks K/V: rows with ``pos < 0`` keep their
    state untouched (bystander slots during another request's prefill)."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    st_old = _layer_slice(mcache, l)
    y, st = mamba2_decode_step(p["mixer"], h, st_old, d_inner=cfg.d_inner,
                               ssm_state=cfg.ssm_state,
                               head_dim=cfg.ssm_head_dim,
                               eps=cfg.norm_eps)
    if pos is not None and jnp.ndim(pos):
        keep = pos >= 0
        st = jax.tree.map(
            lambda new, old: jnp.where(
                keep.reshape((-1,) + (1,) * (old.ndim - 1)),
                new.astype(old.dtype), old),
            st, st_old)
    mcache = jax.tree.map(
        lambda full, new: jax.lax.dynamic_update_index_in_dim(
            full, new.astype(full.dtype), l, 0),
        mcache, st)
    return x + y, mcache


def decode_step(cfg: ArchConfig, params: dict, cache: dict,
                tokens: jax.Array, pos: jax.Array) -> tuple[jax.Array, dict]:
    """One decode step.  tokens (B, 1) int32 (or (B, 1, d) embeds for
    frontend archs); pos: scalar cache index, OR a per-slot (B,) vector for
    continuous batching — each slot reads/writes at its own position, and
    slots with ``pos < 0`` are masked out of every cache write (their
    logits are garbage and must be ignored).  Returns
    (logits (B, 1, V), new cache).

    §Perf D1: layers iterate via fori_loop carrying the GLOBAL caches and
    updating them with token-sized dynamic slices — the cache buffers alias
    in place under donation instead of being rewritten every step."""
    if cfg.takes_embeddings and tokens.ndim == 3:
        x = tokens.astype(cfg.compute_jdtype)
    else:
        x = params["embed"][tokens].astype(cfg.compute_jdtype)

    if cfg.family in ("dense", "moe", "vlm"):
        def step(l, carry):
            h, K, V = carry
            p = _layer_slice(params["blocks"], l)
            h, K, V = _decode_attn_block_inplace(cfg, p, h, K, V, l, pos)
            return h, K, V
        x, k, v = jax.lax.fori_loop(0, cfg.n_layers, step,
                                    (x, cache["k"], cache["v"]))
        cache = {"k": k, "v": v}
    elif cfg.family == "ssm":
        def step(l, carry):
            h, mc = carry
            p = _layer_slice(params["blocks"], l)
            h, mc = _decode_mamba_inplace(cfg, p, h, mc, l, pos)
            return h, mc
        x, cache = jax.lax.fori_loop(0, cfg.n_layers, step, (x, cache))
    elif cfg.family == "hybrid":
        per = cfg.attn_every
        groups = cfg.n_layers // per
        shared = params["shared"]

        def group(g, carry):
            h, mc, K, V = carry

            def inner(i, c2):
                hh, mc2 = c2
                l = g * per + i
                p = _layer_slice(params["blocks"], l)
                hh, mc2 = _decode_mamba_inplace(cfg, p, hh, mc2, l, pos)
                return hh, mc2
            h, mc = jax.lax.fori_loop(0, per, inner, (h, mc))
            h, K, V = _decode_attn_block_inplace(cfg, shared, h, K, V, g,
                                                 pos)
            return h, mc, K, V

        x, mst, k, v = jax.lax.fori_loop(
            0, groups, group, (x, cache["mamba"], cache["k"], cache["v"]))
        cache = {"mamba": mst, "k": k, "v": v}
    elif cfg.family == "audio":
        def step(l, carry):
            h, K, V = carry
            p = _layer_slice(params["blocks"], l)
            xk = _layer_slice(cache["xk"], l)
            xv = _layer_slice(cache["xv"], l)
            h, K, V = _decode_attn_block_inplace(cfg, p, h, K, V, l, pos,
                                                 xk, xv)
            return h, K, V
        x, k, v = jax.lax.fori_loop(0, cfg.n_layers, step,
                                    (x, cache["k"], cache["v"]))
        cache = {"k": k, "v": v, "xk": cache["xk"], "xv": cache["xv"]}
    else:
        raise ValueError(cfg.family)

    return _head(cfg, params, x), cache


def prefill(cfg: ArchConfig, params: dict, *,
            tokens: jax.Array | None = None,
            embeds: jax.Array | None = None,
            enc_embeds: jax.Array | None = None,
            impl: str = "auto") -> jax.Array:
    """Prefill forward: full-sequence backbone, last-token logits only
    (sliced BEFORE the vocab head so the (B, S, V) logits tensor never
    materializes at 32k/500k sequence lengths)."""
    if embeds is None:
        embeds = params["embed"][tokens]
    x = embeds.astype(cfg.compute_jdtype)
    enc = (_encode(cfg, params, enc_embeds, impl)
           if cfg.family == "audio" else None)
    x = _backbone(cfg, params, x, enc=enc, impl=impl)
    return _head(cfg, params, x[:, -1:, :])
