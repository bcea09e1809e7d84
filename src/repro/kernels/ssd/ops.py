"""Jit'd SSD wrapper: pre-scaling, op-variant dispatch via the
``repro.engines`` registry, and the chunked XLA path (same math as the
kernel, expressed with lax.scan over chunks — this is what the 512-device
dry-run lowers so the HLO stays canonical)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.engines import register_op_impl, resolve_op, under_grad_trace

from .ssd import ssd_pallas
from .ref import ssd_ref

__all__ = ["ssd", "ssd_chunked_xla"]


def _prescale(x, dt, a):
    """x (B,L,H,P), dt (B,L,H) [post-softplus], a (H,) [negative] ->
    kernel layout xdt (B,H,L,P), dta (B,H,L).

    §Perf C1: xdt stays in x's dtype — the f32 dt would otherwise promote
    the whole SSD pipeline (and its out-projection all-reduce) to f32,
    doubling HBM and ICI traffic.  dta stays f32 (tiny; drives exps)."""
    xdt = jnp.swapaxes(x * dt[..., None].astype(x.dtype), 1, 2)
    dta = jnp.swapaxes(dt * a[None, None, :], 1, 2)
    return xdt, dta


def ssd_chunked_xla(xdt, dta, bm, cm, *, chunk: int = 128):
    """Chunked SSD in pure jnp (scan over chunks) — O(L Q) not O(L^2)."""
    b, h, l, p = xdt.shape
    n = bm.shape[-1]
    nc = l // chunk
    xdt_c = xdt.reshape(b, h, nc, chunk, p)
    dta_c = dta.reshape(b, h, nc, chunk)
    bm_c = bm.reshape(b, nc, chunk, n)
    cm_c = cm.reshape(b, nc, chunk, n)

    q = chunk
    li = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tril = li >= lj

    def step(s, inp):
        xdt_i, dta_i, bm_i, cm_i = inp          # (B,H,Q,P),(B,H,Q),(B,Q,N),(B,Q,N)
        cdt = xdt_i.dtype                       # compute dtype (bf16/f32)
        seg = jnp.cumsum(dta_i, axis=-1)        # (B,H,Q) f32
        total = seg[..., -1]
        # mask INSIDE the exp: the j>i half has positive exponents that
        # overflow to inf and poison the backward pass (0 * inf = NaN).
        diff = jnp.where(tril, seg[..., :, None] - seg[..., None, :], -1e30)
        # §Perf C1: the (Q,Q) decay/CB products and the chunk dots run in
        # the model's compute dtype with f32 accumulation — the f32 (Q,Q)
        # buffers were the dominant HBM traffic of the SSM prefill.
        decay = jnp.exp(diff).astype(cdt)       # (B,H,Q,Q)
        cb = jnp.einsum("bqn,bkn->bqk", cm_i, bm_i,
                        preferred_element_type=jnp.float32).astype(cdt)
        y = jnp.einsum("bhqk,bhkp->bhqp", cb[:, None] * decay, xdt_i,
                       preferred_element_type=jnp.float32)
        y += jnp.exp(seg)[..., None] * jnp.einsum(
            "bqn,bhpn->bhqp", cm_i.astype(jnp.float32), s)
        w = jnp.exp(total[..., None] - seg)[..., None].astype(cdt) * xdt_i
        s = (jnp.exp(total)[..., None, None] * s
             + jnp.einsum("bhqp,bqn->bhpn", w, bm_i,
                          preferred_element_type=jnp.float32))
        return s, y

    s0 = jnp.zeros((b, h, p, n), jnp.float32)
    inputs = (jnp.moveaxis(xdt_c, 2, 0), jnp.moveaxis(dta_c, 2, 0),
              jnp.moveaxis(bm_c, 1, 0), jnp.moveaxis(cm_c, 1, 0))
    s_fin, ys = jax.lax.scan(step, s0, inputs)
    y = jnp.moveaxis(ys, 0, 2).reshape(b, h, l, p).astype(xdt.dtype)
    return y, s_fin


register_op_impl(
    "ssd", "pallas",
    lambda xdt, dta, bm, cm, *, chunk: ssd_pallas(
        xdt, dta, bm, cm, chunk=chunk,
        interpret=jax.default_backend() != "tpu"),
    priority=10, available=lambda: jax.default_backend() == "tpu",
    grad=False)
register_op_impl(
    "ssd", "xla",
    lambda xdt, dta, bm, cm, *, chunk: ssd_chunked_xla(
        xdt, dta, bm, cm, chunk=chunk),
    priority=0)
register_op_impl(
    "ssd", "ref",
    lambda xdt, dta, bm, cm, *, chunk: ssd_ref(xdt, dta, bm, cm),
    priority=-10)


def ssd(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
        cm: jax.Array, *, chunk: int = 128,
        impl: str = "auto") -> tuple[jax.Array, jax.Array]:
    """Mamba2 SSD.  x (B,L,H,P), dt (B,L,H) post-softplus, a (H,) negative,
    bm/cm (B,L,N).  Returns y (B,L,H,P) and final state (B,H,P,N).

    L is padded up to a chunk multiple with zeros — zero xdt/dta steps are
    identity for the recurrence (state unchanged), so padding is exact.
    Whether the operands are being differentiated is decided here, outside
    the jit, so it keys the jit cache: a differentiated call never reuses
    a trace that resolved a variant without a VJP."""
    return _ssd(x, dt, a, bm, cm, chunk=chunk, impl=impl,
                grad=under_grad_trace(x, dt, a, bm, cm))


@functools.partial(jax.jit, static_argnames=("chunk", "impl", "grad"))
def _ssd(x, dt, a, bm, cm, *, chunk: int, impl: str, grad: bool):
    l_orig = x.shape[1]
    chunk = min(chunk, max(1, l_orig))
    pad = (-l_orig) % chunk
    if pad:
        padl = lambda t: jnp.pad(t, [(0, 0), (0, pad)]
                                 + [(0, 0)] * (t.ndim - 2))
        x, dt, bm, cm = padl(x), padl(dt), padl(bm), padl(cm)
    xdt, dta = _prescale(x, dt, a)
    y, s = resolve_op("ssd", impl, grad=grad)(xdt, dta, bm, cm, chunk=chunk)
    y = jnp.swapaxes(y, 1, 2)
    return (y[:, :l_orig] if pad else y), s
