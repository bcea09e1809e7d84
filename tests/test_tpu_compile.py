"""Compile the main path's kernels for a described TPU v5e chip.

Nothing here runs: each test lowers and compiles for one chip of a
``v5e:2x2`` topology that the TPU compiler describes without a chip
attached, so what Mosaic or XLA refuses on the chip (block shapes that do
not tile, kernels that do not lower, a Pallas kernel under
differentiation) fails here at no chip time.  Shapes are the published
widths of granite-3-2b and mamba2-130m, and every GEMM of the benchmark's
CNN calls.

The topology is described inside a fixture and never while a module is
imported: the TPU library admits one process at a time, and test workers
must all collect the same tests.  Keep every chip compile in this file.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.core.serving import _SERVE_TILE
from repro.core.synergy_mm import DEFAULT_TILE
from repro.engines import op_variants, register_op_impl
from repro.kernels import gemm_block, kernel_block
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.qmm import qmm_pallas
from repro.kernels.ssd import ssd
from repro.kernels.ssd.ssd import ssd_pallas
from repro.kernels.tiled_mm import tiled_mm_pallas
from repro.kernels.vpu_mm import vpu_mm_pallas
from repro.models.attention import attention

from _gemms import MODEL_GEMMS

GRANITE = ARCHS["granite-3-2b"]
MAMBA = ARCHS["mamba2-130m"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_variants():
    """Resolve op variants as on the chip, where the Pallas variants are
    available (this process's backend is the CPU) and run compiled, not
    interpreted."""
    saved = {op: {v.name: v for v in op_variants(op)}
             for op in ("attention_scores", "ssd")}
    register_op_impl(
        "attention_scores", "pallas",
        lambda q, k, v, *, causal, blk_q, blk_k: flash_attention_pallas(
            q, k, v, causal=causal, blk_q=min(128, q.shape[2]),
            blk_k=min(128, k.shape[2])),
        priority=10, grad=False, override=True)
    register_op_impl(
        "ssd", "pallas",
        lambda xdt, dta, bm, cm, *, chunk: ssd_pallas(xdt, dta, bm, cm,
                                                      chunk=chunk),
        priority=10, grad=False, override=True)
    yield
    for op, variants in saved.items():
        v = variants["pallas"]
        register_op_impl(op, v.name, v.fn, priority=v.priority,
                         available=v.available, grad=v.grad, override=True)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _gemm_operands(one_chip, tile, m, dtype):
    """A (m, d_model) @ (d_model, d_ff) GEMM padded to the block the
    engines derive from ``tile``."""
    bm, bn, bk = kernel_block(tile)
    pad = lambda x, b: -(-x // b) * b
    return (_spec(one_chip, (pad(m, bm), pad(GRANITE.d_model, bk)), dtype),
            _spec(one_chip, (pad(GRANITE.d_model, bk), pad(GRANITE.d_ff, bn)),
                  dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tile", [_SERVE_TILE, DEFAULT_TILE],
                         ids=["serve", "default"])
def test_tiled_mm_compiles_at_engine_block(one_chip, tile, dtype):
    a, b = _gemm_operands(one_chip, tile, 8, dtype)
    blk = kernel_block(tile)
    _compile(lambda a, b: tiled_mm_pallas(a, b, tile=blk,
                                          activation=jax.nn.relu), a, b)


#: granite's decode GEMMs at 8 slots: the MLP both ways and the LM head,
#: whose odd vocabulary the wrapper pads where the block does not hold it
_SERVE_GEMMS = [(8, GRANITE.d_model, GRANITE.d_ff),
                (8, GRANITE.d_ff, GRANITE.d_model),
                (8, GRANITE.d_model, GRANITE.vocab_size)]


@pytest.mark.parametrize("m,k,n,dtype", [
    pytest.param(*s, jnp.float32, id=f"{cfg}-{'x'.join(map(str, s))}")
    for cfg, shapes in MODEL_GEMMS.items() for s in shapes] + [
    pytest.param(*s, dtype, id=f"serve-{'x'.join(map(str, s))}-{name}")
    for s in _SERVE_GEMMS
    for dtype, name in ((jnp.float32, "f32"), (jnp.bfloat16, "bf16"))])
def test_tiled_mm_compiles_at_gemm_block(one_chip, m, k, n, dtype):
    """Each f32 GEMM of the CNN cells, unpadded, and the serving GEMMs,
    padded as the wrapper pads them, at the block the wrapper picks:
    Mosaic refuses a block that does not tile or that overflows the
    kernel's VMEM."""
    blk = gemm_block(m, k, n, jnp.dtype(dtype).itemsize, _SERVE_TILE)
    _, bn, bk = blk
    k, n = -(-k // bk) * bk, -(-n // bn) * bn
    a = _spec(one_chip, (m, k), dtype)
    b = _spec(one_chip, (k, n), dtype)
    bias = _spec(one_chip, (n,), dtype)
    compiled = _compile(lambda a, b, bias: tiled_mm_pallas(
        a, b, bias=bias, activation=jax.nn.relu, tile=blk), a, b, bias)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("tile", [_SERVE_TILE, DEFAULT_TILE],
                         ids=["serve", "default"])
def test_qmm_int8_compiles_at_engine_block(one_chip, tile):
    a, b = _gemm_operands(one_chip, tile, 8, jnp.int8)
    scale = _spec(one_chip, (1, b.shape[1]), jnp.float32)
    blk = kernel_block(tile)
    _compile(lambda a, b, s: qmm_pallas(a, b, s, tile=blk), a, b, scale)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_vpu_mm_compiles_at_engine_block(one_chip, dtype):
    # NeonVpuEngine caps the job tile at 128 before the block is derived
    tile = tuple(min(t, 128) for t in (_SERVE_TILE,) * 3)
    a, b = _gemm_operands(one_chip, tile, 8, dtype)
    blk = kernel_block(tile)
    _compile(lambda a, b: vpu_mm_pallas(a, b, tile=blk), a, b)


def test_flash_attention_forward_compiles(one_chip):
    hd = GRANITE.resolved_head_dim
    q = _spec(one_chip, (1, GRANITE.n_heads, 2048, hd), jnp.bfloat16)
    kv = _spec(one_chip, (1, GRANITE.n_kv_heads, 2048, hd), jnp.bfloat16)
    compiled = _compile(lambda q, k, v: flash_attention_pallas(q, k, v),
                        q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


def test_attention_grad_compiles_as_training_resolves_it(one_chip,
                                                         tpu_variants):
    """Training differentiates attention; resolved as on the chip, the
    forward takes the flash kernel and the gradient a variant with a VJP."""
    hd, d = GRANITE.resolved_head_dim, GRANITE.d_model
    kw = dict(n_heads=GRANITE.n_heads, n_kv_heads=GRANITE.n_kv_heads,
              head_dim=hd)
    params = {"wq": _spec(one_chip, (d, GRANITE.n_heads * hd), jnp.bfloat16),
              "wk": _spec(one_chip, (d, GRANITE.n_kv_heads * hd),
                          jnp.bfloat16),
              "wv": _spec(one_chip, (d, GRANITE.n_kv_heads * hd),
                          jnp.bfloat16),
              "wo": _spec(one_chip, (GRANITE.n_heads * hd, d), jnp.bfloat16)}
    x = _spec(one_chip, (1, 2048, d), jnp.bfloat16)
    fwd = _compile(lambda p, x: attention(p, x, **kw), params, x)
    assert "tpu_custom_call" in fwd.as_text()
    _compile(jax.grad(
        lambda p, x: attention(p, x, **kw).astype(jnp.float32).sum()),
        params, x)


def _ssd_operands(one_chip, seq: int = 2048):
    h = MAMBA.ssm_heads
    return (_spec(one_chip, (1, seq, h, MAMBA.ssm_head_dim), jnp.bfloat16),
            _spec(one_chip, (1, seq, h), jnp.float32),
            _spec(one_chip, (h,), jnp.float32),
            _spec(one_chip, (1, seq, MAMBA.ssm_state), jnp.bfloat16),
            _spec(one_chip, (1, seq, MAMBA.ssm_state), jnp.bfloat16))


def test_ssd_forward_compiles(one_chip, tpu_variants):
    compiled = _compile(lambda *a: ssd(*a, chunk=MAMBA.ssm_chunk),
                        *_ssd_operands(one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_grad_compiles_as_training_resolves_it(one_chip, tpu_variants):
    _compile(jax.grad(
        lambda x, *rest: ssd(x, *rest, chunk=MAMBA.ssm_chunk)[0]
        .astype(jnp.float32).sum()), *_ssd_operands(one_chip))
