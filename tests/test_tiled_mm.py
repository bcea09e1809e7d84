"""tiled_mm Pallas kernel vs pure-jnp oracle: shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need the dev deps
from hypothesis import given, settings, strategies as st

from repro.kernels import gemm_block
from repro.kernels.tiled_mm import tiled_matmul, tiled_mm_pallas, tiled_mm_ref

from _gemms import MODEL_GEMMS, RESNET50_CALL


def _rand(key, shape, dtype):
    x = jax.random.normal(jax.random.key(key), shape, jnp.float32)
    return x.astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(32, 32, 32), (64, 128, 96),
                                   (70, 45, 33), (1, 257, 129),
                                   (130, 1, 31)])
def test_matches_ref(shape, dtype):
    m, n, k = shape
    a = _rand(0, (m, k), dtype)
    b = _rand(1, (k, n), dtype)
    y = tiled_matmul(a, b, tile=32)
    r = tiled_mm_ref(a, b)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(r, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("act", [None, jax.nn.relu, jax.nn.silu])
def test_fused_epilogue(act):
    a = _rand(2, (48, 40), jnp.float32)
    b = _rand(3, (40, 56), jnp.float32)
    bias = _rand(4, (56,), jnp.float32)
    y = tiled_matmul(a, b, bias=bias, activation=act, tile=(16, 32, 16))
    r = tiled_mm_ref(a, b, bias=bias, activation=act)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r),
                               rtol=1e-5, atol=1e-5)


def test_paper_tile_size_32():
    """The paper's TS=32 PE configuration is exactly expressible."""
    a = _rand(5, (100, 75), jnp.float32)   # CIFAR conv1-like GEMM panel
    b = _rand(6, (75, 32), jnp.float32)
    y = tiled_matmul(a, b, tile=32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(tiled_mm_ref(a, b)),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=12, deadline=None)
@given(m=st.integers(1, 70), n=st.integers(1, 70), k=st.integers(1, 70),
       tm=st.sampled_from([8, 16, 32]), tn=st.sampled_from([8, 16, 32]),
       tk=st.sampled_from([8, 16, 32]))
def test_property_any_shape_any_tile(m, n, k, tm, tn, tk):
    """Border zero-padding (paper §3.2.1) makes every (shape, tile) pair
    correct — the fixed-size PE serves every layer."""
    a = _rand(m * 7919 + n, (m, k), jnp.float32)
    b = _rand(k * 31 + 1, (k, n), jnp.float32)
    y = tiled_matmul(a, b, tile=(tm, tn, tk))
    np.testing.assert_allclose(np.asarray(y), np.asarray(tiled_mm_ref(a, b)),
                               rtol=2e-5, atol=2e-5)


def test_out_dtype():
    a = _rand(7, (33, 65), jnp.bfloat16)
    b = _rand(8, (65, 17), jnp.bfloat16)
    y = tiled_matmul(a, b, tile=32, out_dtype=jnp.float32)
    assert y.dtype == jnp.float32


@pytest.mark.parametrize("m,k,n,bm", [(196, 147, 64, 64), (200, 75, 1000, 64),
                                      (20_000, 75, 10, None)])
def test_ragged_row_block(m, k, n, bm):
    """A row block that does not divide M: the last block's rows past M
    are read as garbage and never stored, so the result is exact; the
    wrapper picks such a block for a tall GEMM itself (``bm=None``)."""
    a = _rand(9, (m, k), jnp.float32)
    b = _rand(10, (k, n), jnp.float32)
    bias = _rand(11, (n,), jnp.float32)
    if bm is None:
        assert m % gemm_block(m, k, n, 4, 32)[0]
        y = tiled_matmul(a, b, bias=bias, activation=jax.nn.relu, tile=32)
    else:
        y = tiled_mm_pallas(a, b, bias=bias, activation=jax.nn.relu,
                            tile=(bm, n, k), interpret=True)
    r = tiled_mm_ref(a, b, bias=bias, activation=jax.nn.relu)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r),
                               rtol=1e-5, atol=1e-5)


def _grid_steps(m, k, n):
    bm, bn, bk = gemm_block(m, k, n, 4, 32)
    return -(-m // bm) * -(-n // bn) * -(-k // bk)


def test_resnet50_call_takes_few_grid_steps():
    """The 54 GEMMs of a 64-frame ResNet-50 call run in about a thousand
    grid steps at the paper's tile, not the 629,024 of 32-row blocks."""
    assert sum(_grid_steps(*s) for s in RESNET50_CALL) <= 10_000


@pytest.mark.parametrize("cfg", sorted(MODEL_GEMMS))
def test_model_gemms_need_no_padding(cfg):
    """Every GEMM of the benchmark's CNNs has a block that divides its K
    and N (or is the whole dimension), so the wrapper copies nothing."""
    for m, k, n in MODEL_GEMMS[cfg]:
        bm, bn, bk = gemm_block(m, k, n, 4, 32)
        assert k % bk == 0 and n % bn == 0, ((m, k, n), (bm, bn, bk))
