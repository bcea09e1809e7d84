"""The paper's 7 CNN benchmarks: JAX forward correctness + DES reproduction
of the headline claims (Fig 9, Table 6)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_cnns import PAPER_CNNS
from repro.core.im2col import conv2d_gemm, im2col, im2col_operand
from repro.core.synergy_mm import SynergyTrace
from repro.models.cnn import (build_simnet, cnn_flops_per_frame, cnn_forward,
                              init_cnn)


@pytest.mark.parametrize("name", sorted(PAPER_CNNS))
def test_cnn_forward(name):
    cfg = PAPER_CNNS[name]
    params = init_cnn(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1),
                          (2, cfg.input_hw, cfg.input_hw, cfg.cin))
    tr = SynergyTrace()
    with tr.activate():
        logits = jax.jit(lambda p, xx: cnn_forward(cfg, p, xx))(params, x)
    assert logits.shape == (2, cfg.num_classes)
    assert bool(jnp.isfinite(logits).all())
    n_conv = sum(1 for s in cfg.layers if s[0] == "conv")
    n_fc = sum(1 for s in cfg.layers if s[0] == "fc")
    assert len(tr.jobsets) == n_conv + n_fc        # every GEMM traced


def test_im2col_matches_lax_conv():
    x = jax.random.normal(jax.random.key(2), (2, 12, 12, 3))
    w = jax.random.normal(jax.random.key(3), (5, 5, 3, 7))
    out = conv2d_gemm(x, w, stride=1, padding=2)
    ref = jax.lax.conv_general_dilated(
        x, w, (1, 1), [(2, 2), (2, 2)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_im2col_shapes():
    x = jnp.zeros((1, 8, 8, 2))
    patches = im2col(x, 3, 3, stride=1, padding=1)
    assert patches.shape == (1, 64, 18)


def _loop_im2col(x, k, stride, pad):
    """The GEMM operand by a plain loop: one row per output pixel
    (n, oh, ow), its (kh, kw, c) receptive field flattened."""
    x = np.pad(np.asarray(x), ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    n, h, w, c = x.shape
    oh, ow = (h - k) // stride + 1, (w - k) // stride + 1
    out = np.empty((n, oh, ow, k * k * c), x.dtype)
    for i in range(oh):
        for j in range(ow):
            out[:, i, j] = x[:, i * stride:i * stride + k,
                             j * stride:j * stride + k].reshape(n, -1)
    return out.reshape(n * oh * ow, k * k * c)


#: (n, h, c, k, stride, pad): every patch-gathered conv geometry of
#: CIFAR_full (5x5/1 pad 2 on 3 and 32 channels) and ResNet-50 (the 7x7/2
#: pad 3 stem, the 3x3/1 and 3x3/2 pad 1 on 64-512 channels), at small
#: H and W, and a pad-0 strided one
GEOMETRIES = {
    "cifar_conv0": (1, 12, 3, 5, 1, 2),
    "cifar_conv1": (2, 8, 32, 5, 1, 2),
    "resnet_stem": (2, 15, 3, 7, 2, 3),
    "resnet_3x3_c64": (1, 9, 64, 3, 1, 1),
    "resnet_3x3_s2_c128": (2, 10, 128, 3, 2, 1),
    "resnet_3x3_c256": (1, 6, 256, 3, 1, 1),
    "resnet_3x3_s2_c512": (1, 7, 512, 3, 2, 1),
    "k3_s2_pad0": (2, 9, 5, 3, 2, 0),
}


@pytest.mark.parametrize("under_jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_patch_operand_is_bitwise_a_loop_im2col(geometry, under_jit):
    n, h, c, k, s, p = GEOMETRIES[geometry]
    x = jax.random.normal(jax.random.key(5), (n, h, h, c))
    build = lambda x: im2col_operand(x, k, k, s, p)  # noqa: E731
    got = (jax.jit(build) if under_jit else build)(x)
    want = _loop_im2col(x, k, s, p)
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        np.asarray(im2col(x, k, k, s, p)).reshape(want.shape), want)


@pytest.mark.parametrize("k,s,p", [(5, 1, 2), (3, 2, 1), (7, 2, 3)])
def test_conv2d_gemm_grad_matches_lax_conv(k, s, p):
    x = jax.random.normal(jax.random.key(6), (2, 11, 11, 4))
    w = jax.random.normal(jax.random.key(7), (k, k, 4, 3))

    def by_gemm(x, w):
        return jnp.sum(jnp.sin(conv2d_gemm(x, w, stride=s, padding=p)))

    def by_conv(x, w):
        return jnp.sum(jnp.sin(jax.lax.conv_general_dilated(
            x, w, (s, s), [(p, p), (p, p)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))))

    got = jax.grad(by_gemm, argnums=(0, 1))(x, w)
    want = jax.grad(by_conv, argnums=(0, 1))(x, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)


def test_flops_match_paper_gops_scale():
    """Per-frame op counts should sit in the paper's GOPS-at-fps range
    (Table 4): MNIST ~22 MOP, CIFAR_full ~26 MOP."""
    assert 15e6 < cnn_flops_per_frame(PAPER_CNNS["MNIST"]) < 35e6
    assert 15e6 < cnn_flops_per_frame(PAPER_CNNS["CIFAR_full"]) < 40e6


def test_simnet_structure():
    net = build_simnet(PAPER_CNNS["CIFAR_Darknet"])
    convs = [l for l in net.layers if l.kind == "conv"]
    assert len(convs) == 4                       # Table 2: 4 CONV layers
    assert all(l.jobset.num_jobs > 0 for l in convs)
