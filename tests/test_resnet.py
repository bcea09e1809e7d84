"""ResNet-50 v1.5's layer kinds in ``repro.models.cnn`` at a small size on
the CPU: the whole network against the benchmark's plain reference
(``bench/reference/resnet50.py``) on seeded random weights, with and
without the runtime, and each new piece alone: the 1x1 convolution that
reads its input without a gather, the overlapping max pool, batch-norm
folding, the block spans, and the errors for layer kinds a function does
not take."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import core
from repro.core.im2col import im2col
from repro.models import cnn
from repro.models.cnn import CNNConfig, cnn_forward, init_cnn

#: widths / 16 of ResNet-50 at 32 x 32: one block per stage and two in the
#: second, so identity and projection shortcuts (by stride and by width)
#: all run
LAYERS = (("conv", 4, 7, 2, 3), ("maxpool", 3, 2, 1),
          ("bottleneck", 4, 16, 1),
          ("bottleneck", 8, 32, 2), ("bottleneck", 8, 32, 1),
          ("bottleneck", 16, 64, 2), ("bottleneck", 32, 128, 2),
          ("gap",), ("fc", 10))
NET = CNNConfig(name="resnet-small", input_hw=32, cin=3, layers=LAYERS,
                num_classes=10, tile=32)
CFG = {"input_hw": 32, "cin": 3, "num_classes": 10,
       "layers": [list(spec) for spec in LAYERS]}


@pytest.fixture(scope="module")
def case():
    ref = core.load_module("reference", "resnet50")
    params = ref.make_params(CFG, jax.random.key(7))
    x = jax.random.normal(jax.random.key(8), (3, 32, 32, 3))
    want = np.asarray(ref.forward(CFG, params, x))
    return params, x, want


@pytest.mark.parametrize("engines", [None, ["xla", "reference"]],
                         ids=["no_runtime", "runtime"])
def test_matches_the_reference(case, engines):
    params, x, want = case
    if engines is None:
        got = cnn_forward(NET, params, x)
    else:
        from repro.soc import SynergyRuntime
        with SynergyRuntime(engines) as rt:
            got = cnn_forward(NET, params, x, runtime=rt)
            assert rt.stats()["total_panels"] > 0
    assert got.shape == (3, 10)
    assert np.abs(want).max() > 0.1          # logits neither vanish ...
    assert np.abs(want).max() < 1e3          # ... nor explode
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())


def test_layout_matches_the_reference(case):
    params, _, _ = case
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.eval_shape(lambda k: init_cnn(NET, k), jax.random.key(0))
    assert got == jax.tree.map(lambda a: (a.shape, a.dtype), want)
    assert {"block3_proj_w", "block4_a_w"} <= set(params)
    assert "block4_proj_w" not in params     # the identity shortcut


@pytest.mark.parametrize("stride", [1, 2])
def test_one_by_one_conv_reads_without_a_gather(monkeypatch, stride):
    x = jax.random.normal(jax.random.key(1), (2, 9, 9, 5))
    w = jax.random.normal(jax.random.key(2), (1, 1, 5, 7))
    b = jnp.arange(7.0)
    patches = im2col(x, 1, 1, stride, 0).reshape(-1, 5)
    want = patches @ w.reshape(5, 7) + b

    def no_gather(*a, **kw):
        raise AssertionError("a 1x1 convolution ran the im2col gather")

    monkeypatch.setattr(cnn, "im2col", no_gather)
    got = cnn._conv_via_jobs(x, w, b, stride, 0, 32, "one", activation=None)
    oh = (9 - 1) // stride + 1
    assert got.shape == (2, oh, oh, 7)
    np.testing.assert_allclose(np.asarray(got).reshape(-1, 7),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("k,stride,pad", [(3, 2, 1), (3, 1, 1), (2, 2, 0)])
def test_overlapping_max_pool(k, stride, pad):
    x = np.asarray(jax.random.normal(jax.random.key(3), (2, 11, 11, 3)))
    xp = np.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                constant_values=-np.inf)
    oh = (11 + 2 * pad - k) // stride + 1
    want = np.stack([np.stack([
        xp[:, i * stride:i * stride + k, j * stride:j * stride + k].max((1, 2))
        for j in range(oh)], 1) for i in range(oh)], 1)
    got = np.asarray(cnn.maxpool_window(jnp.asarray(x), k, stride, pad))
    np.testing.assert_array_equal(got, want)


def test_fold_batchnorm_matches_explicit_batchnorm():
    keys = jax.random.split(jax.random.key(4), 7)
    x = jax.random.normal(keys[0], (2, 6, 6, 4))
    w = jax.random.normal(keys[1], (3, 3, 4, 5))
    b = jax.random.normal(keys[2], (5,))
    gamma = jax.random.uniform(keys[3], (5,), minval=0.5, maxval=1.5)
    beta = jax.random.normal(keys[4], (5,))
    mean = jax.random.normal(keys[5], (5,))
    var = jax.random.uniform(keys[6], (5,), minval=0.5, maxval=2.0)

    def conv(x, w, b):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST) + b

    want = gamma * (conv(x, w, b) - mean) / jnp.sqrt(var + 1e-5) + beta
    wf, bf = cnn.fold_batchnorm(w, b, gamma, beta, mean, var, 1e-5)
    np.testing.assert_allclose(np.asarray(conv(x, wf, bf)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


def test_block_residual_and_gap_spans(case, monkeypatch):
    params, x, _ = case
    opened = []
    real = cnn.annotate

    def record(name, **tags):
        opened.append((name, tags))
        return real(name, **tags)

    monkeypatch.setattr(cnn, "annotate", record)
    cnn_forward(NET, params, x)
    blocks = [t for n, t in opened if n == "repro/cnn/block"]
    assert blocks == [{"stage": 1, "index": 0}, {"stage": 2, "index": 0},
                      {"stage": 2, "index": 1}, {"stage": 3, "index": 0},
                      {"stage": 4, "index": 0}]
    names = [n for n, _ in opened]
    assert names.count("repro/cnn/residual") == 5
    assert names.count("repro/cnn/gap") == 1
    assert names.count("repro/cnn/pool") == 1
    # 1 stem gather and one per block's 3x3; the 1x1s open the span but
    # gather nothing
    assert names.count("repro/cnn/im2col") == 1 + 5 * 3 + 4


BAD = CNNConfig(name="bad", input_hw=8, cin=3,
                layers=(("conv", 4, 3, 1, 1), ("dropout", 0.5), ("fc", 2)))


@pytest.mark.parametrize("call", [
    lambda: BAD.trace_shapes(),
    lambda: init_cnn(BAD, jax.random.key(0)),
    lambda: cnn.cnn_flops_per_frame(BAD),
    lambda: cnn_forward(BAD, {}, jnp.zeros((1, 8, 8, 3)))],
    ids=["trace_shapes", "init_cnn", "flops", "forward"])
def test_unknown_layer_kind_raises(call):
    with pytest.raises(ValueError, match="dropout"):
        call()


BLOCKY = CNNConfig(name="blocky", input_hw=8, cin=3,
                   layers=(("conv", 4, 3, 1, 1), ("bottleneck", 2, 8, 1),
                           ("fc", 2)))


@pytest.mark.parametrize("call", [
    lambda: cnn.conv_jobsets(BLOCKY),
    lambda: cnn.conv_graph_steps(BLOCKY),
    lambda: cnn.build_simnet(BLOCKY)],
    ids=["conv_jobsets", "conv_graph_steps", "build_simnet"])
def test_linear_chain_functions_refuse_blocks(call):
    with pytest.raises(NotImplementedError, match="'bottleneck'"):
        call()
