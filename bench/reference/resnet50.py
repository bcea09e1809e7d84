"""Plain reference of ResNet-50 v1.5 (He et al., arXiv:1512.03385, Table 1;
the stride on each stage's first 3x3, as MLPerf Inference's
resnet50-v1.5), batch norm folded into each convolution, the weights made
from the seed in the program's layout, and the same network with every
GEMM's operands on a lower-precision grid (the bf16 yardstick of the
comparison, and the int8 and fp8 controls).

Per layer spec of the configuration: ``["conv", cout, k, stride, pad]``
is ``relu(conv(x, W) + b)`` (NHWC, HWIO); ``["maxpool", k, stride, pad]``
a k x k max pool padded with -inf; ``["bottleneck", width, cout, stride]``
is ``relu(c(b(a(x))) + shortcut(x))`` with ``a`` a 1x1 conv to ``width``
+ ReLU, ``b`` a 3x3 conv at ``stride``, pad 1, + ReLU, ``c`` a 1x1 conv to
``cout``, and the shortcut a 1x1 conv at ``stride`` where the block
strides or widens, else ``x``; ``["gap"]`` the mean over H and W;
``["fc", n]`` is ``x W + b`` (the last layer, no ReLU).  Float32 at
``highest`` precision; it imports nothing from the program."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: folded scale of each block's last conv (``c``) against He scale: a
#: trained ResNet's last BN in a block has small gamma, and with it the
#: residual sum stays of unit order through the 16 blocks
LAST_SCALE = 0.2
#: standard deviation of every folded bias (beta - mean * gamma / sigma)
BIAS_STD = 0.1


def _has_proj(cin: int, cout: int, stride: int) -> bool:
    return stride != 1 or cin != cout


def _convs(spec, c: int) -> list[tuple]:
    """``(part, k, stride, pad, cin, cout)`` of a bottleneck's convs."""
    _, width, cout, s = spec
    out = [("a", 1, 1, 0, c, width), ("b", 3, s, 1, width, width),
           ("c", 1, 1, 0, width, cout)]
    if _has_proj(c, cout, s):
        out.append(("proj", 1, s, 0, c, cout))
    return out


def shapes(cfg: dict):
    """(spec, h, w, c) before each layer, and the output (h, w, c)."""
    h = w = int(cfg["input_hw"])
    c = int(cfg["cin"])
    out = []
    for spec in cfg["layers"]:
        spec = tuple(spec)
        out.append((spec, h, w, c))
        kind = spec[0]
        if kind == "conv":
            _, cout, k, st, p = spec
        elif kind == "maxpool":
            _, k, st, p = spec
            cout = c
        elif kind == "bottleneck":
            k, st, p, cout = 3, spec[3], 1, spec[2]
        elif kind == "gap":
            h = w = 1
            continue
        elif kind == "fc":
            h = w = 1
            c = spec[1]
            continue
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
        h = (h + 2 * p - k) // st + 1
        w = (w + 2 * p - k) // st + 1
        c = cout
    return out, (h, w, c)


def make_params(cfg: dict, key) -> dict:
    """Random f32 weights in the layout of ``repro.models.cnn.init_cnn``
    (``conv{i}_w`` (k, k, cin, cout), ``block{i}_{a,b,c,proj}_w``,
    ``fc{i}_w`` (n_in, n_out), biases ``..._b``): He-normal convolutions
    (``LAST_SCALE`` times that for each block's ``c``), N(0, BIAS_STD^2)
    biases.  Made on the device in one jitted call, from one normal draw
    for all weights and one for all biases, cut in network order."""
    layers, _ = shapes(cfg)
    tensors = []                    # (name, shape, std)
    for i, (spec, h, w, c) in enumerate(layers):
        if spec[0] == "conv":
            _, cout, k, _, _ = spec
            tensors.append((f"conv{i}", (k, k, c, cout),
                            (2.0 / (k * k * c)) ** 0.5))
        elif spec[0] == "bottleneck":
            for part, k, _, _, ci, co in _convs(spec, c):
                scale = LAST_SCALE if part == "c" else 1.0
                tensors.append((f"block{i}_{part}", (k, k, ci, co),
                                scale * (2.0 / (k * k * ci)) ** 0.5))
        elif spec[0] == "fc":
            n_in = h * w * c
            tensors.append((f"fc{i}", (n_in, spec[1]), (2.0 / n_in) ** 0.5))
    n_w = sum(math.prod(shape) for _, shape, _ in tensors)
    n_b = sum(shape[-1] for _, shape, _ in tensors)

    def build(key):
        kw, kb = jax.random.split(key)
        ws = jax.random.normal(kw, (n_w,))
        bs = BIAS_STD * jax.random.normal(kb, (n_b,))
        params, ow, ob = {}, 0, 0
        for name, shape, std in tensors:
            size = math.prod(shape)
            params[f"{name}_w"] = ws[ow:ow + size].reshape(shape) * std
            params[f"{name}_b"] = bs[ob:ob + shape[-1]]
            ow, ob = ow + size, ob + shape[-1]
        return params

    return jax.jit(build)(key)


def _fake_quant(x, axes, kind):
    """x on the ``kind`` grid (int8 and fp8 with one scale per slice over
    ``axes``)."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    if kind == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if kind == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    if kind == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    raise ValueError(f"unknown precision {kind!r}")


def forward(cfg: dict, params: dict, x, quant: str | None = None):
    """Logits (N, classes) of frames ``x`` (N, H, W, C).  With ``quant``
    every GEMM's operands are on that grid (int8 and fp8: activations per
    frame, weights per output channel)."""
    hi = jax.lax.Precision.HIGHEST
    layers, _ = shapes(cfg)
    q = (lambda a, axes: _fake_quant(a, axes, quant)) if quant else \
        (lambda a, axes: a)

    def conv(x, name, s, p):
        y = jax.lax.conv_general_dilated(
            q(x, (1, 2, 3)), q(params[f"{name}_w"], (0, 1, 2)), (s, s),
            [(p, p), (p, p)], dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=hi)
        return y + params[f"{name}_b"]

    for i, (spec, _, _, c) in enumerate(layers):
        kind = spec[0]
        if kind == "conv":
            x = jax.nn.relu(conv(x, f"conv{i}", spec[3], spec[4]))
        elif kind == "maxpool":
            _, k, s, p = spec
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, k, k, 1), (1, s, s, 1),
                ((0, 0), (p, p), (p, p), (0, 0)))
        elif kind == "bottleneck":
            _, _, cout, s = spec
            y = jax.nn.relu(conv(x, f"block{i}_a", 1, 0))
            y = jax.nn.relu(conv(y, f"block{i}_b", s, 1))
            y = conv(y, f"block{i}_c", 1, 0)
            short = (conv(x, f"block{i}_proj", s, 0)
                     if _has_proj(c, cout, s) else x)
            x = jax.nn.relu(y + short)
        elif kind == "gap":
            x = jnp.mean(x, axis=(1, 2))
        else:
            x = x.reshape(x.shape[0], -1)
            x = jnp.dot(q(x, (1,)), q(params[f"fc{i}_w"], (0,)),
                        precision=hi) + params[f"fc{i}_b"]
    return x
