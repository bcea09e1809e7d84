"""Plain reference of the paper's CNNs (conv, ReLU, max pool, fc), the
weights made from the seed in the program's layout, and the same network
with every GEMM's operands on a lower-precision grid (the bf16 yardstick of
the comparison, and the int8 and fp8 controls).

Per layer spec of the configuration: ``["conv", cout, k, stride, pad]`` is
``relu(conv(x, W) + b)`` (NHWC, HWIO); ``["pool", s]`` a non-overlapping
s x s max pool that crops odd edges; ``["fc", n]`` is ``x.reshape(N, -1)
W + b``, with ReLU except on the last fc.  Float32 at ``highest``
precision; it imports nothing from the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def shapes(cfg: dict):
    """(spec, h, w, c) before each layer, and the output (h, w, c)."""
    h = w = int(cfg["input_hw"])
    c = int(cfg["cin"])
    out = []
    for spec in cfg["layers"]:
        out.append((tuple(spec), h, w, c))
        if spec[0] == "conv":
            _, cout, k, s, p = spec
            h = (h + 2 * p - k) // s + 1
            w = (w + 2 * p - k) // s + 1
            c = cout
        elif spec[0] == "pool":
            h, w = h // spec[1], w // spec[1]
        elif spec[0] == "fc":
            h = w = 1
            c = spec[1]
    return out, (h, w, c)


def make_params(cfg: dict, key) -> dict:
    """Random f32 weights in the layout of ``repro.models.cnn.init_cnn``
    (``conv{i}_w`` (k, k, cin, cout), ``fc{i}_w`` (n_in, n_out), biases
    ``..._b``), He-scaled, with N(0, 0.1^2) biases so the epilogue counts;
    made on the device in one jitted call."""
    layers, _ = shapes(cfg)

    def build(key):
        params = {}
        for i, (spec, h, w, c) in enumerate(layers):
            if spec[0] == "pool":
                continue
            key, kw, kb = jax.random.split(key, 3)
            if spec[0] == "conv":
                _, cout, k, _, _ = spec
                shape, fan_in, n = (k, k, c, cout), k * k * c, cout
                name = f"conv{i}"
            else:
                shape, fan_in, n = (h * w * c, spec[1]), h * w * c, spec[1]
                name = f"fc{i}"
            params[f"{name}_w"] = (jax.random.normal(kw, shape)
                                   * (2.0 / fan_in) ** 0.5)
            params[f"{name}_b"] = 0.1 * jax.random.normal(kb, (n,))
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
    last_fc = max(i for i, (s, *_r) in enumerate(layers) if s[0] == "fc")
    q = (lambda a, axes: _fake_quant(a, axes, quant)) if quant else \
        (lambda a, axes: a)
    for i, (spec, *_r) in enumerate(layers):
        if spec[0] == "conv":
            _, _, k, s, p = spec
            y = jax.lax.conv_general_dilated(
                q(x, (1, 2, 3)), q(params[f"conv{i}_w"], (0, 1, 2)),
                (s, s), [(p, p), (p, p)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi)
            x = jax.nn.relu(y + params[f"conv{i}_b"])
        elif spec[0] == "pool":
            n, h, w, c = x.shape
            z = spec[1]
            x = x[:, : h - h % z, : w - w % z, :]
            x = x.reshape(n, h // z, z, w // z, z, c).max(axis=(2, 4))
        else:
            x = x.reshape(x.shape[0], -1)
            x = jnp.dot(q(x, (1,)), q(params[f"fc{i}_w"], (0,)),
                        precision=hi) + params[f"fc{i}_b"]
            if i != last_fc:
                x = jax.nn.relu(x)
    return x

