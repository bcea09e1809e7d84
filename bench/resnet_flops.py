"""The GEMMs of a ResNet configuration's call (``configs/resnet50.json``),
from its shapes alone: every convolution as an im2col GEMM and the fc,
and their operations and least time on the chip."""

from __future__ import annotations

from bench.flops import gemm_least_time


def resnet_gemms(cfg: dict, frames: int) -> list[tuple[str, int, int, int]]:
    """(name, m, k, n) of every GEMM of one call on ``frames`` frames, in
    the order they run: the stem conv, each bottleneck's ``a``, ``b``,
    ``c`` and (where it strides or widens) ``proj``, and the fc."""
    h = w = int(cfg["input_hw"])
    c = int(cfg["cin"])
    out = []
    for i, spec in enumerate(cfg["layers"]):
        kind = spec[0]
        if kind == "conv":
            _, cout, k, s, p = spec
            oh, ow = (h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1
            out.append((f"conv{i}", frames * oh * ow, k * k * c, cout))
            h, w, c = oh, ow, cout
        elif kind == "maxpool":
            _, k, s, p = spec
            h, w = (h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1
        elif kind == "bottleneck":
            _, width, cout, s = spec
            oh, ow = (h - 1) // s + 1, (w - 1) // s + 1
            out += [(f"block{i}_a", frames * h * w, c, width),
                    (f"block{i}_b", frames * oh * ow, 9 * width, width),
                    (f"block{i}_c", frames * oh * ow, width, cout)]
            if s != 1 or c != cout:
                out.append((f"block{i}_proj", frames * oh * ow, c, cout))
            h, w, c = oh, ow, cout
        elif kind == "gap":
            h = w = 1
        elif kind == "fc":
            out.append((f"fc{i}", frames, h * w * c, spec[1]))
            h = w = 1
            c = spec[1]
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
    return out


def flops_per_frame(cfg: dict) -> float:
    return sum(2.0 * m * k * n for _, m, k, n in resnet_gemms(cfg, 1))


def call_least_time(cfg: dict, frames: int, peaks: dict) -> float:
    return sum(gemm_least_time(m, k, n, peaks)[0]
               for _, m, k, n in resnet_gemms(cfg, frames))
