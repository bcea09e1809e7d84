"""Operations and bytes of the work a cell asks for, from the
configuration's shapes alone (whatever engine or kernel runs it)."""

from __future__ import annotations


def cnn_gemms(cfg: dict, frames: int) -> list[tuple[str, int, int, int]]:
    """(name, m, k, n) of every CONV (as im2col GEMM) and FC GEMM of one
    call on ``frames`` frames."""
    h = w = int(cfg["input_hw"])
    c = int(cfg["cin"])
    out = []
    for i, spec in enumerate(cfg["layers"]):
        if spec[0] == "conv":
            _, cout, k, s, p = spec
            oh = (h + 2 * p - k) // s + 1
            ow = (w + 2 * p - k) // s + 1
            out.append((f"conv{i}", frames * oh * ow, k * k * c, cout))
            h, w, c = oh, ow, cout
        elif spec[0] == "pool":
            h, w = h // spec[1], w // spec[1]
        elif spec[0] == "fc":
            out.append((f"fc{i}", frames, h * w * c, spec[1]))
            h = w = 1
            c = spec[1]
    return out


def cnn_flops_per_frame(cfg: dict) -> float:
    return sum(2.0 * m * k * n for _, m, k, n in cnn_gemms(cfg, 1))


def gemm_least_time(m: int, k: int, n: int, peaks: dict,
                    elem_bytes: int = 4) -> tuple[float, str]:
    """The least time the chip could take for one (m, k) x (k, n) GEMM
    with its bias: operations over peak FLOP/s, or reading A, B and the
    bias and writing C once over peak bandwidth, whichever is larger; and
    which of the two bounds it."""
    t_flops = 2.0 * m * k * n / peaks["bf16_flops"]
    t_bytes = (elem_bytes * (m * k + k * n + n + m * n)
               / peaks["hbm_bytes_per_s"])
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


def cnn_call_least_time(cfg: dict, frames: int, peaks: dict) -> float:
    return sum(gemm_least_time(m, k, n, peaks)[0]
               for _, m, k, n in cnn_gemms(cfg, frames))
