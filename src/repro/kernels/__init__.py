"""Pallas kernels for the framework's hot spots (tiled GEMM, its VPU-only
and int8 variants, flash attention, the Mamba2 SSD scan)."""

__all__ = ["kernel_block"]

#: the TPU's vector tiling: a block's last dim is a multiple of the lane
#: width (also the MXU's edge on v5e), its second-last a multiple of the
#: sublane tile, which is 8 rows at 32 bits, 16 at bf16 and 32 at int8
_LANES = 128
_SUBLANES_INT8 = 32


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def kernel_block(tile) -> tuple[int, int, int]:
    """The Pallas block ``(bm, bn, bk)`` that the tiled GEMM kernels run a
    job tile ``(ts_m, ts_n, ts_k)`` (or one int edge) as.

    The job tile stays the unit of splitting and accounting (the paper's
    TS=32); the block is what the chip can tile: rows round up to a
    multiple of 32, which is legal for every operand dtype, and the
    output columns and the contraction round up to a multiple of 128."""
    ts_m, ts_n, ts_k = tile if isinstance(tile, tuple) else (tile,) * 3
    return (_round_up(ts_m, _SUBLANES_INT8), _round_up(ts_n, _LANES),
            _round_up(ts_k, _LANES))
