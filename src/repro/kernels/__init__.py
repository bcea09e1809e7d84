"""Pallas kernels for the framework's hot spots (tiled GEMM, its VPU-only
and int8 variants, flash attention, the Mamba2 SSD scan)."""

__all__ = ["GEMM_VMEM_LIMIT", "gemm_block", "kernel_block"]

#: the TPU's vector tiling: a block's last dim is a multiple of the lane
#: width (also the MXU's edge on v5e), its second-last a multiple of the
#: sublane tile, which is 8 rows at 32 bits, 16 at bf16 and 32 at int8
_LANES = 128
_SUBLANES_INT8 = 32


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def kernel_block(tile) -> tuple[int, int, int]:
    """The smallest Pallas block ``(bm, bn, bk)`` that holds a job tile
    ``(ts_m, ts_n, ts_k)`` (or one int edge) as the chip can tile it.

    The job tile stays the unit of splitting and accounting (the paper's
    TS=32); rows round up to a multiple of 32, which is legal for every
    operand dtype, and the output columns and the contraction round up to
    a multiple of 128.  ``vpu_mm`` and ``qmm`` run this block;
    :func:`gemm_block` grows it for ``tiled_mm``, so that one grid cell
    holds many job tiles."""
    ts_m, ts_n, ts_k = tile if isinstance(tile, tuple) else (tile,) * 3
    return (_round_up(ts_m, _SUBLANES_INT8), _round_up(ts_n, _LANES),
            _round_up(ts_k, _LANES))


#: the scoped VMEM the tiled GEMM kernel asks for (v5e has 128 MiB, the
#: default scope is 16 MiB), and the part of it its blocks may take; the
#: rest is left to Mosaic's own temporaries
GEMM_VMEM_LIMIT = 64 << 20
_GEMM_VMEM_BUDGET = 48 << 20
#: what a grid step costs besides its data, in HBM bytes: about 0.3 us of
#: pipeline work per step, at v5e's 819 GB/s
_STEP_BYTES = 256 << 10


def _gemm_vmem(bm: int, bn: int, bk: int, itemsize: int) -> int:
    """VMEM of a block as the chip lays it out: two buffers each of the A
    and B blocks, and four f32 (bm, bn) tiles (two output buffers, the
    accumulator, the dot's result)."""
    sub = _SUBLANES_INT8 // itemsize
    a = _round_up(bm, sub) * _round_up(bk, _LANES) * itemsize
    b = _round_up(bk, sub) * _round_up(bn, _LANES) * itemsize
    return 2 * (a + b) + 4 * _round_up(bm, 8) * _round_up(bn, _LANES) * 4


def _gemm_cost(m: int, k: int, n: int, bm: int, bn: int, bk: int,
               itemsize: int) -> int:
    """HBM bytes a block moves through the grid (i, j, k), plus the
    pipeline's first fetch and last store, which nothing hides, plus each
    step's fixed cost.  A block is fetched again only when its index
    changes: A once per row of blocks where the contraction is one block,
    B once in all where it is the whole of B."""
    gm, gn, gk = -(-m // bm), -(-n // bn), -(-k // bk)
    kp, np_ = gk * bk, gn * bn
    a_reads = 1 if gk == 1 else gn
    b_reads = 1 if gk == gn == 1 else gm
    moved = itemsize * (m * kp * a_reads + kp * np_ * b_reads) + 4 * m * np_
    fill = itemsize * (bm * bk + bk * bn) + 4 * bm * bn
    return moved + fill + gm * gn * gk * _STEP_BYTES


def _edges(d: int, lo: int) -> list[int]:
    """Block edges along a GEMM dimension of size ``d`` that need no
    padding: ``d`` itself, and the multiples of 128, at least ``lo``,
    that divide it."""
    return [d] + [e for e in range(lo, d, _LANES) if d % e == 0]


def gemm_block(m: int, k: int, n: int, itemsize: int,
               tile) -> tuple[int, int, int]:
    """The Pallas block ``(bm, bn, bk)`` that ``tiled_mm`` runs an
    (m, k) @ (k, n) GEMM with operands of ``itemsize`` bytes as: of the
    blocks at least :func:`kernel_block` of the job ``tile`` that fit the
    VMEM budget, the one that moves the fewest HBM bytes
    (:func:`_gemm_cost`).

    K and N take the whole dimension or a multiple of 128 that divides
    it; M takes the whole dimension or a power-of-two multiple of the
    tile's rows, and its last block may be ragged (rows are
    independent).  Only where no such block fits do K and N take a
    multiple of 128 that the caller zero-pads them to."""
    bm0, bn0, bk0 = kernel_block(tile)
    bms = [m] + [bm0 << i for i in range(m.bit_length()) if bm0 << i < m]
    ks, ns = _edges(k, bk0), _edges(n, bn0)
    for pad in (False, True):
        if pad:
            ks += _edges(_round_up(k, bk0), bk0)
            ns += _edges(_round_up(n, bn0), bn0)
        fits = [(bm, bn, bk) for bm in bms for bn in ns for bk in ks
                if _gemm_vmem(bm, bn, bk, itemsize) <= _GEMM_VMEM_BUDGET]
        if fits:
            return min(fits, key=lambda blk: _gemm_cost(m, k, n, *blk,
                                                         itemsize))
    raise ValueError(f"no block of ({m}, {k}) @ ({k}, {n}) fits VMEM")
