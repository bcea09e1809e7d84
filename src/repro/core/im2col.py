"""im2col: data-layout transformation turning convolution into GEMM (§3.1.1).

The paper follows Caffe/Darknet: flatten each (kh, kw, cin) receptive field
into a row, so ``conv(x, w)`` becomes ``A[m, k] @ B[k, n]`` with

    m = out_h * out_w          (per image)
    k = kh * kw * cin
    n = cout

We keep NHWC layout (TPU-native) rather than Darknet's NCHW.

The A operand is built by ONE jitted program per input shape and conv
geometry (:func:`im2col_patches`): padding, the patch extraction and the
final reshape run as one dispatch, with no eager intermediates.
Process-wide counts of the operands built, by path, and of the program's
builds are kept for the metrics registry (:func:`im2col_counts`).
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

_COUNTS = {"patches": 0, "view": 0, "programs": 0}
_COUNTS_LOCK = threading.Lock()


def _count(key: str) -> None:
    with _COUNTS_LOCK:
        _COUNTS[key] += 1


def im2col_counts() -> dict:
    """``{"patches", "view", "programs"}``: A operands built by the patch
    program, 1x1 operands read without a gather, and builds (traces) of
    the patch program, one per input shape and geometry.  Once every
    shape has run, ``programs`` stays put."""
    with _COUNTS_LOCK:
        return dict(_COUNTS)


def conv_out_shape(h: int, w: int, kh: int, kw: int, stride: int,
                   padding: int) -> tuple[int, int]:
    return ((h + 2 * padding - kh) // stride + 1,
            (w + 2 * padding - kw) // stride + 1)


#: TPU vector lane count: a strided slice of fewer channels than this is
#: mostly lane padding
_LANES = 128


@functools.partial(jax.jit, static_argnames=("kh", "kw", "stride",
                                             "padding"))
def im2col_patches(x: jax.Array, kh: int, kw: int, stride: int = 1,
                   padding: int = 0) -> jax.Array:
    """x: (N, H, W, C) -> the GEMM operand (N*OH*OW, KH*KW*C), its columns
    in the (kh, kw, c) order of ``w.reshape(-1, cout)``.

    The KH*KW strided slices of the padded input, side by side along the
    channel axis; except where the slices stride over fewer channels than
    a lane row (ResNet's 7x7/2 stem on RGB: 42 ms against 6 ms a call for
    64 frames on a TPU v5e), which gathers the windows by index instead.
    Both forms give the same array bit for bit."""
    _count("programs")
    n, h, w, c = x.shape
    oh, ow = conv_out_shape(h, w, kh, kw, stride, padding)
    if padding:
        x = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding),
                        (0, 0)))
    if stride > 1 and c < _LANES:
        rows = (np.arange(oh) * stride)[:, None] + np.arange(kh)
        cols = (np.arange(ow) * stride)[:, None] + np.arange(kw)
        patches = x[:, rows][:, :, :, cols]    # (N, OH, KH, OW, KW, C)
        a = patches.transpose(0, 1, 3, 2, 4, 5)
    else:
        a = jnp.concatenate(
            [jax.lax.slice(x, (0, i, j, 0),
                           (n, i + stride * (oh - 1) + 1,
                            j + stride * (ow - 1) + 1, c),
                           (1, stride, stride, 1))
             for i in range(kh) for j in range(kw)], axis=-1)
    return a.reshape(n * oh * ow, kh * kw * c)


def im2col_operand(x: jax.Array, kh: int, kw: int, stride: int = 1,
                   padding: int = 0) -> jax.Array:
    """The (N*OH*OW, KH*KW*C) A operand of a patch-gathered convolution,
    a concrete array when ``x`` is one."""
    _count("patches")
    return im2col_patches(x, kh, kw, stride, padding)


def view_operand(x: jax.Array, stride: int = 1) -> jax.Array:
    """The A operand of a 1x1 convolution without padding: the (strided)
    input read as (N*OH*OW, C), no gather."""
    _count("view")
    xs = x if stride == 1 else x[:, ::stride, ::stride, :]
    return xs.reshape(-1, x.shape[-1])


def im2col(x: jax.Array, kh: int, kw: int, stride: int = 1,
           padding: int = 0) -> jax.Array:
    """x: (N, H, W, C) -> patches (N, OH*OW, KH*KW*C)."""
    a = im2col_operand(x, kh, kw, stride, padding)
    return a.reshape(x.shape[0], -1, a.shape[1])


def im2col_wave(x: jax.Array, kh: int, kw: int, stride: int = 1,
                padding: int = 0) -> jax.Array:
    """Batched multi-image im2col for a serving admission wave.

    x: (N, H, W, C) — ALL frames of the wave stacked along the batch axis
    (every admitted request's frames together) — returns the flattened
    (N*OH*OW, KH*KW*C) GEMM activation panel in one call.  The point is
    the amortization: ONE dispatch of the patch program covers the whole
    wave, instead of one per request; the panel feeds a single batched
    conv GEMM whose row-panel split the runtime then spreads across the
    pool."""
    return im2col_operand(x, kh, kw, stride, padding)


def conv2d_gemm(x: jax.Array, w: jax.Array, stride: int = 1, padding: int = 0,
                matmul=None) -> jax.Array:
    """Convolution via im2col + GEMM (the Synergy CONV path).

    x: (N, H, W, Cin); w: (KH, KW, Cin, Cout) -> (N, OH, OW, Cout).
    ``matmul`` lets callers route the GEMM through ``synergy_mm`` (tile jobs);
    defaults to jnp.matmul.
    """
    kh, kw, cin, cout = w.shape
    n, h, wd, _ = x.shape
    oh, ow = conv_out_shape(h, wd, kh, kw, stride, padding)
    a = im2col_operand(x, kh, kw, stride, padding)  # (N*OH*OW, K)
    b = w.reshape(kh * kw * cin, cout)              # (K, Cout)
    mm = matmul if matmul is not None else jnp.matmul
    out = mm(a, b)                                  # (N*OH*OW, Cout)
    return out.reshape(n, oh, ow, cout)
