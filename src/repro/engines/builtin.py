"""Built-in GEMM engines: XLA dot, Pallas tiled kernel, jnp oracle.

These are the three execution backends the seed's ``impl`` strings used to
pick by hand; now they are ordinary registry entries ranked by their cost
models.  Rates are deliberately coarse — they only need to order the
engines correctly per backend (XLA wins on CPU where Pallas runs in
interpret mode; the Pallas MXU kernel wins on TPU; the oracle never wins).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from .base import (CAP_EPILOGUE, CAP_GEMM, CAP_GRAD, CAP_INTERPRET,
                   CAP_ORACLE, CAP_TILED, CostModel, Engine)

__all__ = ["XlaEngine", "PallasTiledEngine", "ReferenceEngine"]


def _epilogue(y: jax.Array, bias, activation) -> jax.Array:
    if bias is not None:
        y = y + bias
    if activation is not None:
        y = activation(y)
    return y


class XlaEngine(Engine):
    """Canonical ``lax.dot_general`` — the CPU / dry-run path (keeps the
    512-device dry-run HLO clean so ``cost_analysis`` sees canonical dots).
    Handles storage dtype != compute dtype (int8 weight-only quant for
    decode, §Perf B1): dequant-on-read, accumulate in f32."""

    #: coarse sustained MAC rates used only to RANK engines per backend
    _RATES = {"tpu": 60e12, "gpu": 30e12, "cpu": 2e9}

    def __init__(self, name: str = "xla"):
        super().__init__(name, {CAP_GEMM, CAP_EPILOGUE, CAP_GRAD})

    @property
    def cost(self) -> CostModel:
        if self._cost is not None:       # steal-aware recalibration applied
            return self._cost
        return CostModel(self._RATES.get(jax.default_backend(), 2e9))

    def execute(self, a, b, *, bias=None, activation: Callable | None = None,
                tile=(256, 256, 256), out_dtype=None, precision=None):
        if b.dtype != a.dtype:
            b = b.astype(a.dtype)
        y = jax.lax.dot_general(
            a, b, (((a.ndim - 1,), (0,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32)
        y = _epilogue(y, bias, activation)
        return y.astype(out_dtype or a.dtype)


class PallasTiledEngine(Engine):
    """The Pallas ``tiled_mm`` kernel — the TPU-native Synergy PE (a grid
    of blocks sized by the GEMM's shape, each holding many job tiles, VMEM
    double buffering, fused epilogue).  Interpret-mode
    capable: explicitly requesting it off-TPU runs the kernel through the
    Pallas interpreter (validation path)."""

    def __init__(self, name: str = "pallas", *, interpret: bool = False):
        super().__init__(name, {CAP_GEMM, CAP_EPILOGUE, CAP_TILED,
                                CAP_INTERPRET})
        self.interpret = interpret

    @property
    def cost(self) -> CostModel:
        if self._cost is not None:       # steal-aware recalibration applied
            return self._cost
        if jax.default_backend() == "tpu":
            return CostModel(90e12)
        return CostModel(2e6)   # interpreter: auto-dispatch never picks it

    def execute(self, a, b, *, bias=None, activation: Callable | None = None,
                tile=(256, 256, 256), out_dtype=None, precision=None):
        from repro.kernels.tiled_mm import ops as tiled_ops
        if b.dtype != a.dtype:
            b = b.astype(a.dtype)
        return tiled_ops.tiled_matmul(a, b, tile=tile, bias=bias,
                                      activation=activation,
                                      out_dtype=out_dtype,
                                      interpret=self.interpret)


class ReferenceEngine(Engine):
    """Pure-jnp fp32 oracle — correctness baseline, never speed-ranked."""

    def __init__(self, name: str = "reference"):
        super().__init__(name, {CAP_GEMM, CAP_EPILOGUE, CAP_GRAD, CAP_ORACLE},
                         cost=CostModel(5e7))

    def execute(self, a, b, *, bias=None, activation: Callable | None = None,
                tile=(256, 256, 256), out_dtype=None, precision=None):
        from repro.kernels.tiled_mm.ref import tiled_mm_ref
        return tiled_mm_ref(a, b, bias=bias, activation=activation,
                            out_dtype=out_dtype)
