"""The expected result of a GEMM that ``SynergyRuntime.submit_gemm`` splits
into row panels.

Each panel is its own dot, and a dot's rounding can depend on its row count
(XLA's CPU dot blocks its contraction by it), so a split is compared
bitwise against the same panels, not against one unsplit dot."""

import jax.numpy as jnp

from repro.soc.runtime import row_panels


def panel_reference(engine, a, b, ts_m, **kw):
    """``engine.execute`` at fp32 on each :func:`row_panels` row range of
    ``a``, concatenated and cast to ``a``'s dtype: what ``submit_gemm``
    merges when every panel runs on an engine with ``engine``'s math."""
    parts = [engine.execute(a[r0:r1], b, out_dtype=jnp.float32, **kw)
             for r0, r1 in row_panels(a.shape[0], *b.shape, ts_m)]
    return jnp.concatenate(parts, 0).astype(a.dtype)
