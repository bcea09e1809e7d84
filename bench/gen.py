"""The one traffic generator: reads a mix's parameters from
``traffic/<name>.json`` and makes its inputs from the seed.

A frame mix (``closed_loop_frames``) is one client calling on a bank of
``bank_calls`` calls of ``frames_per_call`` frames, in turn.  Every seed
gets the same sizes; the seed draws the frames.  So runs with different
seeds do the same work, and the spread of a metric across seeds is the
system's, not the traffic's."""

from __future__ import annotations

import numpy as np

KINDS = ("closed_loop_frames",)


def frame_bank(traffic: dict, seed: int, shape: tuple[int, int, int]):
    """``bank_calls`` calls of ``frames_per_call`` frames of ``shape``
    (H, W, C), made on the device from the seed in one jitted call."""
    import jax
    import jax.numpy as jnp

    from .core import seed_key
    if traffic["kind"] != "closed_loop_frames":
        raise ValueError(f"not a frame mix: {traffic['kind']!r}")
    dims = (int(traffic["bank_calls"]), int(traffic["frames_per_call"]),
            *shape)
    make = jax.jit(lambda k: jax.random.normal(k, dims, jnp.float32))
    return make(jax.random.fold_in(seed_key(seed), 0xF4A3))


def sample_indices(seed: int, n: int, k: int, salt: int) -> list[int]:
    """``k`` of ``n`` indices drawn from the seed (all where k >= n)."""
    rng = np.random.default_rng([seed, salt])
    if k >= n:
        return list(range(n))
    return sorted(int(i) for i in rng.choice(n, size=k, replace=False))
