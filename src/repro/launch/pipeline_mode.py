"""Pipeline-parallel launch mode: the Synergy inter-frame pipeline at POD
granularity (DESIGN §4 'PP over pod').

The multi-pod mesh's inter-pod links are the slowest fabric; a GPipe
microbatch pipeline keeps that traffic point-to-point (ppermute ring) —
the same communication-pattern argument the paper makes for pipelining
across heterogeneous interconnect.  Stages = contiguous layer groups; each
pod holds one stage's parameters; microbatches stream through
``repro.core.pipeline.gpipe_spmd``.

Demonstrated for the dense family (block stacks split evenly across the
stage axis); validated against the sequential reference in
tests/test_sharding_dryrun.py::test_pp_mode_matches_sequential.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.pipeline import gpipe_spmd
from repro.models.transformer import _attn_block_fwd

__all__ = ["split_stages", "build_pp_forward"]


def split_stages(params: dict, num_stages: int) -> dict:
    """Reshape the stacked (L, ...) block params to (S, L/S, ...)."""
    blocks = params["blocks"]
    return jax.tree.map(
        lambda a: a.reshape((num_stages, a.shape[0] // num_stages)
                            + a.shape[1:]), blocks)


def build_pp_forward(cfg: ArchConfig, mesh, *, stage_axis: str = "pod",
                     microbatches: int = 8):
    """Returns a jitted pipelined backbone forward:
    fn(staged_blocks, embeds (M*mb_sz, S, d)) -> activations, with stages
    mapped onto the ``stage_axis`` of the mesh via shard_map."""
    num_stages = mesh.shape[stage_axis]
    assert cfg.n_layers % num_stages == 0

    def stage_fn(stage_blocks, x):
        def body(h, p):
            return _attn_block_fwd(cfg, p, h), None
        h, _ = jax.lax.scan(body, x, stage_blocks)
        return h

    def pipelined(staged_blocks, mbs):
        my_blocks = jax.tree.map(lambda a: a[0], staged_blocks)
        return gpipe_spmd(stage_fn, my_blocks, mbs,
                          axis_name=stage_axis, num_stages=num_stages)

    f = jax.shard_map(pipelined, mesh=mesh,
                      in_specs=(P(stage_axis), P()),
                      out_specs=P(stage_axis))
    return jax.jit(f), num_stages
