"""Inter-frame pipelining (paper C4) at two scales.

1. :class:`ThreadedPipeline` — the faithful reproduction of the paper's
   HW/SW multi-threaded pipeline: one thread per layer/stage, mailbox
   (bounded synchronized FIFO) between stages, multiple frames in flight.
   Used by the CNN inference example and the utilization benchmarks
   (paper Table 6).

2. :func:`gpipe_spmd` — the pod-scale adaptation: GPipe-style microbatch
   pipeline across a mesh axis inside ``shard_map``.  Stages map to pods;
   activations move with ``jax.lax.ppermute`` (point-to-point on the slow
   inter-pod ICI links — the same communication-pattern argument the paper
   makes for pipelining across heterogeneous fabric).  ``gpipe_reference``
   is the pure-software oracle used by the tests.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp

__all__ = ["ThreadedPipeline", "EngineStage", "StageStats",
           "PipelineStageError", "gpipe_reference", "gpipe_spmd"]


# ---------------------------------------------------------------------------
# 1. Faithful: threaded layer pipeline with mailboxes
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StageStats:
    name: str
    busy_s: float = 0.0
    frames: int = 0
    engine: Optional[str] = None


@dataclasses.dataclass
class EngineStage:
    """A pipeline stage bound to the engine registry.

    ``fn`` processes one frame's payload; ``engine`` (optional) pins the
    stage's GEMMs to a registered engine — the worker runs ``fn`` under
    ``repro.engines.engine_scope``, so every ``synergy_matmul`` traced
    inside routes there (already-jitted fns keep the routing of their
    first trace), and the stage is attributed in the run stats.
    :meth:`gemm` builds the common case — a stage that IS one dense GEMM —
    directly on ``synergy_matmul``, so stage compute flows through the
    same dispatch surface as everything else."""

    name: str
    fn: Callable[[Any], Any]
    engine: Optional[str] = None

    @classmethod
    def gemm(cls, name: str, w, *, bias=None, activation=None,
             tile=None, engine: Optional[str] = None) -> "EngineStage":
        from .synergy_mm import DEFAULT_TILE, synergy_matmul
        tile = tile if tile is not None else DEFAULT_TILE

        def fn(a):
            return synergy_matmul(a, w, bias=bias, activation=activation,
                                  tile=tile, name=name, engine=engine)
        return cls(name, fn, engine)

    def __call__(self, payload):
        return self.fn(payload)


def _as_stage(spec: Union["EngineStage", tuple]) -> EngineStage:
    if isinstance(spec, EngineStage):
        return spec
    name, fn = spec
    return EngineStage(name, fn)


_STOP = object()


@dataclasses.dataclass
class _Failure:
    """A stage exception, traveling the pipe in place of the frame so every
    downstream mailbox keeps draining (no deadlock)."""

    stage: str
    error: BaseException


class PipelineStageError(RuntimeError):
    """Raised by :meth:`ThreadedPipeline.run` when a stage raised; the
    original exception is chained as ``__cause__``."""


class ThreadedPipeline:
    """Producer/consumer layer pipeline (paper §3.1, Figure 2).

    stages: list of :class:`EngineStage` or (name, fn) tuples — fn
    processes one frame's payload.  mailbox_capacity bounds frames in
    flight between adjacent stages.

    ``runtime``: an optional :class:`repro.soc.SynergyRuntime` — stage
    workers run under its :func:`~repro.soc.runtime_scope`, so stage GEMMs
    split across the engine pool and an ``EngineStage.engine`` pin becomes
    a queue-affinity hint rather than a hard route.  When None, a runtime
    scope active in the caller's thread at :meth:`run` time is inherited.

    A raising stage does NOT deadlock the pipe: the exception travels
    downstream as a poison frame, every worker keeps draining its inbox,
    and :meth:`run` re-raises :class:`PipelineStageError` after joining.
    """

    def __init__(self,
                 stages: Sequence[Union[EngineStage,
                                        tuple[str, Callable[[Any], Any]]]],
                 mailbox_capacity: int = 4,
                 runtime: Optional[Any] = None):
        self.stages = [_as_stage(s) for s in stages]
        self.mailboxes = [queue.Queue(maxsize=mailbox_capacity)
                          for _ in range(len(self.stages) + 1)]
        self.stats = [StageStats(s.name, engine=s.engine)
                      for s in self.stages]
        self.runtime = runtime

    def _worker(self, idx: int, runtime) -> None:
        import contextlib

        from repro.engines import engine_scope
        stage = self.stages[idx]
        fn = stage.fn
        if stage.engine is not None:
            raw = fn

            def fn(item):
                with engine_scope(stage.engine):
                    return raw(item)
        inbox, outbox = self.mailboxes[idx], self.mailboxes[idx + 1]
        st = self.stats[idx]
        if runtime is not None:
            from repro.soc import runtime_scope
            scope = runtime_scope(runtime)
        else:
            scope = contextlib.nullcontext()
        with scope:
            while True:
                item = inbox.get()
                if item is _STOP:
                    outbox.put(_STOP)
                    return
                if isinstance(item, _Failure):   # pass the poison through
                    outbox.put(item)
                    continue
                t0 = time.perf_counter()
                try:
                    out = fn(item)
                except BaseException as e:
                    out = _Failure(stage.name, e)
                st.busy_s += time.perf_counter() - t0
                st.frames += 1
                outbox.put(out)

    def run(self, frames: Sequence[Any]) -> tuple[list[Any], dict]:
        runtime = self.runtime
        if runtime is None:
            from repro.soc import current_runtime
            runtime = current_runtime()
        threads = [threading.Thread(target=self._worker, args=(i, runtime),
                                    daemon=True)
                   for i in range(len(self.stages))]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        feeder = threading.Thread(
            target=lambda: ([self.mailboxes[0].put(f) for f in frames],
                            self.mailboxes[0].put(_STOP)),
            daemon=True)
        feeder.start()
        outputs = []
        failure: Optional[_Failure] = None
        while True:
            item = self.mailboxes[-1].get()
            if item is _STOP:
                break
            if isinstance(item, _Failure):
                failure = failure or item       # keep draining to _STOP
                continue
            outputs.append(item)
        wall = time.perf_counter() - t0
        for t in threads:
            t.join()
        feeder.join()
        if failure is not None:
            raise PipelineStageError(
                f"stage {failure.stage!r} raised "
                f"{type(failure.error).__name__}: {failure.error}"
            ) from failure.error
        util = {s.name: (s.busy_s / wall if wall > 0 else 0.0) for s in self.stats}
        return outputs, {
            "wall_s": wall,
            "fps": len(outputs) / wall if wall > 0 else 0.0,
            "stage_utilization": util,
            "stage_engines": {s.name: s.engine for s in self.stats
                              if s.engine is not None},
            "runtime": runtime.stats() if runtime is not None else None,
        }


# ---------------------------------------------------------------------------
# 2. Pod-scale: GPipe microbatch pipeline under shard_map
# ---------------------------------------------------------------------------

def gpipe_reference(stage_fn: Callable[[Any, jax.Array], jax.Array],
                    stage_params: Sequence[Any],
                    microbatches: jax.Array) -> jax.Array:
    """Oracle: apply stages sequentially to each microbatch.

    stage_params: length-S list of per-stage params; microbatches: (M, ...).
    """
    def per_mb(x):
        for p in stage_params:
            x = stage_fn(p, x)
        return x
    return jax.vmap(per_mb)(microbatches)


def gpipe_spmd(stage_fn: Callable[[Any, jax.Array], jax.Array],
               my_params: Any,
               microbatches: jax.Array,
               *,
               axis_name: str,
               num_stages: int) -> jax.Array:
    """GPipe forward pipeline, called INSIDE shard_map.

    Each device along ``axis_name`` holds one stage's params (``my_params``)
    and the full microbatch stream (M, ...) enters at stage 0.  The schedule
    runs M + S - 1 ticks; at each tick every stage processes its current
    microbatch and ppermutes the activation to the next stage, overlapping
    per-tick compute with the point-to-point transfer (XLA schedules the
    ppermute async against the next tick's compute).

    Returns the (M, ...) outputs, valid on the LAST stage (stage < S-1
    devices return zeros) — callers typically ppermute/psum the result back.
    """
    stage = jax.lax.axis_index(axis_name)
    m = microbatches.shape[0]
    ticks = m + num_stages - 1
    x_shape = microbatches.shape[1:]

    def tick(carry, t):
        state, outputs = carry      # state: activation entering this stage
        # stage 0 injects microbatch t (if within range)
        inject = jnp.where(t < m, t, m - 1)
        x0 = microbatches[inject]
        x_in = jnp.where(stage == 0, x0, state)
        y = stage_fn(my_params, x_in)
        # collect finished microbatch on the last stage (masked write — a
        # lax.cond here would give the branches different varying-axis
        # types under shard_map)
        out_idx = t - (num_stages - 1)
        valid = (stage == num_stages - 1) & (out_idx >= 0) & (out_idx < m)
        updated = outputs.at[jnp.clip(out_idx, 0, m - 1)].set(y)
        outputs = jnp.where(valid, updated, outputs)
        # shift activations stage i -> i+1 (ring permute; last->first unused)
        perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
        state = jax.lax.ppermute(y, axis_name, perm)
        return (state, outputs), None

    # stages must preserve activation shape (residual-block property), so the
    # output stream has the input microbatch shape.
    outputs0 = jnp.zeros((m,) + x_shape, dtype=microbatches.dtype)
    state0 = jnp.zeros(x_shape, dtype=microbatches.dtype)
    # the loop body makes the carry vary over the stage axis (ppermute /
    # axis_index); mark the initial carry varying so scan types match
    state0 = jax.lax.pcast(state0, (axis_name,), to="varying")
    outputs0 = jax.lax.pcast(outputs0, (axis_name,), to="varying")
    (_, outputs), _ = jax.lax.scan(tick, (state0, outputs0),
                                   jnp.arange(ticks))
    return outputs
