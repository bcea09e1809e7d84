"""``synergy_mm`` — the composable tiled-MM operator (paper C1/C2).

Every dense GEMM in the framework is routed through :func:`synergy_matmul`.
It does three things:

  1. Registers the GEMM's :class:`~repro.core.job.JobSet` with the active
     :class:`SynergyTrace` (trace-time metadata: the job decomposition the
     schedulers, cost model, and roofline analysis operate on).
  2. Executes: under an active :func:`repro.soc.runtime_scope` the JobSet's
     tile jobs are SPLIT across the live engine pool and merged (work
     stealing balances the split; an ``engine=`` pin is demoted to a
     queue-affinity hint).  Otherwise it asks the
     :class:`~repro.engines.Dispatcher` for the best-capable registered
     :class:`~repro.engines.Engine` (XLA dot on CPU dry-runs, the Pallas
     ``tiled_mm`` kernel on TPU, or whatever the user registered) and runs
     the whole GEMM there.  The old ``impl='auto'|'xla'|'pallas'`` strings
     survive only as a deprecation shim over the engine lookup.
  3. Records per-engine telemetry (jobs, estimated busy seconds, bytes
     moved) on both the engine and the active trace.

The job abstraction is exactly the paper's: one job == one output tile of C,
zero-padded at borders so a single fixed-size engine serves every layer of
every network ("network-agnostic accelerators").

Telemetry semantics: ``synergy_matmul`` runs at JAX trace time, so counters
advance once per traced GEMM (per compilation), mirroring what
``SynergyTrace`` has always recorded — the static job decomposition, not
per-step execution counts.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import warnings
from typing import Callable, Optional, Union

import jax

from repro.engines import (CAP_GRAD, Engine, Telemetry, current_scope_engine,
                           dispatch_gemm, under_grad_trace)

from .job import JobSet

__all__ = ["SynergyTrace", "synergy_matmul", "current_trace", "DEFAULT_TILE"]

# MXU-aligned default tile for the TPU target; the paper-faithful TS=32
# baseline is exercised in benchmarks/EXPERIMENTS §Perf.
DEFAULT_TILE = (256, 256, 256)

_state = threading.local()

#: deprecation shim: legacy ``impl`` strings -> registered engine names
_IMPL_TO_ENGINE = {"auto": None, "xla": "xla", "pallas": "pallas"}


@dataclasses.dataclass
class SynergyTrace:
    """Collects the JobSets of every GEMM traced under this context, plus
    the per-engine telemetry of where the dispatcher routed them."""

    jobsets: list[JobSet] = dataclasses.field(default_factory=list)
    engine_stats: dict[str, Telemetry] = dataclasses.field(
        default_factory=dict)
    _next_layer_id: int = 0

    def add(self, m: int, n: int, k: int, tile, name: str) -> JobSet:
        js = JobSet.for_gemm(self._next_layer_id, m, n, k, tile, name=name)
        self._next_layer_id += 1
        self.jobsets.append(js)
        return js

    def record_engine(self, engine_name: str, js: JobSet,
                      est_s: float) -> None:
        self.engine_stats.setdefault(engine_name, Telemetry()).record(js,
                                                                      est_s)

    def record_runtime(self, accounting: dict) -> None:
        """Book a SynergyRuntime submission's per-engine shares: the split
        GEMM's jobs land on every engine that actually executed part of it
        (stolen jobs included), on the same cost-model busy basis.  The
        gemm itself counts ONCE, credited to the dominant executor, so
        ``sum(gemms) == len(jobsets)`` holds on both dispatch paths."""
        dominant = (max(accounting, key=lambda n: accounting[n]["jobs"])
                    if accounting else None)
        for name, acct in accounting.items():
            t = self.engine_stats.setdefault(name, Telemetry())
            t.record_jobs(acct["jobs"], acct["est_s"], acct["bytes"],
                          gemms=int(name == dominant),
                          steals=acct["steals"])

    @property
    def total_flops(self) -> int:
        return sum(js.total_flops for js in self.jobsets)

    @property
    def num_jobs(self) -> int:
        return sum(js.num_jobs for js in self.jobsets)

    @contextlib.contextmanager
    def activate(self):
        prev = getattr(_state, "trace", None)
        _state.trace = self
        try:
            yield self
        finally:
            _state.trace = prev


def current_trace() -> Optional[SynergyTrace]:
    return getattr(_state, "trace", None)


def _resolve_impl_shim(impl: Optional[str],
                       engine: Union[str, Engine, None]):
    """Translate the legacy ``impl`` string into an engine lookup."""
    if impl is None:
        return engine
    warnings.warn(
        "synergy_matmul(impl=...) is deprecated; use engine=<registered "
        "engine name> or let the dispatcher pick (repro.engines)",
        DeprecationWarning, stacklevel=3)
    if engine is not None:
        return engine          # explicit engine wins over the legacy string
    try:
        return _IMPL_TO_ENGINE[impl]
    except KeyError:
        return impl            # maybe a registered engine name already


def synergy_matmul(a: jax.Array, b: jax.Array, *,
                   bias: jax.Array | None = None,
                   activation: Callable | None = None,
                   tile: tuple[int, int, int] | int = DEFAULT_TILE,
                   name: str = "",
                   engine: Union[str, Engine, None] = None,
                   impl: str | None = None,
                   job_class: str | None = None,
                   out_dtype=None,
                   precision=None) -> jax.Array:
    """C = act(A @ B + bias) through the Synergy tile-job abstraction.

    a: (..., m, k); b: (k, n).  ``engine``: a registered engine name (or
    instance); None lets the dispatcher rank capable engines by cost model.
    ``job_class``: one of :data:`repro.engines.JOB_CLASSES` ("decode",
    "prefill", "train") applying the precision-routing policy — decode
    prefers registered ``int8`` engines, prefill/train require grad-safe
    full-precision paths.  ``impl`` is the deprecated string spelling of
    the engine choice.
    """
    *lead, m, k = a.shape
    k2, n = b.shape
    assert k == k2, f"contraction mismatch {a.shape} @ {b.shape}"
    engine = _resolve_impl_shim(impl, engine)
    if engine is None:
        engine = current_scope_engine()   # engine_scope() pin, if any

    # grad guard: a GEMM being differentiated may only land on CAP_GRAD
    # engines, whatever the job class said (an int8 pin under jax.grad is
    # a hard error, not a silent zero-gradient).
    require = (CAP_GRAD,) if under_grad_trace(a, b, bias) else ()

    batch = 1
    for d in lead:
        batch *= d
    tr = current_trace()
    if tr is not None:
        js = tr.add(batch * m, n, k, tile, name=name or "gemm")
    else:
        js = JobSet.for_gemm(0, batch * m, n, k, tile, name=name or "gemm")

    # Runtime scope: split this GEMM's tile jobs across the live engine
    # pool and merge partials (work stealing balances the split).  An
    # engine pin becomes a queue-affinity HINT, not a hard route.  Under a
    # jit trace the arrays are Tracers the worker threads cannot touch, so
    # traced call sites keep single-engine dispatch.
    from repro.soc.runtime import current_runtime, is_concrete
    rt = current_runtime()
    if rt is not None and is_concrete(a, b, bias):
        # precision routing under a runtime scope happens INSIDE the
        # split (per-job int8 eligibility + LPT over the pool), so no
        # dispatcher ranking pass is needed here — only an explicit
        # engine pin survives as a queue-affinity hint
        affinity = engine.name if isinstance(engine, Engine) else engine
        a2 = a.reshape(-1, k)
        y, accounting = rt.run_matmul(
            js, a2, b, bias=bias, activation=activation,
            tile=tile if isinstance(tile, tuple) else (tile,) * 3,
            out_dtype=out_dtype, precision=precision, affinity=affinity,
            job_class=job_class)
        if tr is not None:
            tr.record_runtime(accounting)
        return y.reshape(*lead, m, n)

    eng = dispatch_gemm(js, engine=engine, require=require,
                        job_class=job_class)
    est_s = eng.estimate(js)
    eng.telemetry.record(js, est_s)
    if tr is not None:
        tr.record_engine(eng.name, js, est_s)

    a2 = a.reshape(-1, k)
    y = eng.execute(a2, b, bias=bias, activation=activation, tile=tile,
                    out_dtype=out_dtype, precision=precision)
    return y.reshape(*lead, m, n)
