"""The Dispatcher: route each GEMM's JobSet to the best-capable engine.

The dispatch rule is the paper's scheduling insight at engine granularity:
filter by capability, rank by the shared cost model, run on the winner.
``synergy_matmul`` consults the default dispatcher for every dense GEMM in
the framework, so registering a faster engine reroutes all work with zero
call-site edits.
"""

from __future__ import annotations

import contextlib
import dataclasses
import sys
import threading
from typing import Iterable, Iterator, Optional, Union

import jax

from repro.obs import trace as _trace

from .base import CAP_GEMM, CAP_GRAD, CAP_INT8, CAP_SIM, Engine
from .registry import get_engine, list_engines

__all__ = ["Dispatcher", "DEFAULT_DISPATCHER", "dispatch_gemm",
           "engine_scope", "current_scope_engine",
           "JobClassPolicy", "JOB_CLASSES", "under_grad_trace"]

_scope = threading.local()


@contextlib.contextmanager
def engine_scope(engine: Union[str, Engine, None]) -> Iterator[None]:
    """Pin every auto-dispatched GEMM in this thread to ``engine`` for the
    duration of the block (trace-time routing: code already jit-compiled
    outside the scope keeps its original routing).  ``None`` restores
    dispatcher auto-selection; scopes nest."""
    prev = getattr(_scope, "engine", None)
    _scope.engine = engine
    try:
        yield
    finally:
        _scope.engine = prev


def current_scope_engine() -> Union[str, Engine, None]:
    return getattr(_scope, "engine", None)


#: jax's forward-mode AD entry points: every differentiation API
#: (grad/vjp/jvp/linearize) funnels the callee's trace through one of
#: these frames in jax/_src/interpreters/ad.py
_AD_FRAME_NAMES = frozenset({"jvpfun", "jvp_subtrace", "linearize", "jvp"})


def _ad_machinery_on_stack() -> bool:
    """The pjit-jvp detection: ``grad(jit(f))`` differentiates the
    *jaxpr* of ``f``, so inside ``f`` only jit tracers are visible — the
    tracer walk in :func:`under_grad_trace` cannot see the outer JVP
    trace.  But the TRACING of ``f`` still happens while the ad
    machinery's Python frames are live (pjit traces its callee from
    inside ``ad.jvpfun`` when the caller is differentiating), so walking
    the interpreter stack for those frames closes the gap.  Only runs at
    trace time (operands already known to be Tracers), so the walk costs
    nothing per executed step.

    Remaining limitation: a jaxpr traced OUTSIDE any grad context and
    later differentiated (``g = jit(f); g(x); grad(g)(x)`` reuses the
    cached trace) is routed before differentiation is known — such call
    sites should still pass ``job_class='train'``."""
    fr = sys._getframe(1)
    while fr is not None:
        code = fr.f_code
        if (code.co_name in _AD_FRAME_NAMES
                and code.co_filename.endswith("interpreters/ad.py")):
            return True
        fr = fr.f_back
    return False


def under_grad_trace(*arrays) -> bool:
    """True when any operand is being traced for differentiation (JVP
    tracers — ``jax.grad``/``vjp``/``jvp``/``linearize`` all route through
    forward mode), or when a jit trace is being built FOR differentiation
    (``grad(jit(f))`` — see :func:`_ad_machinery_on_stack`).  This is the
    dispatch-level guard that keeps CAP_GRAD-free engines (int8
    quantized: round/clip kill the weight gradient; Pallas kernels
    without a VJP rule) off differentiated GEMMs even when no call site
    asked for grad-safety explicitly, and that ``resolve_op(...,
    grad=True)`` applies to op variants."""
    traced = False
    pending = [x for x in arrays if x is not None]
    while pending:
        x = pending.pop()
        if not isinstance(x, jax.core.Tracer):
            continue
        traced = True
        names = (type(x).__name__, type(getattr(x, "_trace", x)).__name__)
        if any("jvp" in n.lower() for n in names):
            return True
        # descend through wrapping tracers: JVP carries primal/tangent,
        # vmap's BatchTracer wraps its inner (possibly JVP) tracer in .val
        for attr in ("primal", "tangent", "val"):
            sub = getattr(x, attr, None)
            if sub is not None:
                pending.append(sub)
    return traced and _ad_machinery_on_stack()


@dataclasses.dataclass(frozen=True)
class JobClassPolicy:
    """Precision-routing policy for one job class.

    ``require``: hard capability filter (candidates lacking any are out).
    ``prefer``:  soft filter — if any candidate advertises every preferred
    capability, selection ranks only those; otherwise it falls back to the
    full candidate set (a pool with no int8 engine still serves decode).
    """

    require: frozenset = frozenset()
    prefer: frozenset = frozenset()


#: the precision-routing table (paper §3 job classes, serving-era names):
#: decode steps are small, memory-bound and error-tolerant — trade
#: precision for rate when an int8 engine is registered.  Prefill feeds
#: the KV cache every later token reads, and training differentiates the
#: GEMM, so both are pinned to grad-safe full-precision paths.  NOTE:
#: CAP_GRAD is a deliberately conservative full-precision proxy — it also
#: keeps prefill off grad-FREE fp32 kernels (Pallas MXU/VPU engines);
#: deployments that trust those for prefill can relax the table
#: (JOB_CLASSES["prefill"] is plain data, not policy machinery).
JOB_CLASSES: dict[str, JobClassPolicy] = {
    "decode": JobClassPolicy(prefer=frozenset({CAP_INT8})),
    # the serving load-shed ladder's degraded tier: sheddable tenants'
    # decode REQUIRES an int8 engine, freeing the fp32 pool for
    # interactive traffic (see repro.soc.qos)
    "decode_degraded": JobClassPolicy(require=frozenset({CAP_INT8}),
                                      prefer=frozenset({CAP_INT8})),
    "prefill": JobClassPolicy(require=frozenset({CAP_GRAD})),
    "train": JobClassPolicy(require=frozenset({CAP_GRAD})),
}


class Dispatcher:
    """Capability-filtered, cost-ranked engine selection.

    ``require``: capabilities every candidate must advertise.
    ``exclude``: capabilities that disqualify a candidate from AUTO
    selection — simulated PEs (they model a 0.1 GMAC/s Zynq fabric and
    would never win, but excluding them keeps auto-dispatch semantics
    independent of what simulators are registered) and int8 quantized
    engines (their cost models beat fp32 peers, so cost ranking alone
    would silently trade away precision process-wide; a job class that
    prefers or requires ``int8`` lifts the exclusion, and an explicit
    ``engine=`` pin bypasses it entirely).
    """

    def __init__(self, require: Iterable[str] = (CAP_GEMM,),
                 exclude: Iterable[str] = (CAP_SIM, CAP_INT8)):
        self.require = frozenset(require)
        self.exclude = frozenset(exclude)

    def candidates(self, require: Iterable[str] = (),
                   exclude: Optional[frozenset] = None) -> list[Engine]:
        req = self.require | frozenset(require)
        exc = self.exclude if exclude is None else exclude
        return [e for e in list_engines()
                if e.supports(req) and not (e.capabilities & exc)
                and e.available()]

    def select(self, jobset, *, engine: Union[str, Engine, None] = None,
               require: Iterable[str] = (),
               job_class: Optional[str] = None) -> Engine:
        """Pick the engine for one JobSet.

        An explicit ``engine`` (name or instance) bypasses ranking but is
        still capability-checked; otherwise the cheapest capable candidate
        by cost-model estimate wins.  ``job_class`` applies the precision
        routing policy in :data:`JOB_CLASSES`: its ``require`` set becomes
        a hard filter (checked even against an explicit engine), and its
        ``prefer`` set narrows auto-selection when any candidate offers it
        (decode prefers ``int8``; prefill/train require ``grad``)."""
        if job_class is None:
            policy = _NO_POLICY
        else:
            try:
                policy = JOB_CLASSES[job_class]
            except KeyError:
                raise KeyError(
                    f"unknown job class {job_class!r}; known: "
                    f"{sorted(JOB_CLASSES)}") from None
        req = self.require | frozenset(require) | policy.require
        if engine is not None:
            eng = get_engine(engine) if isinstance(engine, str) else engine
            if not eng.supports(req):
                missing = sorted(req - eng.capabilities)
                raise ValueError(f"engine {eng.name!r} lacks required "
                                 f"capabilities {missing}")
            return eng
        # a capability the caller/policy asks for cannot also disqualify
        exc = self.exclude - policy.prefer - req
        cands = self.candidates(req - self.require, exclude=exc)
        if not cands:
            raise RuntimeError(
                f"no registered engine satisfies capabilities {sorted(req)}")
        if policy.prefer:
            preferred = [e for e in cands if policy.prefer <= e.capabilities]
            if preferred:
                cands = preferred
        eng = min(cands, key=lambda e: e.estimate(jobset))
        # one module-attribute check: dispatch decisions show up on traces
        # (process-default tracer only; tracing off = no-op)
        if _trace._default is not None:
            _trace._default.emit(
                "dispatch", eng.name, jobset=getattr(jobset, "name", None),
                job_class=job_class, n_candidates=len(cands))
        return eng


_NO_POLICY = JobClassPolicy()

DEFAULT_DISPATCHER = Dispatcher()


def dispatch_gemm(jobset, *, engine: Union[str, Engine, None] = None,
                  require: Iterable[str] = (),
                  job_class: Optional[str] = None) -> Engine:
    """Module-level shorthand for ``DEFAULT_DISPATCHER.select``."""
    return DEFAULT_DISPATCHER.select(jobset, engine=engine, require=require,
                                     job_class=job_class)
