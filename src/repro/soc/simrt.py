"""SimRuntime — a virtual-time replay of one JobSet under the shared steal
policy.

Every job of a JobSet is seeded onto one queue (the static mapping), every
free engine is kicked in pool order, and on each completion the finishing
engine pops its own queue or steals from the busiest victim under the tail
guard — the SAME :func:`repro.soc.policy.should_steal` /
:func:`~repro.soc.policy.pick_victim` the live runtime and the
discrete-event simulator use.  Service times come from the engine cost
models instead of wall clock, so steal decisions are deterministic and
are checked against ``repro.core.scheduler.simulate(policy="ws")`` for
identical cost models.
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
from typing import Optional, Sequence, Union

from repro.engines.base import Engine
from repro.engines.registry import get_engine

from .policy import pick_victim, should_steal

__all__ = ["SimRuntime", "SimRuntimeResult"]


@dataclasses.dataclass
class SimRuntimeResult:
    makespan_s: float
    per_engine_jobs: dict[str, int]
    per_engine_busy: dict[str, float]
    per_engine_steals: dict[str, int]

    @property
    def total_steals(self) -> int:
        return sum(self.per_engine_steals.values())

    @property
    def aggregate_busy_fraction(self) -> float:
        """Table-6 analog: total busy over pool-size x makespan."""
        if self.makespan_s <= 0:
            return 0.0
        n = len(self.per_engine_busy)
        return sum(self.per_engine_busy.values()) / (n * self.makespan_s)


class SimRuntime:
    """Virtual-time work-stealing executor over engine cost models.

    ``tracer=Tracer(...)`` records the SAME event schema the live
    runtime emits (seed/enqueue/dequeue, panel spans, steals) with
    VIRTUAL timestamps, so a sim trace diffs directly against a live
    trace of the same workload.  Unlike the live runtime the sim never
    falls back to the process-default tracer — a ``--trace``'d benchmark
    must not interleave virtual stamps into its wall-clock timeline."""

    def __init__(self, engines: Sequence[Union[str, Engine]], *,
                 tracer=None):
        self.engines = [get_engine(e) if isinstance(e, str) else e
                        for e in engines]
        if not self.engines:
            raise ValueError("SimRuntime needs at least one engine")
        self.tracer = tracer

    def run(self, jobset, *,
            affinity: Optional[str] = None) -> SimRuntimeResult:
        """Execute one JobSet in virtual time.  ``affinity`` seeds every
        job on that engine's queue (the live runtime's queue-affinity hint;
        default: first engine, matching the DES static map of one layer to
        one cluster); stealing distributes from there."""
        j = next(jobset.jobs()) if jobset.num_jobs else None
        if j is None:
            zero = {e.name: 0 for e in self.engines}
            return SimRuntimeResult(0.0, dict(zero),
                                    {e.name: 0.0 for e in self.engines},
                                    dict(zero))
        units = [(1, j.macs, j.bytes_moved)] * jobset.num_jobs

        names = [e.name for e in self.engines]
        queues: list[list] = [[] for _ in self.engines]
        home = names.index(affinity) if affinity in names else 0
        queues[home].extend(units)

        tr = self.tracer
        if tr is not None:
            tr.emit("seed", "manager", ts=0.0, runtime="sim",
                    n_jobs=len(units), affinity=affinity)
            for u in units:
                tr.emit("enqueue", names[home], ts=0.0,
                        jobset=jobset.name, n_jobs=u[0], priority=0)

        rates = [e.cost.macs_per_s for e in self.engines]
        fastest = max(rates)
        busy = [0.0] * len(self.engines)
        jobs_run = [0] * len(self.engines)
        steals = [0] * len(self.engines)
        free = [True] * len(self.engines)

        events: list = []
        seq = itertools.count()
        now = 0.0

        def unit_time(i: int, unit) -> float:
            n_jobs, macs, nbytes = unit
            return n_jobs * self.engines[i].cost.job_time(macs, nbytes)

        def try_dispatch(i: int) -> None:
            if not free[i]:
                return
            unit = None
            stolen = False
            victim = None
            if queues[i]:
                unit = queues[i].pop(0)
            else:
                lens = [len(q) for q in queues]
                if any(lens):
                    v = pick_victim(lens)
                    if v != i and should_steal(rates[i] / fastest, lens[v]):
                        unit = queues[v].pop()     # steal from the tail
                        stolen = True
                        victim = names[v]
            if unit is None:
                return
            dt = unit_time(i, unit)
            free[i] = False
            busy[i] += dt
            jobs_run[i] += unit[0]
            steals[i] += int(stolen)
            if tr is not None:
                if stolen:
                    tr.emit("steal", names[i], ts=now, victim=victim,
                            jobset=jobset.name, priority=0, probe=False)
                else:
                    tr.emit("dequeue", names[i], ts=now,
                            jobset=jobset.name, n_jobs=unit[0])
                tr.span("panel", names[i], now, dt, jobset=jobset.name,
                        n_jobs=unit[0], stolen=stolen, priority=0)
            heapq.heappush(events, (now + dt, next(seq), i))

        def kick_all() -> None:
            for i in range(len(self.engines)):
                try_dispatch(i)

        kick_all()
        while events:
            now, _, i = heapq.heappop(events)
            free[i] = True
            try_dispatch(i)

        return SimRuntimeResult(
            makespan_s=now,
            per_engine_jobs=dict(zip(names, jobs_run)),
            per_engine_busy=dict(zip(names, busy)),
            per_engine_steals=dict(zip(names, steals)))
