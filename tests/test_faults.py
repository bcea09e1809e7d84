"""repro.soc.faults: deterministic fault injection + runtime recovery.

Covers the ISSUE 9 acceptance criteria: seed-reproducible FaultPlans,
panel retry with bitwise-identical merged outputs (exactly-once merge —
a retried panel's failed attempt never double-merges), worker-death
detection re-seeding queued + in-flight panels, the stall sweep's
idempotent duplicate re-execution, the opt-in NaN/Inf integrity guard,
faults feeding the HealthPolicy quarantine EMA, graph node retry before
descendant-cancel, the per-job drain-error fix, flight-recorder dumps on
retry exhaustion, serving surviving a mid-prefill engine crash, and the
fault trace's schema.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.job import JobSet
from repro.engines import CAP_GEMM, CostModel, Engine
from repro.obs.flightrec import FlightRecorder
from repro.obs.trace import EVENT_KINDS, Tracer, validate_events
from repro.soc import (FaultPlan, FaultSpec, FaultyEngine, GraphNode,
                       HealthPolicy, InjectedFault, PanelRetryExhausted,
                       RetryPolicy, SynergyRuntime, wrap_pool)


class _MathEngine(Engine):
    """All instances compute the IDENTICAL fp32 jnp.dot, so merged
    results are placement-independent and bitwise comparable across
    fault-free and faulted runs."""

    def __init__(self, name, macs_per_s=5e8):
        super().__init__(name, {CAP_GEMM, "epilogue"},
                         cost=CostModel(macs_per_s=macs_per_s))
        self.executed = 0

    def execute(self, a, b, *, bias=None, activation=None, tile=None,
                out_dtype=None, precision=None):
        self.executed += 1
        y = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        if bias is not None:
            y = y + bias
        if activation is not None:
            y = activation(y)
        return y.astype(out_dtype or a.dtype)


def _pool(n=3, macs_per_s=5e8):
    return [_MathEngine(f"fe{i}", macs_per_s) for i in range(n)]


def _ab(m, k, n, seed=0):
    ka, kb = jax.random.split(jax.random.key(seed))
    return (jax.random.normal(ka, (m, k)), jax.random.normal(kb, (k, n)))


def _run_gemm(engines, *, retry=None, tracer=None, name="faults",
              m=256, k=64, n=48, seed=0, **rt_kw):
    # every panel is seeded on fe0, but on a loaded host the other
    # engines can steal every panel before fe0 runs one: a test that
    # needs a planned fault to fire runs a one-engine pool
    a, b = _ab(m, k, n, seed)
    with SynergyRuntime(engines, name=name, retry=retry, tracer=tracer,
                        **rt_kw) as rt:
        fut = rt.submit_gemm(
            a, b, jobset=JobSet.for_gemm(0, m, k, n, 32, name="g0"),
            tile=(32, 32, 32), affinity="fe0")
        y = fut.result(60)
        stats = rt.stats()
    return np.asarray(y), fut, stats


# -------------------------------------------------------------- the plan

def test_fault_plan_is_seed_reproducible():
    engines = ["a", "b", "c"]
    p1 = FaultPlan.random(42, engines)
    p2 = FaultPlan.random(42, engines)
    assert p1.specs == p2.specs
    assert p1.specs != FaultPlan.random(43, engines).specs
    # the default draw is retryable-only: the chaos-sweep contract
    assert all(s.kind in ("raise", "corrupt", "slowdown")
               for s in p1.specs)


def test_fault_spec_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec("e", "meltdown")
    with pytest.raises(ValueError, match="count"):
        FaultSpec("e", "raise", count=0)
    with pytest.raises(ValueError, match="at_call"):
        FaultSpec("e", "raise", at_call=-1)
    s = FaultSpec("e", "raise", at_call=2, count=3)
    assert [s.hits(c) for c in range(6)] == [False, False, True, True,
                                             True, False]


def test_retry_policy_validation():
    with pytest.raises(ValueError, match="max_attempts"):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError, match="monitor_interval_s"):
        RetryPolicy(monitor_interval_s=0)
    assert RetryPolicy(heartbeat_timeout_s=0.5,
                       monitor_interval_s=0.05).timeout_steps == 10
    assert RetryPolicy(heartbeat_timeout_s=0.01,
                       monitor_interval_s=1.0).timeout_steps == 1


def test_wrap_pool_only_wraps_targeted_engines():
    pool = _pool(3)
    plan = FaultPlan((FaultSpec("fe1", "raise"),), seed=0)
    wrapped = wrap_pool(pool, plan)
    assert isinstance(wrapped[1], FaultyEngine)
    assert wrapped[0] is pool[0] and wrapped[2] is pool[2]
    # delegation is attribute-faithful: no phantom int8 entry points
    assert not hasattr(wrapped[1], "execute_int8")
    assert wrapped[1].telemetry is pool[1].telemetry
    assert wrapped[1].cost.macs_per_s == pool[1].cost.macs_per_s


def test_heartbeat_monitor_is_shared_definition():
    """One heartbeat-timeout definition, not two: the runtime's
    worker-death detector must BE the elastic-training monitor."""
    import repro.runtime.fault_tolerance as ft
    import repro.soc.runtime as rt_mod
    assert rt_mod.HeartbeatMonitor is ft.HeartbeatMonitor


# --------------------------------------------------- retry, bitwise merge

def test_injected_raise_retries_bitwise_and_exactly_once():
    """The keystone invariant: two injected panel exceptions cost two
    retries and NOTHING else — merged output bitwise-identical to the
    fault-free run, every panel merged exactly once."""
    ref, _, _ = _run_gemm(_pool())
    plan = FaultPlan((FaultSpec("fe0", "raise", at_call=0, count=2),),
                     seed=3)
    tracer = Tracer()
    y, fut, stats = _run_gemm(wrap_pool(_pool(1), plan, tracer=tracer),
                              retry=RetryPolicy(max_attempts=3),
                              tracer=tracer)
    assert np.array_equal(y, ref)
    assert plan.injected == [("fe0", "raise", 0), ("fe0", "raise", 1)]
    assert stats["retries"] == 2 and fut.retries == 2
    # exactly-once: failed attempts never reached the merge
    assert fut.execution_counts == [1] * len(fut.execution_counts)
    assert sum(a["jobs"] for a in fut.accounting.values()) == 8 * 2
    kinds = {e.kind for e in tracer.events()}
    assert {"fault_injected", "panel_retry"} <= kinds
    validate_events(tracer.events())


def test_retry_avoids_failed_engine():
    """fe0 ALWAYS raises; the submission can only succeed if retries
    re-seed onto the other engines."""
    plan = FaultPlan((FaultSpec("fe0", "raise", at_call=0, count=10_000),),
                     seed=0)
    ref, _, _ = _run_gemm(_pool())
    y, fut, stats = _run_gemm(wrap_pool(_pool(), plan),
                              retry=RetryPolicy(max_attempts=3))
    assert np.array_equal(y, ref)
    assert stats["retries"] >= 1
    # every injection the audit log shows happened on fe0, and each
    # faulted panel's retry succeeded elsewhere on the FIRST try
    assert {e for e, _, _ in plan.injected} == {"fe0"}
    assert stats["retries"] == len(plan.injected)


def test_retry_exhaustion_raises_and_dumps_flight(tmp_path):
    """A panel that fails everywhere surfaces PanelRetryExhausted with
    its audit trail, and the flight recorder dumps the post-mortem."""
    plan = FaultPlan(
        tuple(FaultSpec(f"fe{i}", "raise", at_call=0, count=10_000)
              for i in range(2)), seed=0)
    tracer = Tracer()
    flight = FlightRecorder(tracer, dir=str(tmp_path))
    a, b = _ab(64, 32, 32)
    with SynergyRuntime(wrap_pool(_pool(2), plan, tracer=tracer),
                        name="exhaust", retry=RetryPolicy(max_attempts=2),
                        tracer=tracer, flight_recorder=flight) as rt:
        fut = rt.submit_gemm(
            a, b, jobset=JobSet.for_gemm(0, 64, 32, 32, 32, name="doom"),
            tile=(32, 32, 32))
        with pytest.raises(PanelRetryExhausted) as ei:
            fut.result(60)
    assert ei.value.jobset_name == "doom"
    assert ei.value.attempts == 2
    assert isinstance(ei.value.last, InjectedFault)
    dumps = list(tmp_path.glob("flightrec-*retry_exhausted*.json"))
    assert dumps, "retry exhaustion must flight-record a post-mortem"


def test_backoff_delays_reseed():
    # one engine (see _run_gemm); the retry re-seeds onto it, as nowhere
    # else is left
    plan = FaultPlan((FaultSpec("fe0", "raise", at_call=0, count=1),),
                     seed=0)
    ref, _, _ = _run_gemm(_pool(1))
    t0 = time.perf_counter()
    y, fut, stats = _run_gemm(
        wrap_pool(_pool(1), plan),
        retry=RetryPolicy(max_attempts=3, backoff_s=0.15))
    assert np.array_equal(y, ref)
    assert stats["retries"] == 1
    assert time.perf_counter() - t0 >= 0.15


# ------------------------------------------------------------ worker death

def test_worker_death_reseeds_orphans_bitwise():
    """A worker killed mid-panel: the heartbeat monitor detects the dead
    thread, retires the engine, and the orphaned panels (queued AND the
    one it died holding) re-seed onto the survivors."""
    ref, _, _ = _run_gemm(_pool())
    plan = FaultPlan((FaultSpec("fe1", "die", at_call=0),), seed=0)
    tracer = Tracer()
    retry = RetryPolicy(heartbeat_timeout_s=0.1, monitor_interval_s=0.02)
    a, b = _ab(256, 64, 48)
    with SynergyRuntime(wrap_pool(_pool(), plan, tracer=tracer),
                        name="death", retry=retry, tracer=tracer) as rt:
        # seed onto the doomed engine: it dies holding its FIRST panel,
        # leaving both an in-flight orphan and queued orphans to re-seed
        fut = rt.submit_gemm(
            a, b, jobset=JobSet.for_gemm(0, 256, 64, 48, 32, name="g0"),
            tile=(32, 32, 32), affinity="fe1")
        y = fut.result(60)
        stats = rt.stats()
        assert "fe1" not in rt.engine_names     # retired, not respawned
    assert np.array_equal(np.asarray(y), ref)
    assert stats["worker_deaths"] == 1
    assert stats["orphan_reseeds"] >= 1
    assert fut.execution_counts == [1] * len(fut.execution_counts)
    kinds = {e.kind for e in tracer.events()}
    assert {"worker_death", "orphan_reseed", "fault_injected"} <= kinds


def test_dropped_completion_recovered_by_stall_sweep():
    """A dropped completion leaves the panel in flight forever; only the
    stall sweep's DUPLICATE re-execution recovers it — and the
    idempotent per-index merge keeps the duplicate safe."""
    ref, _, _ = _run_gemm(_pool())
    plan = FaultPlan((FaultSpec("fe0", "drop", at_call=0),), seed=0)
    retry = RetryPolicy(stall_timeout_s=0.15, monitor_interval_s=0.02)
    y, fut, stats = _run_gemm(wrap_pool(_pool(1), plan), retry=retry)
    assert np.array_equal(y, ref)
    assert stats["retries"] >= 1
    # exactly-once MERGE even when execution happened twice
    assert fut.execution_counts == [1] * len(fut.execution_counts)


# --------------------------------------------------------- integrity guard

def test_corrupt_output_guard_opt_in():
    """check_outputs=True turns NaN corruption into a retryable fault;
    without the guard the corruption merges silently (documented)."""
    ref, _, _ = _run_gemm(_pool())
    plan = FaultPlan((FaultSpec("fe0", "corrupt", at_call=0),), seed=0)
    y, fut, stats = _run_gemm(
        wrap_pool(_pool(1), plan),
        retry=RetryPolicy(max_attempts=3, check_outputs=True))
    assert np.array_equal(y, ref)
    assert np.isfinite(y).all()
    assert stats["retries"] >= 1
    # the guard is opt-in: check_outputs=False lets the NaN through
    plan2 = FaultPlan((FaultSpec("fe0", "corrupt", at_call=0),), seed=0)
    y2, _, _ = _run_gemm(wrap_pool(_pool(1), plan2),
                         retry=RetryPolicy(max_attempts=3))
    assert np.isnan(y2).any()


# ----------------------------------------------------- health integration

def test_repeated_faults_quarantine_engine():
    """Faults drive the health EMA toward zero, tripping the SAME
    quarantine machinery a thermal collapse would.  fe1 never completes
    a healthy panel, so its quarantine rides the zero-baseline path
    (min_samples straight faults); quarantine_below is kept low so noisy
    wall-clock rates can't also condemn the honest engines."""
    plan = FaultPlan((FaultSpec("fe1", "raise", at_call=0, count=10_000),),
                     seed=0)
    health = HealthPolicy(alpha=0.5, quarantine_below=0.2,
                          min_samples=3, probe_interval_s=1e9)
    a, b = _ab(512, 64, 48)
    with SynergyRuntime(wrap_pool(_pool(), plan), name="sick",
                        retry=RetryPolicy(max_attempts=4),
                        health=health) as rt:
        for i in range(6):
            rt.submit_gemm(
                a, b, jobset=JobSet.for_gemm(i, 512, 64, 48, 32,
                                             name=f"g{i}"),
                tile=(32, 32, 32)).result(60)
        stats = rt.stats()
    assert stats["engines"]["fe1"]["faults"] >= 3
    assert stats["quarantines"] >= 1
    assert stats["engines"]["fe1"]["quarantined"]


# ------------------------------------------------------- drain-error fix

def test_drained_jobsets_get_distinct_exception_instances():
    """Regression: _drain_jobs_locked used to complete EVERY drained job
    with the SAME exception instance — concurrent waiters re-raising one
    object cross-contaminate tracebacks.  Each jobset must get its own
    copy, naming the jobset it drained."""
    slow = _MathEngine("slow", 5e8)
    orig = slow.execute

    def gated(a, b, **kw):
        time.sleep(0.3)
        return orig(a, b, **kw)
    slow.execute = gated
    a, b = _ab(64, 32, 32)
    caught = {}
    with SynergyRuntime([slow], name="drain") as rt:
        futs = [rt.submit_gemm(
            a, b, jobset=JobSet.for_gemm(i, 64, 32, 32, 32,
                                         name=f"js{i}"),
            tile=(32, 32, 32)) for i in range(2)]

        def waiter(i):
            try:
                futs[i].result(30)
            except BaseException as e:  # noqa: BLE001 - capturing for assert
                caught[i] = e
        threads = [threading.Thread(target=waiter, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        time.sleep(0.05)               # waiters parked, panels queued
        with rt._cond:
            rt._drain_jobs_locked(lambda j: True,
                                  RuntimeError("upstream failed"))
        for t in threads:
            t.join(30)
    assert set(caught) == {0, 1}
    assert caught[0] is not caught[1]
    for i in (0, 1):
        assert f"js{i}" in str(caught[i])
        assert "upstream failed" in str(caught[i])


# ------------------------------------------------------ graph node retry

def test_graph_node_retries_before_cancel():
    """A failing graph node re-launches up to node_retries times BEFORE
    the failure cancels descendants."""
    attempts = {"n": 0}

    def flaky(rt):
        attempts["n"] += 1
        if attempts["n"] == 1:
            raise InjectedFault("first launch fails")
        return 41

    tracer = Tracer()
    with SynergyRuntime(_pool(2), name="gretry", tracer=tracer) as rt:
        gf = rt.submit_graph(
            [GraphNode(name="flaky", run=flaky),
             GraphNode(name="after", run=lambda rt, v: v + 1)],
            [(0, 1)], name="retrygraph", node_retries=1)
        vals = gf.result(60)
    assert vals == [41, 42]
    assert attempts["n"] == 2
    assert gf.retries >= 1
    assert "graph_node_retry" in {e.kind for e in tracer.events()}


def test_graph_node_retry_exhaustion_still_cancels():
    def doomed(rt):
        raise InjectedFault("always fails")

    with SynergyRuntime(_pool(2), name="gdoom") as rt:
        gf = rt.submit_graph(
            [GraphNode(name="doomed", run=doomed),
             GraphNode(name="after", run=lambda rt, v: v + 1)],
            [(0, 1)], name="doomgraph", node_retries=2)
        with pytest.raises(InjectedFault):
            gf.result(60)
    assert gf.retries >= 2


# ------------------------------------------------------------ observability

def test_fault_event_kinds_are_registered():
    assert {"fault_injected", "panel_retry", "worker_death",
            "orphan_reseed", "graph_node_retry"} <= EVENT_KINDS


def test_metrics_export_fault_counters():
    from repro.obs.metrics import MetricsRegistry, collect_runtime
    plan = FaultPlan((FaultSpec("fe1", "raise", at_call=0),), seed=0)
    with SynergyRuntime(wrap_pool(_pool(), plan), name="metrics",
                        retry=RetryPolicy(max_attempts=3)) as rt:
        a, b = _ab(128, 32, 32)
        rt.submit_gemm(
            a, b, jobset=JobSet.for_gemm(0, 128, 32, 32, 32, name="m0"),
            tile=(32, 32, 32)).result(60)
        reg = MetricsRegistry()
        collect_runtime(rt, reg)
    assert reg.counter("repro_runtime_retries_total").value == 1
    assert reg.counter("repro_runtime_worker_deaths_total").value == 0
    assert reg.counter("repro_runtime_orphan_reseeds_total").value == 0
    assert "repro_runtime_retries_total" in reg.render()


def test_stats_reset_zeroes_fault_counters():
    plan = FaultPlan((FaultSpec("fe0", "raise", at_call=0),), seed=0)
    with SynergyRuntime(wrap_pool(_pool(2), plan), name="rst",
                        retry=RetryPolicy(max_attempts=3)) as rt:
        a, b = _ab(64, 32, 32)
        rt.submit_gemm(
            a, b, jobset=JobSet.for_gemm(0, 64, 32, 32, 32, name="r0"),
            tile=(32, 32, 32)).result(60)
        assert rt.stats()["retries"] == 1
        rt.reset_stats()
        st = rt.stats()
    assert st["retries"] == 0 and st["worker_deaths"] == 0
    assert st["orphan_reseeds"] == 0


# ------------------------------------------------------------ trace schema

def test_fault_trace_conforms_to_live_schema():
    """A fault run's trace passes the replay invariants, uses only
    registered event kinds, and tags each recovery event with the keys
    its readers expect."""
    plan = FaultPlan((FaultSpec("fe0", "raise", at_call=0, count=2),),
                     seed=5)
    tr = Tracer()
    _, fut, stats = _run_gemm(wrap_pool(_pool(1), plan, tracer=tr),
                              retry=RetryPolicy(max_attempts=3), tracer=tr)
    evs = tr.events()
    assert validate_events(evs, engines={"fe0"}) == []
    assert {e.kind for e in evs} <= EVENT_KINDS

    def tag_keys(kind):
        return {frozenset(e.tags) for e in evs if e.kind == kind}
    assert tag_keys("fault_injected") == {
        frozenset({"fault", "call", "at_call"})}
    assert tag_keys("panel_retry") == {
        frozenset({"jobset", "attempt", "err"})}
    assert stats["retries"] == fut.retries == 2


# -------------------------------------------------- serving survives faults

def test_serving_wave_survives_engine_crash():
    """A serving wave with a worker killed mid-prefill completes every
    request with token streams BITWISE identical to the fault-free run,
    and the retries surface in ServeStats.runtime_retries."""
    from repro.configs import ARCHS, reduced
    from repro.core.serving import Request, SynergyServer
    from repro.models import init_model
    cfg = reduced(ARCHS["granite-3-2b"], n_layers=2, d_model=32,
                  n_heads=2, d_ff=64, vocab=128)
    params = init_model(cfg, jax.random.key(0))

    def requests():
        return [Request(i, jax.random.randint(jax.random.key(i),
                                              (4,), 0, 128),
                        max_new_tokens=4) for i in range(3)]

    def serve(engines, retry=None):
        with SynergyRuntime(engines, name="srv", retry=retry) as rt:
            srv = SynergyServer(cfg, params, slots=2, max_len=32,
                                prefill_len=4, runtime=rt)
            reqs = requests()
            for r in reqs:
                srv.submit(r)
            stats = srv.run()
        return [list(r.out) for r in reqs], stats

    clean_tokens, clean_stats = serve(_pool())
    assert clean_stats.runtime_retries == 0
    plan = FaultPlan((FaultSpec("fe1", "die", at_call=0),
                      FaultSpec("fe0", "raise", at_call=0, count=2)),
                     seed=11)
    retry = RetryPolicy(max_attempts=4, heartbeat_timeout_s=0.1,
                        monitor_interval_s=0.02)
    fault_tokens, fault_stats = serve(wrap_pool(_pool(), plan), retry)
    assert fault_tokens == clean_tokens     # bitwise token streams
    assert fault_stats.runtime_retries >= 1
    assert len(plan.injected) >= 2


# ------------------------------------------------------- chaos acceptance

def test_chaos_acceptance_crash_plus_exceptions_bitwise():
    """The ISSUE 9 acceptance scenario: a 3-engine pool with a worker
    crash mid-submission plus two injected panel exceptions completes
    every submission with results bitwise-identical to the fault-free
    run, the trace shows the retries and orphan re-seeds, and no
    RuntimeFuture hangs."""
    a, b = _ab(384, 64, 48, seed=7)
    jobsets = [JobSet.for_gemm(i, 384, 64, 48, 32, name=f"chaos{i}")
               for i in range(4)]

    def run(engines, retry=None, tracer=None):
        outs = []
        with SynergyRuntime(engines, name="chaos", retry=retry,
                            tracer=tracer) as rt:
            futs = [rt.submit_gemm(a, b, jobset=js, tile=(32, 32, 32),
                                   affinity="fe0") for js in jobsets]
            for f in futs:
                outs.append(np.asarray(f.result(60)))
            stats = rt.stats()
        return outs, stats, futs

    ref, _, _ = run(_pool())
    plan = FaultPlan((FaultSpec("fe2", "die", at_call=1),
                      FaultSpec("fe1", "raise", at_call=0, count=2)),
                     seed=23)
    tracer = Tracer()
    retry = RetryPolicy(max_attempts=4, heartbeat_timeout_s=0.1,
                        monitor_interval_s=0.02)
    outs, stats, futs = run(wrap_pool(_pool(), plan, tracer=tracer),
                            retry, tracer)
    for y, r in zip(outs, ref):
        assert np.array_equal(y, r)
    assert stats["worker_deaths"] == 1
    assert stats["retries"] >= 2
    assert stats["orphan_reseeds"] >= 1
    for f in futs:
        assert f.done()
        assert f.execution_counts == [1] * len(f.execution_counts)
    kinds = {e.kind for e in tracer.events()}
    assert {"fault_injected", "panel_retry", "worker_death",
            "orphan_reseed"} <= kinds
    validate_events(tracer.events())


def test_fault_free_pool_has_no_monitor_thread():
    """retry=None keeps the hot path untouched: no monitor thread, no
    live-panel registry entries."""
    with SynergyRuntime(_pool(2), name="clean") as rt:
        a, b = _ab(64, 32, 32)
        rt.submit_gemm(
            a, b, jobset=JobSet.for_gemm(0, 64, 32, 32, 32, name="c0"),
            tile=(32, 32, 32)).result(60)
        assert rt._monitor is None
        assert not rt._live_panels
        st = rt.stats()
    assert st["retries"] == 0 and st["worker_deaths"] == 0
