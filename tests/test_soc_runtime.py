"""SynergyRuntime: work-stealing execution over live engine pools.

Covers the acceptance criteria of the runtime PR: split-and-merge GEMMs
match the oracle, work conservation under randomized steal timing, nonzero
steals + strictly higher aggregate busy fraction vs single-engine pinning
for a steady-frame ThreadedPipeline, live add/remove rebalance (including
registry-driven), serving submissions, and DES <-> SimRuntime conformance.
"""

import random
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.clusters import Accelerator, Cluster
from repro.core.job import JobSet
from repro.core.pipeline import EngineStage, ThreadedPipeline
from repro.core.scheduler import SimLayer, SimNet, simulate
from repro.core.synergy_mm import SynergyTrace, synergy_matmul
from repro.engines import (CAP_GEMM, CostModel, Engine, get_engine,
                           registered)
from repro.soc import (SimRuntime, SynergyRuntime, current_runtime,
                       runtime_scope, should_steal)


def _ab(m, k, n, seed=0):
    ka, kb = jax.random.split(jax.random.key(seed))
    return (jax.random.normal(ka, (m, k)), jax.random.normal(kb, (k, n)))


class _DelayEngine(Engine):
    """Deterministic-output engine with seeded random per-job delays —
    randomized steal timing without randomized results."""

    def __init__(self, name, macs_per_s=1e9, seed=0, max_delay_s=0.004):
        super().__init__(name, {CAP_GEMM, "epilogue"},
                         cost=CostModel(macs_per_s=macs_per_s))
        self._rng = random.Random(seed)
        self._max_delay_s = max_delay_s
        self.executed = 0

    def execute(self, a, b, *, bias=None, activation=None, tile=None,
                out_dtype=None, precision=None):
        time.sleep(self._rng.random() * self._max_delay_s)
        self.executed += 1
        y = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        if bias is not None:
            y = y + bias
        if activation is not None:
            y = activation(y)
        return y.astype(out_dtype or a.dtype)


# ------------------------------------------------------------ split + merge

def test_runtime_scope_splits_and_matches_dot():
    a, b = _ab(300, 64, 48)
    with SynergyRuntime(["F-PE", "S-PE"]) as rt, rt.scope():
        tr = SynergyTrace()
        with tr.activate():
            y = synergy_matmul(a, b, tile=32, name="split")
    np.testing.assert_allclose(np.asarray(y), np.asarray(jnp.dot(a, b)),
                               rtol=1e-4, atol=1e-4)
    # all 10x2 tile jobs booked, across however many engines executed
    assert sum(t.jobs for t in tr.engine_stats.values()) == 20
    stats = rt.stats()
    assert stats["total_jobs"] == 20
    assert stats["submissions"] == 1


def test_runtime_scope_epilogue_and_border_tiles():
    a, b = _ab(70, 33, 45, seed=3)       # border tiles in every direction
    bias = jax.random.normal(jax.random.key(9), (45,))
    with SynergyRuntime(["F-PE", "S-PE"]) as rt, rt.scope():
        y = synergy_matmul(a, b, bias=bias, activation=jax.nn.relu, tile=32)
    ref = get_engine("reference").execute(a, b, bias=bias,
                                          activation=jax.nn.relu)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_runtime_scope_is_inert_under_jit():
    """Traced arrays cannot cross worker threads: under jit the call falls
    back to single-engine dispatch and stays correct."""
    a, b = _ab(64, 32, 32, seed=4)
    f = jax.jit(lambda a, b: synergy_matmul(a, b, tile=32))
    with SynergyRuntime(["F-PE", "S-PE"]) as rt, rt.scope():
        y = f(a, b)
        assert rt.stats()["total_jobs"] == 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(jnp.dot(a, b)),
                               rtol=1e-4, atol=1e-4)


def test_current_runtime_scope_nesting():
    rt1 = SynergyRuntime(["F-PE"], name="outer")
    rt2 = SynergyRuntime(["S-PE"], name="inner")
    assert current_runtime() is None
    try:
        with runtime_scope(rt1):
            assert current_runtime() is rt1
            with runtime_scope(rt2):
                assert current_runtime() is rt2
            assert current_runtime() is rt1
        assert current_runtime() is None
    finally:
        rt1.shutdown()
        rt2.shutdown()


# ------------------------------------------------------- work conservation

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_work_conservation_under_randomized_stealing(seed):
    """Every tile job executes exactly once no matter how steals interleave,
    and the merged result is bit-exact vs the same split executed serially
    on one engine of the same family."""
    engines = [_DelayEngine(f"d{i}", macs_per_s=(i + 1) * 1e9,
                            seed=seed * 10 + i) for i in range(3)]
    a, b = _ab(17 * 16, 40, 24, seed=seed)
    js = JobSet.for_gemm(0, a.shape[0], 24, 40, 16)
    with SynergyRuntime(engines) as rt:
        fut = rt.submit_gemm(a, b, jobset=js, tile=(16, 16, 16))
        y = fut.result(60)
    assert fut.execution_counts == [1] * 17          # exactly once, per panel
    acct_jobs = sum(x["jobs"] for x in fut.accounting.values())
    assert acct_jobs == js.num_jobs == 17 * 2
    assert sum(e.executed for e in engines) == 17
    # bit-exact oracle: same row panels on a single same-family engine
    solo = _DelayEngine("solo", seed=99, max_delay_s=0.0)
    parts = [solo.execute(a[r:r + 16], b) for r in range(0, a.shape[0], 16)]
    assert np.array_equal(np.asarray(y), np.asarray(jnp.concatenate(parts)))


def test_accounting_submission_conserves_jobs():
    js = JobSet.for_gemm(0, 320, 128, 64, 32)
    with SynergyRuntime(["F-PE", "S-PE", "NEON"]) as rt:
        futs = [rt.submit(js, affinity="F-PE") for _ in range(4)]
        for fut in futs:
            fut.result(30)
            assert sum(x["jobs"] for x in fut.accounting.values()) \
                == js.num_jobs
    assert rt.stats()["total_jobs"] == 4 * js.num_jobs


# ------------------------------------- acceptance: steals + busy fraction

def _agg_busy_fraction(before, after):
    """Table-6 analog over a fixed pool: total cost-model busy seconds over
    pool-size x the busiest engine's busy seconds."""
    deltas = [a.busy_s - b.busy_s for b, a in zip(before, after)]
    top = max(deltas)
    return sum(deltas) / (len(deltas) * top) if top > 0 else 0.0


def test_pipeline_runtime_steals_and_beats_pinned_busy_fraction():
    """ISSUE acceptance: with >=2 engines, a steady-frame ThreadedPipeline
    run through runtime_scope() reports nonzero steal count and strictly
    higher aggregate busy fraction than the same workload pinned to a
    single engine (simulated-PE pool)."""
    pool = ["F-PE", "S-PE"]
    engines = [get_engine(n) for n in pool]
    w = jax.random.normal(jax.random.key(0), (64, 48))
    frames = [jax.random.normal(jax.random.key(i), (320, 64))
              for i in range(6)]

    def snap():
        return [e.telemetry.snapshot() for e in engines]

    # pinned: every GEMM hard-routed to F-PE (PR-1 single-engine dispatch);
    # TS=32 gives 10 row-panel jobs per frame, deep enough for the tail
    # guard to let the 0.5x S-PE steal
    stages = [EngineStage.gemm("mm", w, engine="F-PE", tile=(32, 32, 32)),
              ("post", lambda y: float(jnp.sum(y)))]
    b0 = snap()
    outs, _ = ThreadedPipeline(stages).run(frames)
    pinned_frac = _agg_busy_fraction(b0, snap())
    assert len(outs) == len(frames)
    assert pinned_frac == pytest.approx(1.0 / len(pool))

    # runtime: same stages, same pin — now a queue-affinity hint; the idle
    # S-PE steals tile jobs from F-PE's deque
    with SynergyRuntime(pool, name="accept") as rt, rt.scope():
        b1 = snap()
        outs, stats = ThreadedPipeline(stages).run(frames)
        rt_frac = _agg_busy_fraction(b1, snap())
    assert len(outs) == len(frames)
    rstats = stats["runtime"]
    assert rstats is not None and rstats["total_steals"] > 0
    assert rt_frac > pinned_frac
    assert rstats["aggregate_busy_fraction"] > 1.0 / len(pool)


# --------------------------------------------------- live pool add/remove

def test_add_engine_mid_run_rebalances():
    slow = _DelayEngine("slow-only", macs_per_s=1e9, seed=1,
                        max_delay_s=0.01)
    helper = _DelayEngine("helper", macs_per_s=1e9, seed=2, max_delay_s=0.0)
    a, b = _ab(24 * 16, 32, 16, seed=7)
    js = JobSet.for_gemm(0, a.shape[0], 16, 32, 16)
    with SynergyRuntime([slow]) as rt:
        fut = rt.submit_gemm(a, b, jobset=js, tile=(16, 16, 16))
        rt.add_engine(helper)
        y = fut.result(120)
        assert rt.stats()["rebalances"] >= 1
    assert helper.executed > 0, "added engine never picked up queued work"
    assert slow.executed + helper.executed == 24
    np.testing.assert_allclose(np.asarray(y), np.asarray(jnp.dot(a, b)),
                               rtol=1e-4, atol=1e-4)


def test_remove_engine_mid_run_work_still_completes():
    doomed = _DelayEngine("doomed", macs_per_s=1e9, seed=3,
                          max_delay_s=0.01)
    survivor = _DelayEngine("survivor", macs_per_s=1e9, seed=4,
                            max_delay_s=0.0)
    a, b = _ab(24 * 16, 32, 16, seed=8)
    js = JobSet.for_gemm(0, a.shape[0], 16, 32, 16)
    with SynergyRuntime([doomed, survivor]) as rt:
        fut = rt.submit_gemm(a, b, jobset=js, tile=(16, 16, 16),
                             affinity="doomed")
        rt.remove_engine("doomed")
        y = fut.result(120)
        assert "doomed" not in rt.engine_names
    assert fut.execution_counts == [1] * 24
    assert survivor.executed > 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(jnp.dot(a, b)),
                               rtol=1e-4, atol=1e-4)


def test_trace_counts_split_gemm_once():
    """A split GEMM is still ONE gemm: trace gemms sum to len(jobsets)
    on the runtime path exactly as on the dispatcher path."""
    a, b = _ab(320, 64, 48, seed=13)
    tr = SynergyTrace()
    with SynergyRuntime(["F-PE", "S-PE"]) as rt, rt.scope():
        with tr.activate():
            synergy_matmul(a, b, tile=32, name="g0")
            synergy_matmul(a, b, tile=32, name="g1")
    assert sum(t.gemms for t in tr.engine_stats.values()) == 2
    assert sum(t.jobs for t in tr.engine_stats.values()) == tr.num_jobs


def test_runtime_scope_is_thread_local():
    """A scope in one thread must not hijack GEMMs in unrelated threads
    (explicit engine= pins there keep routing through the dispatcher)."""
    import threading
    seen = {}

    def other_thread():
        seen["runtime"] = current_runtime()

    with SynergyRuntime(["F-PE"]) as rt, rt.scope():
        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
        assert current_runtime() is rt
    assert seen["runtime"] is None


def test_stats_totals_survive_engine_removal():
    """Hot-unplug folds the retired worker's counters into the totals —
    monitoring never sees total_jobs/total_steals go backwards."""
    e1 = _DelayEngine("r1", seed=21, max_delay_s=0.002)
    e2 = _DelayEngine("r2", seed=22, max_delay_s=0.0)
    a, b = _ab(12 * 16, 32, 16, seed=23)
    js = JobSet.for_gemm(0, a.shape[0], 16, 32, 16)
    with SynergyRuntime([e1, e2]) as rt:
        rt.submit_gemm(a, b, jobset=js, tile=(16, 16, 16)).result(60)
        before = rt.stats()
        assert before["total_jobs"] == 12
        rt.remove_engine("r1")
        after = rt.stats()
    assert after["total_jobs"] == before["total_jobs"]
    assert after["total_steals"] == before["total_steals"]
    assert "r1" not in after["engines"]


def test_reregister_single_engine_pool_keeps_queued_work():
    """Swapping the ONLY engine of a follow_registry pool (the registered()
    shadow pattern) must hand queued jobs to the replacement, not fail
    them with 'no engines left'."""
    slow = _DelayEngine("solo-pe", seed=31, max_delay_s=0.01)
    swap = _DelayEngine("solo-pe", seed=32, max_delay_s=0.0)
    a, b = _ab(16 * 16, 32, 16, seed=33)
    js = JobSet.for_gemm(0, a.shape[0], 16, 32, 16)
    with registered(slow):
        with SynergyRuntime(["solo-pe"], follow_registry=True) as rt:
            fut = rt.submit_gemm(a, b, jobset=js, tile=(16, 16, 16))
            with registered(swap):           # atomic same-name swap
                y = fut.result(120)
            assert fut.execution_counts == [1] * 16
    assert slow.executed + swap.executed == 16
    assert swap.executed > 0, "replacement engine never ran queued work"
    np.testing.assert_allclose(np.asarray(y), np.asarray(jnp.dot(a, b)),
                               rtol=1e-4, atol=1e-4)


def test_follow_registry_tracks_register_unregister():
    """register_engine/unregister_engine mid-run adapt the live pool — the
    paper's runtime reconfigurability as an API property."""
    ext = _DelayEngine("hotplug", macs_per_s=5e9, seed=5, max_delay_s=0.0)
    with SynergyRuntime(["F-PE"], follow_registry=True) as rt:
        assert rt.engine_names == ["F-PE"]
        with registered(ext):
            assert "hotplug" in rt.engine_names
            a, b = _ab(10 * 32, 48, 32, seed=9)
            js = JobSet.for_gemm(0, a.shape[0], 32, 48, 32)
            y = rt.submit_gemm(a, b, jobset=js).result(60)
            np.testing.assert_allclose(np.asarray(y),
                                       np.asarray(jnp.dot(a, b)),
                                       rtol=1e-4, atol=1e-4)
        assert "hotplug" not in rt.engine_names


# ---------------------------------------------------------- submit_many

def test_submit_many_matches_individual_submissions():
    """The batched accounting path (ONE lock/LPT-seed/wakeup per wave)
    completes every jobset as its own submission with the same totals as
    N individual submits; empty jobsets come back already finished."""
    jobsets = [JobSet.for_gemm(i, 64 * (i + 1), 32, 48, 32, name=f"js{i}")
               for i in range(4)]
    empty = JobSet.for_gemm(9, 0, 32, 48, 32, name="empty")
    with SynergyRuntime(["F-PE", "S-PE"], name="many") as rt:
        futs = rt.submit_many(jobsets + [empty])
        assert futs[-1].done()            # empty: finished in place
        for fut, js in zip(futs, jobsets):
            fut.result(60)
            assert sum(a["jobs"] for a in fut.accounting.values()) \
                == js.num_jobs
            assert sum(a["est_s"] for a in fut.accounting.values()) > 0
        stats = rt.stats()
    # one submission per non-empty jobset, all jobs conserved
    assert stats["submissions"] == len(jobsets)
    assert stats["total_jobs"] == sum(js.num_jobs for js in jobsets)


def test_submit_many_requires_started_runtime():
    rt = SynergyRuntime(["F-PE"], name="cold")
    js = JobSet.for_gemm(0, 64, 32, 48, 32)
    with pytest.raises(RuntimeError, match="not started"):
        rt.submit_many([js])


# -------------------------------------------------------------- serving

def test_server_routes_jobs_through_runtime():
    from repro.configs import ARCHS, reduced
    from repro.core.serving import Request, SynergyServer
    from repro.models import init_model
    cfg = reduced(ARCHS["granite-3-2b"], n_layers=2, d_model=32,
                  n_heads=2, d_ff=64, vocab=128)
    params = init_model(cfg, jax.random.key(0))
    with SynergyRuntime(["F-PE", "S-PE"]) as rt:
        srv = SynergyServer(cfg, params, slots=2, max_len=32,
                            prefill_len=4, runtime=rt)
        for i in range(3):
            srv.submit(Request(i, jax.random.randint(jax.random.key(i),
                                                     (4,), 0, 128),
                               max_new_tokens=4))
        stats = srv.run()
    assert stats.prefills == 3
    assert stats.runtime_jobs > 0
    assert stats.job_busy_s["prefill"] > 0
    assert stats.job_busy_s["decode"] > 0
    assert set(stats.job_engine.values()) <= {"F-PE", "S-PE"}
    assert rt.stats()["total_jobs"] == stats.runtime_jobs


# ------------------------------------------------------ DES conformance

def test_simruntime_conforms_to_des_work_stealing():
    """The virtual-time runtime and simulate(policy='ws') make IDENTICAL
    steal decisions for identical cost models: per-engine busy seconds
    (hence job counts) and utilization agree exactly."""
    js = JobSet.for_gemm(0, 320, 128, 96, 32, name="conv0")
    net = SimNet("one", (SimLayer("conv0", "conv", jobset=js,
                                  im2col_bytes=0),))
    clusters = [Cluster("A", (Accelerator("F-PE0", "F-PE"),)),
                Cluster("B", (Accelerator("S-PE0", "S-PE"),))]
    des = simulate(net, clusters, policy="ws", mapping={"conv0": 0},
                   frames=1, inflight=1, warmup_frames=0)
    sim = SimRuntime(["F-PE", "S-PE"]).run(js, affinity="F-PE")
    des_busy = {"F-PE": des.per_cluster_busy["A"] * des.makespan_s,
                "S-PE": des.per_cluster_busy["B"] * des.makespan_s}
    for kind in ("F-PE", "S-PE"):
        assert sim.per_engine_busy[kind] == pytest.approx(des_busy[kind],
                                                          rel=1e-12)
    assert sim.makespan_s == pytest.approx(des.makespan_s, rel=1e-12)
    assert sim.aggregate_busy_fraction == pytest.approx(des.utilization,
                                                        rel=1e-12)
    assert sim.total_steals > 0       # the slow engine stole real work


def test_steal_policy_is_shared_object():
    """One policy, three executors: the simulator, the live runtime and
    SimRuntime must all call the SAME function."""
    import repro.core.scheduler as sched
    import repro.soc.policy as policy
    import repro.soc.runtime as runtime
    import repro.soc.simrt as simrt
    assert sched.should_steal is policy.should_steal
    assert runtime.should_steal is policy.should_steal
    assert simrt.should_steal is policy.should_steal
    assert should_steal is policy.should_steal
    # the tail guard itself
    assert should_steal(1.0, 1) and should_steal(0.5, 3)
    assert not should_steal(0.5, 2) and not should_steal(1.0, 0)


def test_simruntime_no_affinity_and_empty_jobset():
    js = JobSet.for_gemm(0, 64, 64, 32, 32)
    res = SimRuntime(["F-PE", "S-PE"]).run(js)
    assert sum(res.per_engine_jobs.values()) == js.num_jobs
    empty = JobSet.for_gemm(0, 0, 0, 0, 32)
    res0 = SimRuntime(["F-PE"]).run(empty)
    assert res0.makespan_s == 0.0 and res0.total_steals == 0


# ------------------------------------ panel and queue-wait counters

def _panel_counters_hold(st):
    """Per-engine panels and queue waits add up to the totals."""
    per = st["engines"].values()
    assert st["total_panels"] == sum(p["panels"] for p in per) \
        + st["retired"]["panels"]
    assert st["total_queue_wait_s"] == pytest.approx(
        sum(p["queue_wait_s"] for p in per)
        + st["retired"]["queue_wait_s"])
    assert st["total_queue_wait_s"] >= 0.0


@pytest.mark.parametrize("seed", [0, 1])
def test_panels_and_queue_wait_count_every_execution_under_steals(seed):
    engines = [_DelayEngine(f"q{i}", macs_per_s=(i + 1) * 1e9,
                            seed=seed * 10 + i) for i in range(3)]
    a, b = _ab(20 * 16, 32, 16, seed=seed)
    js = JobSet.for_gemm(0, a.shape[0], 16, 32, 16)
    with SynergyRuntime(engines) as rt:
        rt.submit_gemm(a, b, jobset=js, tile=(16, 16, 16),
                       affinity="q0").result(60)
        st = rt.stats()
    # a steal is one execution, booked once, by the thief
    assert st["total_panels"] == 20 == sum(e.executed for e in engines)
    assert st["total_steals"] <= st["total_panels"]
    assert st["total_queue_wait_s"] > 0.0
    _panel_counters_hold(st)


def test_panels_count_a_retried_panel_twice():
    from repro.soc import FaultPlan, FaultSpec, RetryPolicy, wrap_pool
    engines = [_DelayEngine(f"f{i}", seed=i, max_delay_s=0.001)
               for i in range(2)]
    plan = FaultPlan((FaultSpec("f0", "raise", at_call=0),), seed=0)
    a, b = _ab(8 * 16, 32, 16, seed=5)
    js = JobSet.for_gemm(0, a.shape[0], 16, 32, 16)
    with SynergyRuntime(wrap_pool(engines, plan),
                        retry=RetryPolicy(max_attempts=3)) as rt:
        y = rt.submit_gemm(a, b, jobset=js, tile=(16, 16, 16),
                           affinity="f0").result(60)
        st = rt.stats()
    assert plan.injected == [("f0", "raise", 0)]
    assert st["retries"] == 1
    assert st["total_panels"] == 8 + st["retries"]
    _panel_counters_hold(st)
    np.testing.assert_allclose(np.asarray(y), np.asarray(jnp.dot(a, b)),
                               rtol=1e-4, atol=1e-4)


def test_panel_totals_survive_hot_unplug_and_reset_clears_them():
    doomed = _DelayEngine("u0", seed=31, max_delay_s=0.01)
    survivor = _DelayEngine("u1", seed=32, max_delay_s=0.0)
    a, b = _ab(24 * 16, 32, 16, seed=9)
    js = JobSet.for_gemm(0, a.shape[0], 16, 32, 16)
    with SynergyRuntime([doomed, survivor]) as rt:
        fut = rt.submit_gemm(a, b, jobset=js, tile=(16, 16, 16),
                             affinity="u0")
        before = rt.stats()
        rt.remove_engine("u0")
        during = rt.stats()
        fut.result(120)
        after = rt.stats()
        assert before["total_panels"] <= during["total_panels"] \
            <= after["total_panels"] == 24
        assert before["total_queue_wait_s"] <= during["total_queue_wait_s"] \
            <= after["total_queue_wait_s"]
        assert "u0" not in after["engines"]
        _panel_counters_hold(after)
        rt.reset_stats()
        st = rt.stats()
    assert st["total_panels"] == 0 and st["total_queue_wait_s"] == 0.0
    assert st["total_jobs"] == 0


def test_prometheus_shows_panel_and_queue_wait_totals():
    from repro.obs.metrics import (MetricsRegistry, parse_prometheus,
                                   render_prometheus)
    a, b = _ab(6 * 16, 32, 16, seed=2)
    js = JobSet.for_gemm(0, a.shape[0], 16, 32, 16)
    with SynergyRuntime([_DelayEngine("m0"), _DelayEngine("m1")]) as rt:
        rt.submit_gemm(a, b, jobset=js, tile=(16, 16, 16)).result(60)
        st = rt.stats()
        got = parse_prometheus(render_prometheus(runtime=rt,
                                                 registry=MetricsRegistry()))
    assert got["repro_runtime_panels_total"] == [({}, 6.0)]
    [(labels, wait)] = got["repro_runtime_queue_wait_seconds_total"]
    assert labels == {} and wait == pytest.approx(st["total_queue_wait_s"])


def test_cnn_forward_bitwise_equal_with_profiler_on_and_off(tmp_path):
    from repro.models.cnn import CNNConfig, cnn_forward, init_cnn
    net = CNNConfig(name="tiny", input_hw=8, cin=1, tile=8, layers=(
        ("conv", 4, 3, 1, 1), ("pool", 2), ("conv", 8, 3, 1, 1),
        ("fc", 10)))
    params = init_cnn(net, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (4, 8, 8, 1))
    with SynergyRuntime([_DelayEngine("p0", max_delay_s=0.0),
                         _DelayEngine("p1", max_delay_s=0.0)]) as rt:
        off = np.asarray(cnn_forward(net, params, x, runtime=rt))
        jax.profiler.start_trace(str(tmp_path))
        try:
            on = np.asarray(cnn_forward(net, params, x, runtime=rt))
        finally:
            jax.profiler.stop_trace()
    assert np.array_equal(on, off)


# ----------------------------------------------- panel plan (row_panels)

def _cifar_full_gemms(frames):
    """The (m, k, n, JobSet) of every CONV (as im2col) and FC GEMM of one
    CIFAR_full call of ``frames`` frames, read off a shape-only trace."""
    from repro.configs.paper_cnns import CIFAR_FULL
    from repro.models.cnn import cnn_forward, init_cnn
    params = init_cnn(CIFAR_FULL, jax.random.key(0))
    x = jax.ShapeDtypeStruct((frames, CIFAR_FULL.input_hw,
                              CIFAR_FULL.input_hw, CIFAR_FULL.cin),
                             jnp.float32)
    tr = SynergyTrace()
    with tr.activate():
        jax.eval_shape(lambda x: cnn_forward(CIFAR_FULL, params, x), x)
    return [(js.m, js.k, js.n, js) for js in tr.jobsets]


def _check_plan(panels, m, ts_m, js):
    """The panels cover [0, m) once, in order; every panel but the last
    is a whole number of tile rows; their tile jobs are the JobSet's."""
    assert panels[0][0] == 0 and panels[-1][1] == m
    assert all(p[1] == q[0] for p, q in zip(panels, panels[1:]))
    assert all((r1 - r0) % ts_m == 0 for r0, r1 in panels[:-1])
    assert all(r1 > r0 for r0, r1 in panels)
    gn = js.grid[1]
    assert sum(-(-(r1 - r0) // ts_m) * gn for r0, r1 in panels) \
        == js.num_jobs


@pytest.mark.parametrize("layer", range(4))
@pytest.mark.parametrize("frames", [1, 32, 64])
def test_row_panels_one_panel_per_cifar_full_gemm_on_the_tpu(frames, layer):
    from repro.soc.runtime import row_panels
    gemms = _cifar_full_gemms(frames)
    assert len(gemms) == 4          # conv0, conv1, conv2, fc
    m, k, n, js = gemms[layer]
    panels = row_panels(m, k, n, js.ts_m, backend="tpu")
    assert panels == [(0, m)]
    _check_plan(panels, m, js.ts_m, js)


@pytest.mark.parametrize("backend", [None, "cpu", "gpu"])
@pytest.mark.parametrize("m,k,n,ts_m", [(300, 64, 48, 32),
                                         (65536, 75, 32, 32),
                                         (64, 1024, 10, 32),
                                         (1, 1024, 10, 32),
                                         (4096, 2048, 8192, 32)])
def test_row_panels_at_a_zero_floor_are_the_grid_rows(m, k, n, ts_m,
                                                      backend):
    from repro.soc.runtime import row_panels
    panels = row_panels(m, k, n, ts_m, backend=backend)
    assert panels == [(t * ts_m, min((t + 1) * ts_m, m))
                      for t in range(-(-m // ts_m))]
    _check_plan(panels, m, ts_m, JobSet.for_gemm(0, m, n, k, ts_m))


@pytest.mark.parametrize("m,rows", [(8192, 4096), (16384, 4096),
                                    (10000, 4096)])
def test_row_panels_split_a_gemm_above_the_floor(m, rows):
    # a granite prefill FFN GEMM (k 2048, n 8192): 32 x 2048 x 8192 MACs
    # per tile row, so 128 tile rows (4096 rows) reach the 6e10 floor
    from repro.soc.runtime import PANEL_FLOOR_MACS, row_panels
    k, n, ts_m = 2048, 8192, 32
    panels = row_panels(m, k, n, ts_m, backend="tpu")
    t = rows // ts_m
    assert t & (t - 1) == 0                              # a power of two
    assert t * ts_m * k * n >= PANEL_FLOOR_MACS["tpu"]
    assert t // 2 * ts_m * k * n < PANEL_FLOOR_MACS["tpu"]
    assert [r1 - r0 for r0, r1 in panels[:-1]] == [rows] * (len(panels) - 1)
    assert len(panels) == -(-m // rows) >= 2
    _check_plan(panels, m, ts_m, JobSet.for_gemm(0, m, n, k, ts_m))


@pytest.mark.parametrize("n_engines", [1, 3])
def test_panels_do_not_depend_on_the_pool_or_its_rates(monkeypatch,
                                                       n_engines):
    # a floor of 4 tile rows' MACs: a 32-row-tile GEMM of 512 rows
    # dispatches 4 panels of 128 rows, whatever engines run them and
    # however often their rates are recalibrated
    from repro.soc import runtime as rt_mod
    m, k, n, ts = 512, 32, 16, 32
    monkeypatch.setitem(rt_mod.PANEL_FLOOR_MACS, jax.default_backend(),
                        4 * ts * k * n)
    a, b = _ab(m, k, n, seed=4)
    js = JobSet.for_gemm(0, m, n, k, ts)
    engines = [_DelayEngine(f"r{i}", macs_per_s=(i + 1) * 1e9, seed=i,
                            max_delay_s=0.002) for i in range(n_engines)]
    with SynergyRuntime(engines, recalibrate_every=1) as rt:
        for _ in range(3):
            y = rt.submit_gemm(a, b, jobset=js,
                               tile=(ts, ts, ts)).result(60)
        st = rt.stats()
    assert st["total_panels"] == 3 * 4 == sum(e.executed for e in engines)
    assert st["total_jobs"] == 3 * js.num_jobs
    np.testing.assert_allclose(np.asarray(y), np.asarray(jnp.dot(a, b)),
                               rtol=1e-4, atol=1e-4)


def _cifar_calls(frames, n_calls=2):
    from repro.configs.paper_cnns import CIFAR_FULL
    from repro.models.cnn import init_cnn
    params = init_cnn(CIFAR_FULL, jax.random.key(3))
    xs = [jax.random.normal(jax.random.key(10 + i),
                            (frames, 32, 32, 3)) for i in range(n_calls)]
    return CIFAR_FULL, params, xs


def _run_cifar(pool, net, params, xs, job_class=None):
    from repro.models.cnn import cnn_forward
    with SynergyRuntime(pool()) as rt:
        ys = [np.asarray(cnn_forward(net, params, x, runtime=rt,
                                     job_class=job_class)) for x in xs]
        return ys, rt.stats()


class _RowExactEngine(Engine):
    """A GEMM whose every output row is rounded the same whatever rows
    come with it (a product and a sum over k, no blocked dot), so a
    split and the whole GEMM must agree bit for bit."""

    def __init__(self):
        super().__init__("row-exact", {CAP_GEMM, "epilogue"},
                         cost=CostModel(macs_per_s=1e9))

    def execute(self, a, b, *, bias=None, activation=None, tile=None,
                out_dtype=None, precision=None):
        y = (a[:, :, None] * b[None, :, :]).sum(1)
        if bias is not None:
            y = y + bias
        if activation is not None:
            y = activation(y)
        return y.astype(out_dtype or a.dtype)


def _int8_pool():
    from repro.quant.engine import QuantizedEngine
    return [QuantizedEngine(get_engine("xla"))]


def _forced_tpu_floor(monkeypatch):
    from repro.soc import runtime as rt_mod
    monkeypatch.setitem(rt_mod.PANEL_FLOOR_MACS, jax.default_backend(),
                        rt_mod.PANEL_FLOOR_MACS["tpu"])


@pytest.mark.parametrize("pool,job_class",
                         [(lambda: [_RowExactEngine()], None),
                          (_int8_pool, "decode")],
                         ids=["row-exact", "int8-decode"])
@pytest.mark.parametrize("frames", [1, 4])
def test_cnn_forward_under_the_tpu_floor_matches_the_tile_row_split(
        monkeypatch, frames, pool, job_class):
    net, params, xs = _cifar_calls(frames)
    split, st_split = _run_cifar(pool, net, params, xs, job_class)
    _forced_tpu_floor(monkeypatch)
    whole, st_whole = _run_cifar(pool, net, params, xs, job_class)
    for y0, y1 in zip(split, whole):
        assert np.array_equal(y0, y1)
    assert st_whole["total_jobs"] == st_split["total_jobs"]
    assert st_whole["total_panels"] == 4 * len(xs)
    gms = [-(-m // 32) for m, *_ in _cifar_full_gemms(frames)]
    assert st_split["total_panels"] == sum(gms) * len(xs)


@pytest.mark.parametrize("frames", [1, 4])
def test_cnn_forward_under_the_tpu_floor_is_the_engines_own_call(
        monkeypatch, frames):
    # On an xla pool a one-panel GEMM is the engine's call on the whole
    # GEMM.  Against the tile-row split it agrees to rounding only: XLA's
    # CPU dot blocks its contraction by the row count.
    from repro.models.cnn import cnn_forward
    net, params, xs = _cifar_calls(frames)
    split, st_split = _run_cifar(lambda: [get_engine("xla")], net, params,
                                 xs)
    _forced_tpu_floor(monkeypatch)
    whole, st_whole = _run_cifar(lambda: [get_engine("xla")], net, params,
                                 xs)
    for x, y0, y1 in zip(xs, split, whole):
        own = np.asarray(cnn_forward(net, params, x, engine="xla"))
        assert np.array_equal(y1, own)
        np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-5)
    assert st_whole["total_jobs"] == st_split["total_jobs"]
    assert st_whole["total_panels"] == 4 * len(xs)


def test_submit_span_is_tagged_with_the_panel_plan(monkeypatch, tmp_path):
    import glob
    import os
    from jax.profiler import ProfileData
    _forced_tpu_floor(monkeypatch)
    net, params, xs = _cifar_calls(2, n_calls=1)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _run_cifar(lambda: [get_engine("xla")], net, params, xs)
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                    "*", "*.xplane.pb"))
    tags = [dict(e.stats) for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name == "repro/runtime/submit"]
    ms = [m for m, *_ in _cifar_full_gemms(2)]
    assert [t["panels"] for t in tags] == [1] * 4
    assert [t["rows_per_panel"] for t in tags] == ms
