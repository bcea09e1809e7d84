"""Multi-tenant QoS: priorities, SLO deadlines, preemption at panel
granularity, admission/fairness/shedding, and self-healing engine pools.

Covers the PR's acceptance surface without the hypothesis dev-dependency
(see ``test_qos_props.py`` for the property sweeps):

  * pure policy units (:mod:`repro.soc.qos_policy`) — queue insertion,
    victim choice, effective deadlines, stride fair share;
  * :class:`repro.soc.qos.EngineHealth` lifecycle state machine;
  * live runtime placement: priority-sorted deques, deadline-aware seed
    order, QoS victim choice in ``_try_steal_locked``, and end-to-end
    priority completion ordering behind a gated worker;
  * live quarantine/readmission of a rate-degraded engine, and degraded
    placement when every engine is quarantined;
  * the live ``_seed_locked`` seed map of a tagged batch, pinned
    (shared-function identity asserted too);
  * serving tenancy: bounded queues + ``AdmissionRejected`` retry-after,
    weighted fair admission, the shed ladder's int8 degradation,
    per-tenant stats, and bitwise token parity of a tenanted server
    against the untenanted FIFO path on an unloaded pool.
"""

import math
import random
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.core.job import JobSet
from repro.core.serving import (Request, ServeTimeoutError, SynergyServer,
                                TenantStats)
from repro.engines import CAP_GEMM, CAP_INT8, CostModel, Engine, get_engine
from repro.models import init_model
from repro.soc import (AdmissionRejected, EngineHealth, FairShare,
                       HealthPolicy, QosClass, QosTag, SynergyRuntime,
                       Tenant, effective_deadline, qos_victim,
                       queue_insert_index)
from repro.soc.qos import BULK, DEFAULT_CLASS
from repro.soc.runtime import _RuntimeJob, _Submission

from _panels import panel_reference


def _cfg():
    return reduced(ARCHS["granite-3-2b"], n_layers=2, d_model=32,
                   n_heads=2, d_ff=64, vocab=128)


def _server(slots=2, **kw):
    cfg = _cfg()
    params = init_model(cfg, jax.random.key(0))
    return SynergyServer(cfg, params, slots=slots, max_len=32,
                         prefill_len=4, **kw)


# ------------------------------------------------------------ policy units

def test_queue_insert_index():
    # all-neutral queue: plain append (the pre-QoS behavior)
    assert queue_insert_index([0, 0, 0], 0) == 3
    assert queue_insert_index([], 5) == 0
    # ahead of strictly lower priority, behind peers (FIFO within class)
    assert queue_insert_index([10, 10, 0, -5], 10) == 2
    assert queue_insert_index([10, 0], 5) == 1
    assert queue_insert_index([10, 5, 0], -1) == 3


def test_qos_victim_prefers_lowest_tail_priority():
    # bulk tail (-10) wins over a busier neutral queue
    assert qos_victim([0, -10, 0], [5, 3, 4]) == 1
    # ties on tail priority fall back to the busiest (pick_victim)
    assert qos_victim([0, 0, 0], [2, 7, 4]) == 1
    assert qos_victim([3], [1]) == 0


def test_effective_deadline():
    assert effective_deadline(10.0, 2.5) == 7.5
    assert effective_deadline(math.inf, 1.0) == math.inf


def test_fair_share_weighted_picks():
    fs = FairShare()
    counts = {"a": 0, "b": 0}
    cands = [("a", 0, math.inf, 4.0), ("b", 0, math.inf, 1.0)]
    for _ in range(10):
        name = fs.pick(cands)
        counts[name] += 1
        fs.charge(name, 4.0 if name == "a" else 1.0)
    # stride scheduling: 4x weight -> 4x the admissions
    assert counts == {"a": 8, "b": 2}


def test_fair_share_priority_trumps_virtual_time():
    fs = FairShare()
    fs.charge("hi", 1.0)          # hi has spent credit already
    picked = fs.pick([("hi", 10, math.inf, 1.0),
                      ("lo", 0, math.inf, 1.0)])
    assert picked == "hi"


def test_fair_share_idle_tenant_rejoins_at_floor():
    fs = FairShare()
    for _ in range(5):
        fs.charge("busy", 1.0)
    # a late joiner enters at the current minimum, not at 0 credit-hoard
    fs.pick([("busy", 0, math.inf, 1.0), ("late", 0, math.inf, 1.0)])
    assert fs.snapshot()["late"] == pytest.approx(
        min(5.0, fs.snapshot()["busy"]))


# ----------------------------------------------------- EngineHealth units

def test_engine_health_lifecycle():
    pol = HealthPolicy(alpha=0.5, quarantine_below=0.5, readmit_above=0.8,
                       min_samples=3, probe_interval_s=0.25,
                       min_probe_samples=2)
    h = EngineHealth()
    assert h.health == 1.0                  # no data: presumed healthy
    h.observe(100.0, pol)                   # first sample seeds the EMA
    assert h.ema_rate == 100.0 and h.baseline == 100.0
    h.observe(100.0, pol)
    assert not h.should_quarantine(pol)     # min_samples gate (2 < 3)
    h.observe(10.0, pol)                    # ema -> 55: above threshold
    assert not h.should_quarantine(pol)
    h.observe(10.0, pol)                    # ema -> 32.5 < 50
    assert h.should_quarantine(pol)
    h.enter_quarantine(now=100.0)
    assert h.quarantined and h.quarantines == 1
    assert not h.probe_due(100.1, pol)      # probe cadence
    assert h.probe_due(100.3, pol)
    h.observe(100.0, pol)                   # probe 1: ema -> 66.25
    assert not h.recovered(pol)             # min_probe_samples gate
    h.observe(100.0, pol)                   # probe 2: ema -> 83.1 >= 80
    assert h.recovered(pol)
    h.exit_quarantine()
    assert not h.quarantined and h.probe_samples == 0
    # baseline was NOT raised by quarantine probes
    assert h.baseline == 100.0


# ------------------------------------------------- runtime placement units

class _Plain(Engine):
    def __init__(self, name, macs_per_s=1e9):
        super().__init__(name, {CAP_GEMM, "epilogue"},
                         cost=CostModel(macs_per_s=macs_per_s))

    def execute(self, a, b, *, bias=None, activation=None, tile=None,
                out_dtype=None, precision=None):
        y = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        return y.astype(out_dtype or a.dtype)


def _job(sub, index, priority=0, deadline_at=math.inf, macs=1000):
    return _RuntimeJob(sub, index, None, 1, macs, 0, priority=priority,
                       deadline_at=deadline_at)


def test_enqueue_keeps_queue_priority_sorted():
    from collections import deque
    js = JobSet.for_gemm(0, 32, 32, 32, 32)
    sub = _Submission(js, 6, None)
    q: deque = deque()
    for i, prio in enumerate([0, 0, 10, -5, 10, 3]):
        SynergyRuntime._enqueue(q, _job(sub, i, prio))
    prios = [j.priority for j in q]
    assert prios == sorted(prios, reverse=True)
    # FIFO within class: the two priority-10 jobs keep submission order
    tens = [j.index for j in q if j.priority == 10]
    assert tens == [2, 4]


def test_seed_order_neutral_is_identity_else_edf():
    js = JobSet.for_gemm(0, 32, 32, 32, 32)
    sub = _Submission(js, 4, None)
    neutral = [_job(sub, i) for i in range(4)]
    # all-neutral: the SAME sequence comes back (bitwise pre-QoS parity)
    assert SynergyRuntime._seed_order(neutral, 1e9) is neutral
    jobs = [_job(sub, 0, priority=0, deadline_at=math.inf),
            _job(sub, 1, priority=10, deadline_at=5.0, macs=2000),
            _job(sub, 2, priority=10, deadline_at=4.0, macs=1000),
            _job(sub, 3, priority=-10)]
    got = [j.index for j in SynergyRuntime._seed_order(jobs, 1e9)]
    # priority desc; within the 10s, earliest EFFECTIVE deadline first
    assert got == [2, 1, 0, 3]


def test_try_steal_picks_lowest_priority_tail_victim():
    e = [_Plain(f"v{i}") for i in range(3)]
    rt = SynergyRuntime(e)          # never started: queues are ours
    js = JobSet.for_gemm(0, 32, 32, 32, 32)
    sub = _Submission(js, 8, None)
    ws = list(rt._workers.values())
    # thief = ws[0] (empty); ws[1] busier but neutral; ws[2] holds bulk
    for i in range(3):
        ws[1].queue.append(_job(sub, i, priority=0))
    for i in range(2):
        ws[2].queue.append(_job(sub, 3 + i, priority=-10))
    stolen = rt._try_steal_locked(ws[0])
    assert stolen is not None and stolen.priority == -10
    assert stolen.index == 4        # the TAIL of the bulk victim
    assert len(ws[2].queue) == 1 and len(ws[1].queue) == 3


def test_priority_completion_order_behind_gated_worker():
    """With one worker blocked mid-panel, an interactive submission that
    arrives AFTER a bulk one still finishes first: its panels enter the
    queue ahead of the queued bulk panels (preemption at panel
    granularity — the in-flight panel itself is never killed)."""
    gate = threading.Event()
    seen: list[int] = []

    class _GateEngine(_Plain):
        def execute(self, a, b, **kw):
            if a.shape[1] == 4:          # the gate GEMM: k == 4
                gate.wait(30)
            seen.append(a.shape[1])
            return super().execute(a, b, **kw)

    eng = _GateEngine("gated")
    k_bulk, k_inter = 8, 12
    with SynergyRuntime([eng], name="gate") as rt:
        a_gate = jnp.ones((16, 4)); b_gate = jnp.ones((4, 8))
        f0 = rt.submit_gemm(a_gate, b_gate,
                            jobset=JobSet.for_gemm(0, 16, 8, 4, 16),
                            tile=(16, 16, 16))
        time.sleep(0.2)                  # worker is inside the gate panel
        a_b = jnp.ones((48, k_bulk)); b_b = jnp.ones((k_bulk, 8))
        fb = rt.submit_gemm(a_b, b_b,
                            jobset=JobSet.for_gemm(1, 48, 8, k_bulk, 16),
                            tile=(16, 16, 16), qos=QosTag(-10))
        a_i = jnp.ones((48, k_inter)); b_i = jnp.ones((k_inter, 8))
        fi = rt.submit_gemm(a_i, b_i,
                            jobset=JobSet.for_gemm(2, 48, 8, k_inter, 16),
                            tile=(16, 16, 16), qos=QosTag(10))
        gate.set()
        for f in (f0, fb, fi):
            f.result(60)
    assert seen[0] == 4
    # every interactive panel ran before every bulk panel
    assert seen[1:4] == [k_inter] * 3 and seen[4:] == [k_bulk] * 3


# ------------------------------------------------- live self-healing pool

class _SickEngine(_Plain):
    """Wall-clock paced engine with a MUTABLE per-panel delay — flip
    ``delay_s`` to simulate a thermal-throttled / failing accelerator."""

    def __init__(self, name, delay_s):
        super().__init__(name, macs_per_s=1e9)
        self.delay_s = delay_s

    def execute(self, a, b, **kw):
        time.sleep(self.delay_s)
        return super().execute(a, b, **kw)


def _gemm(rt, step, m=16, affinity=None):
    a = jnp.ones((m, 32)); b = jnp.ones((32, 16))
    return rt.submit_gemm(a, b,
                          jobset=JobSet.for_gemm(step, m, 16, 32, 16),
                          tile=(16, 16, 16), affinity=affinity)


def test_quarantine_and_readmission_lifecycle():
    pol = HealthPolicy(alpha=0.5, quarantine_below=0.5, readmit_above=0.6,
                       min_samples=3, probe_interval_s=0.05,
                       min_probe_samples=2)
    sick = _SickEngine("sick", delay_s=0.008)
    buddy = _SickEngine("buddy", delay_s=0.008)
    with SynergyRuntime([sick, buddy], name="heal", health=pol) as rt:
        # phase 1: establish a healthy baseline on both workers (four
        # panels a GEMM: with one, the buddy can win every race for it and
        # leave "sick" no baseline to fall from)
        for s in range(8):
            _gemm(rt, s, m=64, affinity="sick").result(30)
        assert not rt.stats()["engines"]["sick"]["quarantined"]

        # phase 2: the sick engine degrades 15x -> quarantine
        sick.delay_s = 0.12
        deadline = time.monotonic() + 30
        step = 100
        while not rt.stats()["engines"]["sick"]["quarantined"]:
            assert time.monotonic() < deadline, "never quarantined"
            _gemm(rt, step, affinity="sick").result(30)
            step += 1
        st = rt.stats()
        assert st["quarantines"] >= 1
        assert st["engines"]["sick"]["health"] < 1.0
        assert sick.telemetry.snapshot().quarantines >= 1
        rebalances_at_quarantine = st["rebalances"]
        assert rebalances_at_quarantine >= 1    # deque drained to buddy

        # quarantined worker takes no seeds: fresh work lands on buddy
        before = rt.stats()["engines"]["buddy"]["jobs"]
        _gemm(rt, step, affinity="sick").result(30)
        step += 1
        assert rt.stats()["engines"]["buddy"]["jobs"] > before

        # phase 3: engine recovers; probation probes re-admit it
        sick.delay_s = 0.008
        deadline = time.monotonic() + 60
        while rt.stats()["engines"]["sick"]["quarantined"]:
            assert time.monotonic() < deadline, "never re-admitted"
            # deep buddy queue so the probe steal passes the tail guard
            _gemm(rt, step, m=64, affinity="buddy").result(60)
            step += 1
        assert rt.stats()["rebalances"] > rebalances_at_quarantine


def test_health_none_keeps_stats_shape():
    with SynergyRuntime([_Plain("nh")], name="nohealth") as rt:
        _gemm(rt, 0).result(30)
        st = rt.stats()
    assert st["quarantines"] == 0
    assert st["engines"]["nh"]["health"] is None
    assert st["engines"]["nh"]["quarantined"] is False


@pytest.mark.parametrize("int8_buddy", [False, True],
                         ids=["all-fp32", "int8-buddy"])
def test_submission_completes_with_every_engine_quarantined(int8_buddy):
    """With every eligible engine quarantined the seed falls back to
    degraded placement: the panels land on the quarantined eligible
    engines (never on a healthy int8 engine an fp32 GEMM may not use) and
    the GEMM completes, bitwise the per-panel reference."""
    from repro.quant import QuantizedEngine
    pool = [_Plain("q0"), _Plain("q1")]
    if int8_buddy:
        pool[1] = QuantizedEngine(get_engine("xla"), name="q-int8")
    fp32 = {e.name for e in pool if CAP_INT8 not in e.capabilities}
    pol = HealthPolicy(min_probe_samples=10**9)      # no readmission
    a = jax.random.normal(jax.random.key(0), (64, 32))
    b = jax.random.normal(jax.random.key(1), (32, 16))
    js = JobSet.for_gemm(0, 64, 16, 32, 16)
    with SynergyRuntime(pool, name="allq", health=pol) as rt:
        with rt._cond:
            for n in fp32:
                rt._workers[n].health.enter_quarantine(time.monotonic())
        fut = rt.submit_gemm(a, b, jobset=js, tile=(16, 16, 16))
        y = fut.result(60)
        st = rt.stats()
    assert all(st["engines"][n]["quarantined"] for n in fp32)
    assert set(fut.accounting) <= fp32
    assert sum(x["jobs"] for x in fut.accounting.values()) == js.num_jobs
    assert np.array_equal(np.asarray(y), np.asarray(
        panel_reference(_Plain("solo"), a, b, 16)))


# ----------------------------------------------------- shared QoS policy

def test_qos_functions_are_shared_objects():
    import repro.soc.policy as policy
    import repro.soc.qos_policy as qp
    import repro.soc.runtime as runtime
    assert runtime.qos_victim is qp.qos_victim
    assert runtime.queue_insert_index is qp.queue_insert_index
    assert runtime.effective_deadline is qp.effective_deadline
    assert runtime.lpt_pick is policy.lpt_pick


def test_seed_map_conforms_to_live_seeding():
    """The live ``_seed_locked`` places a tagged batch by deadline sort,
    LPT pick and priority insertion.  The expected map is the one the
    virtual-time replay of the same batch gave for the same cost models,
    written out here as a literal."""
    subs = [
        (JobSet.for_gemm(0, 128, 64, 32, 32, name="bulk"), QosTag(-10)),
        (JobSet.for_gemm(1, 64, 64, 32, 32, name="hot"), QosTag(10, 0.5)),
        (JobSet.for_gemm(2, 96, 64, 32, 32, name="mid"), None),
    ]
    F, S = "F-PE", "S-PE"
    expected = ((S, F, F, S, F, F, S, F),
                (F, S, F, F),
                (S, F, F, S, F, F))

    rt = SynergyRuntime(["F-PE", "S-PE"])      # never started
    jobs, sids = [], []
    from repro.soc.qos_policy import NEUTRAL_TAG
    for sid, (js, tag) in enumerate(subs):
        tag = tag or NEUTRAL_TAG
        units = rt._accounting_units(js, "job")
        sub = _Submission(js, len(units), None)
        for i, (fn, n_jobs, macs, nbytes) in enumerate(units):
            jobs.append(_RuntimeJob(sub, i, fn, n_jobs, macs, nbytes,
                                    priority=tag.priority,
                                    deadline_at=tag.deadline_at))
            sids.append(sid)
        sub._sid = sid
    rt._seed_locked(jobs, affinity=None)
    live = [[None] * len(expected[s]) for s in range(len(subs))]
    for name, w in rt._workers.items():
        for job in w.queue:
            live[job.sub._sid][job.index] = name
    assert tuple(tuple(m) for m in live) == expected


# -------------------------------------------------------- serving tenancy

GOLD = QosClass("gold", priority=10, deadline_s=120.0, weight=4.0)


def _reqs(n, tenant=None, base=0, max_new=3):
    return [Request(base + i, jnp.arange(4, dtype=jnp.int32) + i,
                    max_new_tokens=max_new, tenant=tenant)
            for i in range(n)]


def test_tenanted_server_end_to_end_stats():
    srv = _server(slots=2, tenants=[Tenant("gold", GOLD),
                                    Tenant("bulk", BULK)])
    reqs = _reqs(2, "gold") + _reqs(3, "bulk", base=10)
    for r in reqs:
        srv.submit(r)
    stats = srv.run()
    assert all(len(r.out) >= 3 for r in reqs)
    assert all(r.done_at is not None for r in reqs)
    g, b = stats.tenants["gold"], stats.tenants["bulk"]
    assert g.admitted == 2 and b.admitted == 3
    assert g.prefills == 2 and b.prefills == 3
    assert g.tokens_out + b.tokens_out == stats.tokens_out
    assert g.queue_wait_s >= 0 and g.max_queue_wait_s >= 0
    # gold's 120 s deadline: every completion is accounted, all hits
    assert g.deadline_hits + g.deadline_misses == 2
    assert g.deadline_attainment == 1.0
    # bulk has no deadline: vacuous attainment
    assert b.deadline_hits == b.deadline_misses == 0
    assert b.deadline_attainment == 1.0


def test_unknown_tenant_and_constructor_validation():
    srv = _server(slots=2, tenants=[Tenant("a")])
    with pytest.raises(KeyError, match="unknown tenant"):
        srv.submit(Request(0, jnp.arange(4, dtype=jnp.int32), 2,
                           tenant="nope"))
    with pytest.raises(ValueError, match="duplicate tenant"):
        _server(tenants=[Tenant("a"), Tenant("a")])
    with pytest.raises(ValueError, match="tenants"):
        _server(tenants=[])


def test_bounded_queue_rejects_with_retry_after():
    srv = _server(slots=2, tenants=[Tenant("t", DEFAULT_CLASS,
                                           max_pending=2)])
    for r in _reqs(2, "t"):
        srv.submit(r)
    with pytest.raises(AdmissionRejected) as ei:
        srv.submit(Request(9, jnp.arange(4, dtype=jnp.int32), 2,
                           tenant="t"))
    assert ei.value.tenant == "t"
    assert ei.value.retry_after_s > 0
    assert "retry after" in str(ei.value)
    assert srv.stats.admission_rejects == 1
    assert srv.stats.tenants["t"].rejected == 1


def test_untenanted_global_max_pending_bound():
    srv = _server(slots=2, max_pending=1)
    srv.submit(Request(0, jnp.arange(4, dtype=jnp.int32), 2))
    with pytest.raises(AdmissionRejected):
        srv.submit(Request(1, jnp.arange(4, dtype=jnp.int32), 2))
    assert srv.stats.admission_rejects == 1
    # the real mutable legacy list is still exposed
    srv.pending.clear()
    srv.submit(Request(2, jnp.arange(4, dtype=jnp.int32), 2))
    assert len(srv.pending) == 1


def test_pending_property_tenanted_snapshot():
    srv = _server(slots=2, tenants=[Tenant("a"), Tenant("b")])
    for r in _reqs(2, "a") + _reqs(1, "b", base=10):
        srv.submit(r)
    assert len(srv.pending) == 3
    assert {r.tenant for r in srv.pending} == {"a", "b"}


def test_weighted_fair_admission_order():
    srv = _server(slots=2, tenants=[Tenant("gold", GOLD),
                                    Tenant("bulk", BULK)])
    for r in _reqs(8, "gold") + _reqs(8, "bulk", base=100):
        srv.submit(r)
    picked = srv._pick_requests(10)
    # peek only: nothing popped
    assert len(srv.pending) == 16
    names = [n for n, _ in picked]
    # gold outranks bulk by priority: admitted first while it has work
    assert names[:8] == ["gold"] * 8
    assert names[8:] == ["bulk"] * 2


def test_shed_ladder_engages_and_degrades_decode():
    """Under queue pressure the ladder degrades SHEDDABLE tenants' decode
    to the int8-only job class BEFORE anything is rejected."""
    from repro.quant import QuantizedEngine
    pool = [get_engine("F-PE"),
            QuantizedEngine(get_engine("xla"), name="int8-shed")]
    with SynergyRuntime(pool, name="shed") as rt:
        srv = _server(slots=2, runtime=rt,
                      tenants=[Tenant("bulk", BULK, max_pending=4)])
        for r in _reqs(4, "bulk", max_new=3):
            srv.submit(r)
        with pytest.raises(AdmissionRejected):
            srv.submit(Request(99, jnp.arange(4, dtype=jnp.int32), 3,
                               tenant="bulk"))
        assert srv.stats.shed_engagements == 1     # 80% watermark crossed
        stats = srv.run()
    assert stats.shed_degraded_steps > 0
    assert stats.tenants["bulk"].degraded_steps > 0


def test_serve_timeout_error_carries_identity():
    err = ServeTimeoutError("decode/s3", 1.5, {"F-PE": {"jobs": 2}},
                            rids=(7, 8), tenants=("gold", "", "bulk"))
    assert err.rids == (7, 8)
    assert err.tenants == ("gold", "bulk")
    msg = str(err)
    assert "rids=[7, 8]" in msg and "'bulk'" in msg and "'gold'" in msg
    bare = ServeTimeoutError("x", 1.0, {})
    assert "rids" not in str(bare)


def test_tenanted_matches_untenanted_tokens_bitwise():
    """QoS must be a SCHEDULING layer only: on an unloaded pool a
    default-class tenanted server produces bitwise-identical token
    streams (and decode GEMM outputs) to the untenanted FIFO server."""
    def run(tenants):
        with SynergyRuntime(["F-PE", "S-PE"], name="parity") as rt:
            srv = _server(slots=2, runtime=rt, tenants=tenants,
                          keep_decode_outputs=True)
            tname = tenants[0].name if tenants else None
            reqs = [Request(i, jnp.arange(4, dtype=jnp.int32) + i,
                            max_new_tokens=4, tenant=tname)
                    for i in range(4)]
            for r in reqs:
                srv.submit(r)
            srv.run()
            return [list(r.out) for r in reqs], srv.decode_gemm_outputs

    toks_fifo, outs_fifo = run(None)
    toks_qos, outs_qos = run([Tenant("default")])
    assert toks_qos == toks_fifo
    assert len(outs_qos) == len(outs_fifo)
    for a, b in zip(outs_fifo, outs_qos):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_deadline_misses_are_counted():
    srv = _server(slots=2,
                  tenants=[Tenant("t", QosClass("t", deadline_s=0.0))])
    for r in _reqs(2, "t"):
        srv.submit(r)
    stats = srv.run()
    ts = stats.tenants["t"]
    assert ts.deadline_misses == 2 and ts.deadline_hits == 0
    assert ts.deadline_attainment == 0.0


def test_tenant_stats_attainment_empty():
    assert TenantStats().deadline_attainment == 1.0


# --------------------------------------- seeded deterministic QoS sweep

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_random_qos_tags_conserve_work(seed):
    """Accounting waves under random priorities/deadlines: every future
    completes and every tile job is booked exactly once (the no-
    hypothesis twin of the property sweep in test_qos_props.py)."""
    rng = random.Random(seed)
    with SynergyRuntime(["F-PE", "S-PE", "NEON"], name=f"sweep{seed}") \
            as rt:
        futs, total = [], 0
        for w in range(4):
            jobsets = [JobSet.for_gemm(w * 10 + i, 32 * rng.randint(1, 4),
                                       64, 32, 32, name=f"w{w}j{i}")
                       for i in range(3)]
            tag = QosTag(rng.choice([-10, 0, 10]),
                         rng.choice([math.inf, 5.0]))
            futs.extend(rt.submit_many(jobsets, qos=tag))
            total += sum(js.num_jobs for js in jobsets)
        for f in futs:
            f.result(60)
            assert sum(a["jobs"] for a in f.accounting.values()) \
                == f.jobset.num_jobs
        assert rt.stats()["total_jobs"] == total
