"""repro.obs: tracer, metrics, flight recorder, and instrumentation
invariants (ISSUE 8)."""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.core.job import JobSet
from repro.core.serving import Request, ServeTimeoutError, SynergyServer
from repro.engines import CAP_GEMM, CostModel, Engine, Telemetry
from repro.models import init_model
from repro.obs import (EVENT_KINDS, FlightRecorder, MetricsRegistry, Tracer,
                       load_chrome_trace, parse_prometheus,
                       render_prometheus, validate_events)
from repro.obs.metrics import Histogram
from repro.obs.trace import (TraceEvent, get_default_tracer,
                             set_default_tracer, trace_scope)
from repro.soc import HealthPolicy, SynergyRuntime, Tenant
from repro.soc.qos import QosClass
from repro.soc.simrt import SimRuntime


# ------------------------------------------------------------ tracer core

def test_tracer_ring_keeps_newest_and_counts_drops():
    tr = Tracer(capacity=10, flush_every=1)
    for i in range(25):
        tr.emit("seed", "manager", ts=float(i), n=i)
    evs = tr.events()
    assert len(evs) == 10
    assert [e.tags["n"] for e in evs] == list(range(15, 25))
    assert tr.dropped == 15


def test_tracer_thread_local_cells_all_flush():
    tr = Tracer(capacity=100_000)

    def worker(tid):
        for i in range(500):
            tr.emit("enqueue", f"eng{tid}", ts=float(i), i=i)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tr.events()     # flushes every cell, including partial chunks
    assert len(evs) == 2000
    # stable order: (ts, seq) — same-ts events keep emission order
    assert [e.ts for e in evs] == sorted(e.ts for e in evs)


def test_tracer_span_and_validate():
    tr = Tracer()
    tr.span("panel", "e0", 1.0, 0.5, jobset="j0", n_jobs=2)
    evs = tr.events()
    assert [e.kind for e in evs] == ["panel_start", "panel_end"]
    assert evs[0].dur == 0.5 and evs[1].ts == 1.5
    assert validate_events(evs) == []


def test_validate_catches_broken_invariants():
    bad = [TraceEvent(0.0, "panel_end", "e0"),
           TraceEvent(1.0, "panel_start", "e0"),
           TraceEvent(2.0, "steal", "e0", tags={"victim": "e0"}),
           TraceEvent(3.0, "steal", "e1", tags={"victim": "ghost"}),
           TraceEvent(4.0, "nonsense", "e0")]
    errs = validate_events(bad, engines={"e0", "e1"})
    assert len(errs) == 5
    assert any("without panel_start" in e for e in errs)
    assert any("unmatched panel_start" in e for e in errs)
    assert any("steal from self" in e for e in errs)
    assert any("ghost" in e for e in errs)
    assert any("unknown event kind" in e for e in errs)


def test_default_tracer_scope():
    assert get_default_tracer() is None
    tr = Tracer()
    with trace_scope(tr):
        assert get_default_tracer() is tr
    assert get_default_tracer() is None


# ------------------------------------------------- runtime event round-trip

@pytest.fixture
def traced_burst(tmp_path):
    """A 3-engine pool with everything seeded on one engine (forced
    steals), exported to a Chrome trace and parsed back."""
    tr = Tracer(capacity=100_000)
    a, b = jnp.ones((128, 32)), jnp.ones((32, 32))
    with SynergyRuntime(["F-PE", "S-PE", "NEON"], name="obs-rt",
                        tracer=tr) as rt:
        futs = [rt.submit_gemm(
            a, b, jobset=JobSet.for_gemm(s, 128, 32, 32, 32,
                                         name=f"burst{s}"),
            tile=(32, 32, 32), affinity="F-PE") for s in range(10)]
        for f in futs:
            f.result(60)
        stats = rt.stats()
    path = tmp_path / "trace.json"
    n = tr.export_chrome_trace(str(path))
    assert n > 0
    return tr, stats, path


def test_runtime_trace_round_trip_and_replay_invariants(traced_burst):
    tr, stats, path = traced_burst
    engines = {"F-PE", "S-PE", "NEON"}
    live = tr.events()
    assert validate_events(live, engines=engines) == []
    counts = tr.counts()
    # every panel executed exactly once: starts == ends == dequeues+steals
    assert counts["panel_start"] == counts["panel_end"]
    assert counts["panel_start"] == counts["dequeue"] + counts["steal"]
    assert counts["steal"] > 0          # affinity burst forces stealing
    # steal events agree with the runtime's own accounting
    assert counts["steal"] == sum(
        es["steals"] for es in stats["engines"].values())

    # export -> parse -> same invariants hold on the parsed stream
    parsed = load_chrome_trace(str(path))
    assert validate_events(parsed, engines=engines) == []
    assert (sum(1 for e in parsed if e.kind == "steal")
            == counts["steal"])
    # panel spans survive with durations and jobset tags
    spans = [e for e in parsed if e.kind == "panel_start"]
    assert spans and all(e.dur is not None and e.dur >= 0 for e in spans)
    assert all(e.tags.get("jobset", "").startswith("burst") for e in spans)


def test_chrome_trace_structure(traced_burst):
    _, _, path = traced_burst
    with open(path) as f:
        data = json.load(f)
    evs = data["traceEvents"]
    # metadata names one row per track, engines included
    names = {d["args"]["name"] for d in evs
             if d.get("ph") == "M" and d.get("name") == "thread_name"}
    assert {"F-PE", "S-PE", "NEON", "manager"} <= names
    phases = {d["ph"] for d in evs}
    assert "X" in phases and "i" in phases and "M" in phases
    assert all(d["ts"] >= 0 for d in evs if d["ph"] != "M")


# ------------------------------------------------------- sim conformance

def test_sim_trace_same_schema_as_live():
    """The virtual-time twin emits the live schema: same kinds, same tag
    keys on panel/steal events, virtual stamps from 0."""
    js = JobSet.for_gemm(0, 256, 64, 64, 32, name="simjob")
    sim = SimRuntime(["F-PE", "S-PE"], tracer=Tracer(capacity=10_000))
    res = sim.run(js, affinity="F-PE")
    evs = sim.tracer.events()
    assert evs and {e.kind for e in evs} <= EVENT_KINDS
    assert validate_events(evs, engines={"F-PE", "S-PE"}) == []
    assert min(e.ts for e in evs) == 0.0
    assert max(e.ts for e in evs) == pytest.approx(res.makespan_s)
    panel = next(e for e in evs if e.kind == "panel_start")
    assert {"jobset", "n_jobs", "stolen", "priority"} <= set(panel.tags)
    steals = [e for e in evs if e.kind == "steal"]
    assert len(steals) == res.total_steals
    for s in steals:
        assert {"victim", "jobset", "priority", "probe"} <= set(s.tags)

    # live trace of the same workload: kind vocabulary is identical and
    # per-kind tag keys match, so the two traces are diffable
    lt = Tracer(capacity=10_000)
    with SynergyRuntime(["F-PE", "S-PE"], name="conf", tracer=lt) as rt:
        rt.submit_gemm(jnp.ones((256, 64)), jnp.ones((64, 64)),
                       jobset=js, tile=(32, 32, 32),
                       affinity="F-PE").result(60)
    live = lt.events()

    def tag_keys(events):
        out = {}
        for e in events:
            out.setdefault(e.kind, set()).update(e.tags)
        return out

    sim_keys, live_keys = tag_keys(evs), tag_keys(live)
    for kind in set(sim_keys) & set(live_keys):
        assert sim_keys[kind] <= live_keys[kind] | {"runtime"}, kind


def test_graph_trace_has_node_events():
    mk = lambda i: JobSet.for_gemm(i, 64, 32, 32, 32, name=f"n{i}")
    tr = Tracer()
    with SynergyRuntime(["F-PE", "S-PE"], name="obs-graph",
                        tracer=tr) as rt:
        gf = rt.submit_graph([mk(0), mk(1), mk(2)], [(0, 1), (0, 2)],
                             name="fork")
        gf.result(60)
    counts = tr.counts()
    assert counts["graph_node_ready"] == 3
    assert counts["graph_node_done"] == 3
    done_ts = {e.tags["node"]: e.ts for e in tr.events()
               if e.kind == "graph_node_done"}
    assert done_ts[0] <= done_ts[1] and done_ts[0] <= done_ts[2]
    assert gf.finish_order[0] == 0


# ----------------------------------------------------------- metrics

def test_metrics_render_and_parse_round_trip():
    reg = MetricsRegistry()
    reg.counter("obs_test_total", "a counter").inc(3)
    reg.gauge("obs_test_depth", "a gauge", ("engine",)).labels("e0").set(2.5)
    h = reg.histogram("obs_test_wait_seconds", "a histogram",
                      buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.render()
    parsed = parse_prometheus(text)
    assert parsed["obs_test_total"] == [({}, 3.0)]
    assert parsed["obs_test_depth"] == [({"engine": "e0"}, 2.5)]
    buckets = {lb["le"]: v for lb, v in parsed["obs_test_wait_seconds_bucket"]}
    assert buckets == {"0.1": 1.0, "1": 2.0, "+Inf": 3.0}   # cumulative
    assert parsed["obs_test_wait_seconds_count"] == [({}, 3.0)]
    assert parsed["obs_test_wait_seconds_sum"][0][1] == pytest.approx(5.55)


def test_metrics_type_conflict_rejected():
    reg = MetricsRegistry()
    reg.counter("obs_conflict")
    with pytest.raises(ValueError, match="re-registered"):
        reg.gauge("obs_conflict")
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad name!")


def test_histogram_observe_is_allocation_free():
    h = Histogram(buckets=(1.0, 2.0))
    h.observe(0.5)
    import tracemalloc
    tracemalloc.start()
    for _ in range(100):
        h.observe(1.5)
    current, _ = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert current < 512        # bookkeeping noise only, no per-obs allocs
    assert h.count == 101


def test_render_prometheus_covers_runtime_views():
    reg = MetricsRegistry()
    with SynergyRuntime(["F-PE", "S-PE"], name="obs-m") as rt:
        rt.submit_gemm(jnp.ones((64, 32)), jnp.ones((32, 32)),
                       jobset=JobSet.for_gemm(0, 64, 32, 32, 32),
                       tile=(32, 32, 32)).result(30)
        text = render_prometheus(runtime=rt, registry=reg)
    parsed = parse_prometheus(text)
    for name in ("repro_engine_queue_depth", "repro_engine_jobs_total",
                 "repro_engine_steals_total", "repro_engine_busy_fraction",
                 "repro_runtime_steal_rate",
                 "repro_runtime_submissions_total"):
        assert name in parsed, name
    engines = {lb["engine"] for lb, _ in parsed["repro_engine_jobs_total"]}
    assert engines == {"F-PE", "S-PE"}
    total = sum(v for _, v in parsed["repro_engine_jobs_total"])
    assert total == rt.stats()["total_jobs"]


# ------------------------------------------ serving: parity + flight rec

def _cfg():
    return reduced(ARCHS["granite-3-2b"], n_layers=2, d_model=32,
                   n_heads=2, d_ff=64, vocab=128)


def _serve_tokens(tracer, metrics=None):
    from repro.models.cnn import CNNConfig
    tiny = CNNConfig(name="tiny", input_hw=8, cin=1, layers=(
        ("conv", 4, 3, 1, 1), ("pool", 2), ("fc", 10)))
    cfg = _cfg()
    params = init_model(cfg, jax.random.key(0))
    with SynergyRuntime(["F-PE", "S-PE"], name="obs-parity",
                        tracer=tracer) as rt:
        srv = SynergyServer(cfg, params, slots=2, max_len=32,
                            prefill_len=4, runtime=rt, prefill_cnn=tiny,
                            keep_decode_outputs=True, max_inflight=1,
                            metrics=metrics)
        reqs = [Request(i, jnp.arange(4, dtype=jnp.int32) + i,
                        max_new_tokens=5) for i in range(4)]
        for r in reqs:
            srv.submit(r)
        srv.run()
    return [list(r.out) for r in reqs], srv.decode_gemm_outputs


def test_disabled_tracer_bitwise_parity_on_token_streams():
    """Tracing is observation only: tokens AND raw decode GEMM outputs
    are bitwise identical with a tracer attached and with none."""
    toks_off, outs_off = _serve_tokens(None)
    toks_on, outs_on = _serve_tokens(Tracer(capacity=200_000))
    assert toks_off == toks_on
    assert len(outs_off) == len(outs_on) > 0
    for ya, yb in zip(outs_off, outs_on):
        assert np.array_equal(np.asarray(ya), np.asarray(yb))


class _StuckEngine(Engine):
    """Sleeps far past the server's submit_timeout."""

    def __init__(self, name="stuck"):
        super().__init__(name, {CAP_GEMM, "epilogue"},
                         cost=CostModel(macs_per_s=1e9))

    def execute(self, a, b, **kw):
        time.sleep(2.0)
        return jnp.zeros((a.shape[0], b.shape[1]), a.dtype)


def test_flight_recorder_dumps_on_forced_timeout(tmp_path):
    from repro.models.cnn import CNNConfig
    tiny = CNNConfig(name="tiny", input_hw=8, cin=1, layers=(
        ("conv", 4, 3, 1, 1), ("fc", 10)))
    cfg = _cfg()
    params = init_model(cfg, jax.random.key(0))
    tr = Tracer(capacity=10_000)
    rec = FlightRecorder(tr, dir=str(tmp_path), last_n=64)
    with SynergyRuntime([_StuckEngine()], name="obs-stuck",
                        tracer=tr, flight_recorder=rec) as rt:
        srv = SynergyServer(cfg, params, slots=1, max_len=16,
                            prefill_len=4, runtime=rt, prefill_cnn=tiny,
                            submit_timeout=0.1)
        srv.submit(Request(0, jnp.arange(4, dtype=jnp.int32),
                           max_new_tokens=2))
        with pytest.raises(ServeTimeoutError):
            srv.run()
        rt.shutdown(drain=False, timeout=5.0)
    assert srv._flight is rec          # server inherited the recorder
    assert len(rec.dumps) == 1
    with open(rec.dumps[0]) as f:
        dump = json.load(f)
    assert dump["reason"] == "serve_timeout"
    assert dump["context"]["timeout_s"] == 0.1
    assert "stuck" in dump["stats"]["runtime"]["engines"]
    assert len(dump["events"]) <= 64
    kinds = {e["kind"] for e in dump["events"]}
    assert kinds <= EVENT_KINDS


def test_flight_recorder_cap_and_bad_dir(tmp_path):
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("")
    rec = FlightRecorder(None, dir=str(blocker / "sub"), max_dumps=2)
    assert rec.dump("x") is None       # unwritable dir: never raises
    rec2 = FlightRecorder(None, dir=str(tmp_path), max_dumps=0)
    assert rec2.dump("x") is None and rec2.suppressed == 1


# --------------------------- tenants + quarantine acceptance integration

class _SickEngine(Engine):
    def __init__(self, name, macs_per_s=1e9):
        super().__init__(name, {CAP_GEMM, "epilogue"},
                         cost=CostModel(macs_per_s=macs_per_s))
        self.delay_s = 0.008

    def execute(self, a, b, **kw):
        time.sleep(self.delay_s)
        y = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        return y.astype(a.dtype)


def test_tenanted_quarantine_run_produces_full_trace(tmp_path):
    """ISSUE 8 acceptance: a serving run with tenants + a quarantine
    yields a Chrome trace with per-engine tracks and steal / quarantine /
    deadline / admission events, and the flight recorder captured the
    quarantine."""
    pol = HealthPolicy(alpha=0.5, quarantine_below=0.5, readmit_above=0.6,
                       min_samples=3, probe_interval_s=1e9,
                       min_probe_samples=2)
    tr = Tracer(capacity=200_000)
    rec = FlightRecorder(tr, dir=str(tmp_path))
    sick, buddy = _SickEngine("sick"), _SickEngine("buddy")
    b = jnp.ones((32, 16))

    def gemm(rt, step, affinity=None, m=16):
        return rt.submit_gemm(
            jnp.ones((m, 32)), b, jobset=JobSet.for_gemm(step, m, 16, 32, 16),
            tile=(16, 16, 16), affinity=affinity)

    with SynergyRuntime([sick, buddy], name="obs-heal", health=pol,
                        tracer=tr, flight_recorder=rec) as rt:
        # four panels a GEMM: "sick" surely runs some (its baseline) and
        # "buddy" surely steals some; with one panel either can win every
        # race for it
        for s in range(6):
            gemm(rt, s, affinity="sick", m=64).result(30)
        sick.delay_s = 0.12
        deadline = time.monotonic() + 30
        step = 100
        while not rt.stats()["engines"]["sick"]["quarantined"]:
            assert time.monotonic() < deadline, "never quarantined"
            gemm(rt, step, affinity="sick").result(30)
            step += 1

        # a tenanted serving run on the SAME tracer (serving tracks)
        from repro.models.cnn import CNNConfig
        tiny = CNNConfig(name="tiny", input_hw=8, cin=1, layers=(
            ("conv", 4, 3, 1, 1), ("fc", 10)))
        cfg = _cfg()
        params = init_model(cfg, jax.random.key(0))
        srv = SynergyServer(
            cfg, params, slots=2, max_len=32, prefill_len=4, runtime=rt,
            prefill_cnn=tiny,
            tenants=[Tenant("gold", QosClass(priority=10, deadline_s=60.0)),
                     Tenant("bulk")])
        for i in range(3):
            srv.submit(Request(i, jnp.arange(4, dtype=jnp.int32) + i,
                               max_new_tokens=3,
                               tenant="gold" if i == 0 else "bulk"))
        srv.run()

    evs = tr.events()
    assert validate_events(evs, engines={"sick", "buddy"}) == []
    kinds = {e.kind for e in evs}
    assert {"quarantine", "steal", "admission", "deadline_hit",
            "panel_start", "panel_end"} <= kinds
    assert rec.dumps, "quarantine must flight-record"
    with open(rec.dumps[0]) as f:
        assert json.load(f)["reason"] == "quarantine"

    path = tmp_path / "accept.json"
    tr.export_chrome_trace(str(path))
    with open(path) as f:
        data = json.load(f)
    names = {d["args"]["name"] for d in data["traceEvents"]
             if d.get("ph") == "M" and d.get("name") == "thread_name"}
    assert {"sick", "buddy", "serving", "admission"} <= names
    # metrics exposition over the same run parses and shows the tenants
    text = render_prometheus(runtime=rt, server=srv,
                             registry=MetricsRegistry())
    parsed = parse_prometheus(text)
    tenants = {lb["tenant"] for lb, _ in parsed["repro_tenant_tokens_total"]}
    assert tenants == {"gold", "bulk"}
    assert parsed["repro_runtime_quarantines_total"][0][1] >= 1


# ------------------------------------- Telemetry view regression (bugfix)

def test_busy_fraction_reads_consistently_under_concurrent_merge():
    """busy_fraction must read busy+idle under the lock: hammering
    record_runtime/merge from threads can never produce a fraction
    outside [0, 1] (the torn-read symptom) and totals stay exact."""
    t = Telemetry()
    stop = threading.Event()
    bad = []

    def writer():
        while not stop.is_set():
            t.record_runtime(wall_busy_s=0.001, idle_s=0.001)

    def reader():
        while not stop.is_set():
            f = t.busy_fraction
            if not (0.0 <= f <= 1.0):
                bad.append(f)

    threads = [threading.Thread(target=writer) for _ in range(2)] + \
              [threading.Thread(target=reader) for _ in range(2)]
    for th in threads:
        th.start()
    time.sleep(0.3)
    stop.set()
    for th in threads:
        th.join()
    assert not bad
    snap = t.snapshot()
    assert snap.wall_busy_s == pytest.approx(snap.idle_s)
    assert t.busy_fraction == pytest.approx(0.5)


def test_merge_mid_window_never_double_counts_idle():
    """The worker books an idle window only AFTER cond.wait returns, so
    a snapshot taken mid-window UNDERCOUNTS idle; merging a mid-window
    snapshot with the final state must never exceed the true totals."""
    src = Telemetry()
    src.record_runtime(idle_s=0.5)         # window 1 fully booked
    mid = src.snapshot()                   # snapshot while window 2 open
    src.record_runtime(idle_s=0.25)        # window 2 lands afterwards
    assert mid.idle_s == 0.5               # open window invisible: no double
    merged = Telemetry()
    merged.merge(mid)
    assert merged.idle_s == 0.5
    final = Telemetry()
    final.merge(src.snapshot())
    assert final.idle_s == pytest.approx(0.75)
    # merging two engines' snapshots sums exactly once each
    other = Telemetry()
    other.record_runtime(wall_busy_s=0.75, idle_s=0.25)
    final.merge(other.snapshot())
    assert final.idle_s == pytest.approx(1.0)
    assert final.wall_busy_s == pytest.approx(0.75)
    assert final.busy_fraction == pytest.approx(0.75 / 1.75)
