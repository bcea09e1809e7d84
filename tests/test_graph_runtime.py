"""repro.soc.graph: dataflow-graph submissions over the live runtime.

Covers the ISSUE 6 tentpole invariants: successors' panels enter the
deques the moment their predecessors' tail panels land (finish_order
respects every edge), host gather nodes overlap GEMM nodes, adopted
``submit_gemm`` futures complete their node bitwise-identically to a
serial reference, failures cancel descendants, ``GraphFuture.cancel``
drains queued-but-unstarted panels (satellite 1), and a chain graph
books exactly the jobs of the same JobSets submitted back to back.
"""

import random
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.job import JobSet
from repro.engines import CAP_GEMM, CostModel, Engine, get_engine
from repro.soc import GraphCancelled, GraphNode, SynergyRuntime
from repro.soc.graph import validate_dag

from _panels import panel_reference


def _ab(m, k, n, seed=0):
    ka, kb = jax.random.split(jax.random.key(seed))
    return (jax.random.normal(ka, (m, k)), jax.random.normal(kb, (k, n)))


class _DelayEngine(Engine):
    """Deterministic-output engine with seeded random per-job delays —
    randomized steal timing without randomized results."""

    def __init__(self, name, macs_per_s=1e9, seed=0, max_delay_s=0.003):
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


class _SleepyEngine(Engine):
    """Every panel sleeps: keeps queues populated so cancellation can
    observe queued-but-unstarted panels."""

    def __init__(self, name="sleepy", delay_s=0.15):
        super().__init__(name, {CAP_GEMM, "epilogue"},
                         cost=CostModel(macs_per_s=1e9))
        self._delay_s = delay_s
        self.executed = 0

    def execute(self, a, b, *, bias=None, activation=None, tile=None,
                out_dtype=None, precision=None):
        time.sleep(self._delay_s)
        self.executed += 1
        return jnp.dot(a.astype(jnp.float32),
                       b.astype(jnp.float32)).astype(out_dtype or a.dtype)


# ----------------------------------------------------------- validate_dag

def test_validate_dag_rejects_cycles_and_bad_edges():
    with pytest.raises(ValueError, match="cycle"):
        validate_dag(3, [(0, 1), (1, 2), (2, 0)])
    with pytest.raises(ValueError, match="self-edge"):
        validate_dag(2, [(0, 0)])
    with pytest.raises(ValueError, match="out of range"):
        validate_dag(2, [(0, 5)])
    succs, preds = validate_dag(3, [(0, 2), (1, 2)])
    assert succs == [[2], [2], []]
    assert preds == [[], [], [0, 1]]     # edge order preserved


# ----------------------------------------------- accounting-only DAG nodes

def test_graph_accounting_diamond_orders_and_books_all_jobs():
    """Bare JobSets as nodes: every tile job is scheduled and booked, and
    the completion order respects every dependency edge (the reap-order
    audit trail of the per-node dependency counters)."""
    jss = [JobSet.for_gemm(i, 96, 64, 32, 32, name=f"n{i}")
           for i in range(4)]
    edges = [(0, 1), (0, 2), (1, 3), (2, 3)]
    with SynergyRuntime(["F-PE", "S-PE"], name="diamond") as rt:
        gf = rt.submit_graph(jss, edges, name="diamond")
        vals = gf.result(60)
    assert vals == [None] * 4            # accounting nodes carry no value
    pos = {nid: i for i, nid in enumerate(gf.finish_order)}
    for u, v in edges:
        assert pos[u] < pos[v], (gf.finish_order, (u, v))
    assert gf.node_states() == ["done"] * 4
    total = sum(a["jobs"] for a in gf.accounting.values())
    assert total == sum(js.num_jobs for js in jss)
    assert rt.stats()["total_jobs"] == total


def test_graph_empty_jobset_node_cascades():
    """A zero-job node completes instantly and releases its successors."""
    empty = JobSet.for_gemm(0, 0, 32, 32, 32, name="empty")
    real = JobSet.for_gemm(1, 64, 32, 32, 32, name="real")
    with SynergyRuntime(["F-PE"], name="empty") as rt:
        gf = rt.submit_graph([empty, real], [(0, 1)])
        gf.result(60)
    assert gf.node_states() == ["done", "done"]


# ------------------------------------------------- value-flow (run nodes)

def test_graph_value_flow_adopted_gemm_bitwise():
    """Host nodes flow values along edges; a run node returning a
    RuntimeFuture (nested submit_gemm) is ADOPTED — the node completes at
    the submission's tail panel, and the chained numerics are bitwise
    identical to the serial reference."""
    a, w1 = _ab(48, 32, 32, seed=1)
    _, w2 = _ab(48, 32, 24, seed=2)
    js1 = JobSet.for_gemm(0, 48, 32, 32, 16, name="g1")
    js2 = JobSet.for_gemm(1, 48, 24, 32, 16, name="g2")
    nodes = [
        GraphNode(name="scale", run=lambda rt: a * 2.0),
        GraphNode(name="g1", run=lambda rt, x: rt.submit_gemm(
            x, w1, jobset=js1, tile=(16, 16, 16))),
        GraphNode(name="relu", run=lambda rt, y: jax.nn.relu(y)),
        GraphNode(name="g2", run=lambda rt, y: rt.submit_gemm(
            y, w2, jobset=js2, tile=(16, 16, 16))),
    ]
    with SynergyRuntime(["F-PE", "S-PE"], name="flow") as rt:
        gf = rt.submit_graph(nodes, [(0, 1), (1, 2), (2, 3)], name="flow")
        vals = gf.result(60)
    fpe = get_engine("F-PE")
    ref = panel_reference(
        fpe, jax.nn.relu(panel_reference(fpe, a * 2.0, w1, 16)), w2, 16)
    assert np.array_equal(np.asarray(vals[3]), np.asarray(ref))
    assert gf.node_future(1) is not None      # adopted submission futures
    assert gf.node_future(0) is None          # pure host node: no future


def test_graph_parallel_branches_share_the_pool():
    """Two independent GEMM branches fan out over the pool and a join
    node sees both predecessor values in edge order."""
    a, w = _ab(64, 32, 32, seed=3)
    jss = [JobSet.for_gemm(i, 64, 32, 32, 16, name=f"br{i}")
           for i in range(2)]
    nodes = [
        GraphNode(name="b0", run=lambda rt: rt.submit_gemm(
            a, w, jobset=jss[0], tile=(16, 16, 16))),
        GraphNode(name="b1", run=lambda rt: rt.submit_gemm(
            a * 3.0, w, jobset=jss[1], tile=(16, 16, 16))),
        GraphNode(name="join", run=lambda rt, y0, y1: y0 + y1),
    ]
    with SynergyRuntime(["F-PE", "S-PE"], name="fan") as rt:
        gf = rt.submit_graph(nodes, [(0, 2), (1, 2)], name="fan")
        vals = gf.result(60)
    fpe = get_engine("F-PE")
    ref = (panel_reference(fpe, a, w, 16)
           + panel_reference(fpe, a * 3.0, w, 16))
    assert np.array_equal(np.asarray(vals[2]), np.asarray(ref))


# ------------------------------------------------- failure / cancellation

def test_graph_failure_cancels_descendants():
    boom = RuntimeError("boom")

    def fail(rt, x):
        raise boom

    nodes = [
        GraphNode(name="ok", run=lambda rt: 1),
        GraphNode(name="bad", run=fail),
        GraphNode(name="downstream", run=lambda rt, x: x),
    ]
    with SynergyRuntime(["F-PE"], name="fail") as rt:
        gf = rt.submit_graph(nodes, [(0, 1), (1, 2)], name="fail")
        with pytest.raises(RuntimeError, match="boom"):
            gf.result(60)
    assert gf.node_states() == ["done", "failed", "cancelled"]


def test_graph_cancel_drains_queued_panels_and_downstream():
    """Satellite 1: cancel() marks every not-yet-started node cancelled
    AND drains the running submissions' queued panels from the worker
    deques — the sleepy engine never executes the drained tail, and the
    runtime keeps serving fresh work afterwards."""
    eng = _SleepyEngine(delay_s=0.15)
    a, w = _ab(4 * 16, 32, 16, seed=5)
    js0 = JobSet.for_gemm(0, a.shape[0], 16, 32, 16, name="head")
    js1 = JobSet.for_gemm(1, a.shape[0], 16, 32, 16, name="tail")
    nodes = [
        GraphNode(name="head", run=lambda rt: rt.submit_gemm(
            a, w, jobset=js0, tile=(16, 16, 16))),
        GraphNode(name="tail", run=lambda rt, y: rt.submit_gemm(
            y, w[:16, :].T @ w, jobset=js1, tile=(16, 16, 16))),
    ]
    with SynergyRuntime([eng], name="cancel") as rt:
        gf = rt.submit_graph(nodes, [(0, 1)], name="cancel")
        time.sleep(0.05)                 # first panel in flight, rest queued
        gf.cancel("test cancel")
        with pytest.raises(GraphCancelled):
            gf.result(60)
        # the 4-panel head never ran to completion: queued panels drained
        assert eng.executed < 4
        assert gf.node_states()[1] == "cancelled"
        # the pool is healthy: fresh work still completes
        y = rt.submit_gemm(a[:16], w, jobset=JobSet.for_gemm(
            2, 16, 16, 32, 16, name="after"), tile=(16, 16, 16)).result(60)
        assert np.array_equal(np.asarray(y),
                              np.asarray(jnp.dot(a[:16], w)))


def test_runtime_shutdown_cancels_active_graphs():
    eng = _SleepyEngine(delay_s=0.2)
    a, w = _ab(4 * 16, 32, 16, seed=6)
    js = JobSet.for_gemm(0, a.shape[0], 16, 32, 16, name="shut")
    rt = SynergyRuntime([eng], name="shut")
    rt.start()
    gf = rt.submit_graph(
        [GraphNode(name="g", run=lambda r: r.submit_gemm(
            a, w, jobset=js, tile=(16, 16, 16))),
         GraphNode(name="down", run=lambda r, y: y)],
        [(0, 1)], name="shut")
    time.sleep(0.05)
    rt.shutdown()
    with pytest.raises((GraphCancelled, RuntimeError)):
        gf.result(10)


# ------------------------------------------ randomized DAG property sweep

def _random_dag_case(seed: int):
    """One seeded random case: topology, node kinds, mixed pool."""
    rng = random.Random(seed)
    n = rng.randint(3, 6)
    edges = [(u, v) for u in range(n) for v in range(u + 1, n)
             if rng.random() < 0.45]
    kinds = [rng.choice(["gemm", "acct"]) for _ in range(n)]
    return n, edges, kinds


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_random_dag_exactly_once_ordered_and_bitwise(seed):
    """Property (satellite 3, seeded sweep): random DAGs over a mixed
    fp32/int8 pool with randomized steal timing execute every node
    exactly once, complete predecessors strictly before successors, and
    produce gemm values bitwise equal to the serial reference."""
    from repro.quant import QuantizedEngine
    n, edges, kinds = _random_dag_case(seed)
    _, preds = validate_dag(n, edges)
    d = 32
    base = [jax.random.normal(jax.random.key(100 + i), (48, d))
            for i in range(n)]
    w = jax.random.normal(jax.random.key(7), (d, d))
    ran: list[int] = []

    def make_node(i):
        if kinds[i] == "acct":
            return GraphNode(name=f"acct{i}",
                             jobset=JobSet.for_gemm(i, 96, 64, 32, 32,
                                                    name=f"acct{i}"))

        def run(rt, *pvals, _i=i):
            ran.append(_i)
            x = base[_i]
            for pv in pvals:
                if pv is not None:       # accounting preds carry no value
                    x = x + pv
            return rt.submit_gemm(x, w, jobset=JobSet.for_gemm(
                _i, 48, d, d, 16, name=f"gemm{_i}"), tile=(16, 16, 16))
        return GraphNode(name=f"gemm{i}", run=run)

    pool = [_DelayEngine("dly-a", seed=seed), _DelayEngine("dly-b", seed=seed + 9),
            QuantizedEngine(get_engine("xla"), name=f"int8-{seed}")]
    with SynergyRuntime(pool, name=f"rand{seed}") as rt:
        gf = rt.submit_graph([make_node(i) for i in range(n)], edges,
                             name=f"rand{seed}")
        vals = gf.result(120)
    # every run node executed exactly once
    assert sorted(ran) == [i for i in range(n) if kinds[i] == "gemm"]
    # reap order respects every edge
    pos = {nid: i for i, nid in enumerate(gf.finish_order)}
    for u, v in edges:
        assert pos[u] < pos[v]
    # serial reference, same pred-value accumulation order (edge order)
    solo = _DelayEngine("solo", max_delay_s=0.0)
    ref: list = [None] * n
    for i in range(n):
        if kinds[i] == "acct":
            continue
        x = base[i]
        for p in preds[i]:
            if ref[p] is not None:
                x = x + ref[p]
        ref[i] = panel_reference(solo, x, w, 16)
    for i in range(n):
        if kinds[i] == "gemm":
            assert np.array_equal(np.asarray(vals[i]), np.asarray(ref[i])), i
        else:
            assert vals[i] is None


# ------------------------------------------------ chain vs back to back

def test_graph_chain_matches_back_to_back_runs():
    """A chain of accounting nodes books exactly the tile jobs of the same
    JobSets submitted back to back, and reaps them in chain order."""
    jss = [JobSet.for_gemm(i, 512, 256, 128, 32, name=f"l{i}")
           for i in range(3)]
    with SynergyRuntime(["F-PE", "S-PE", "NEON"], name="chain") as rt:
        gf = rt.submit_graph(jss, [(0, 1), (1, 2)], name="chain")
        gf.result(60)
        chain_jobs = rt.stats()["total_jobs"]
    with SynergyRuntime(["F-PE", "S-PE", "NEON"], name="serial") as rt:
        futs = []
        for js in jss:
            futs.append(rt.submit(js))
            futs[-1].result(60)
        serial_jobs = rt.stats()["total_jobs"]
    assert gf.finish_order == [0, 1, 2]
    assert sum(a["jobs"] for a in gf.accounting.values()) \
        == sum(sum(a["jobs"] for a in f.accounting.values()) for f in futs)
    assert chain_jobs == serial_jobs == sum(js.num_jobs for js in jss)
