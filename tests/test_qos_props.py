"""Hypothesis property tests for the multi-tenant QoS scheduler (ISSUE 7
satellite c).

For RANDOM priority/deadline mixes, workload sizes, and steal-timing
seeds:

  * every tile panel executes exactly once and every GEMM's value is
    bitwise equal to the same row panels run on one engine, whatever QoS
    tags are attached — QoS reorders work, it never changes or drops it;
  * LOW-priority submissions still complete (and book the right number
    of jobs) when capacity allows — priority queueing starves nobody.

The seeded deterministic sweep in ``test_qos.py`` covers the core
invariants when the hypothesis dev-dependency is absent.
"""

import math
import random
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need the dev deps
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.job import JobSet                         # noqa: E402
from repro.engines import CAP_GEMM, CostModel, Engine    # noqa: E402
from repro.soc import QosTag, SynergyRuntime              # noqa: E402

from _panels import panel_reference                       # noqa: E402


class _DelayEngine(Engine):
    """Deterministic-output engine with seeded random per-job delays."""

    def __init__(self, name, macs_per_s=1e9, seed=0, max_delay_s=0.002):
        super().__init__(name, {CAP_GEMM, "epilogue"},
                         cost=CostModel(macs_per_s=macs_per_s))
        self._rng = random.Random(seed)
        self._max_delay_s = max_delay_s

    def execute(self, a, b, *, bias=None, activation=None, tile=None,
                out_dtype=None, precision=None):
        time.sleep(self._rng.random() * self._max_delay_s)
        y = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        return y.astype(out_dtype or a.dtype)


@settings(max_examples=12, deadline=None)
@given(wl_seed=st.integers(0, 2**16), steal_seed=st.integers(0, 2**16))
def test_random_tags_exactly_once_bitwise(wl_seed, steal_seed):
    rng = random.Random(wl_seed)
    d = 32
    w = jax.random.normal(jax.random.key(5), (d, 16))
    subs = []
    for i in range(rng.randint(2, 5)):
        m = 16 * rng.randint(1, 4)
        a = jax.random.normal(jax.random.key(100 + wl_seed + i), (m, d))
        tag = QosTag(rng.choice([-10, -1, 0, 10]),
                     rng.choice([math.inf, 0.5, 5.0]))
        subs.append((a, tag))

    pool = [_DelayEngine("qp-a", seed=steal_seed),
            _DelayEngine("qp-b", macs_per_s=4e8, seed=steal_seed + 1)]
    solo = _DelayEngine("solo", max_delay_s=0.0)
    with SynergyRuntime(pool, name="qosprop") as rt:
        futs = [rt.submit_gemm(a, w,
                               jobset=JobSet.for_gemm(i, a.shape[0], 16,
                                                      d, 16, name=f"p{i}"),
                               tile=(16, 16, 16), qos=tag)
                for i, (a, tag) in enumerate(subs)]
        for f, (a, _) in zip(futs, subs):
            got = f.result(120)
            # exactly-once panels: the runtime booked every tile job
            assert sum(x["jobs"] for x in f.accounting.values()) \
                == f.jobset.num_jobs
            ref = panel_reference(solo, a, w, 16)
            assert np.array_equal(np.asarray(got), np.asarray(ref))
        st_ = rt.stats()
    assert st_["total_jobs"] == sum(f.jobset.num_jobs for f in futs)


@settings(max_examples=12, deadline=None)
@given(wl_seed=st.integers(0, 2**16), steal_seed=st.integers(0, 2**16))
def test_low_priority_never_starves_with_capacity(wl_seed, steal_seed):
    """A best-effort submission behind a stream of interactive work still
    finishes — the runtime drains queues in priority order but never
    parks low-priority panels forever while workers have capacity."""
    rng = random.Random(wl_seed)
    pool = [_DelayEngine("st-a", seed=steal_seed, max_delay_s=0.001),
            _DelayEngine("st-b", seed=steal_seed + 1, max_delay_s=0.001)]
    d = 32
    w = jax.random.normal(jax.random.key(7), (d, 16))
    a_lo = jax.random.normal(jax.random.key(wl_seed), (32, d))
    with SynergyRuntime(pool, name="starve") as rt:
        lo = rt.submit_gemm(a_lo, w,
                            jobset=JobSet.for_gemm(0, 32, 16, d, 16,
                                                   name="lo"),
                            tile=(16, 16, 16), qos=QosTag(-20))
        his = []
        for i in range(rng.randint(3, 6)):
            a = jax.random.normal(jax.random.key(1000 + i), (32, d))
            his.append(rt.submit_gemm(
                a, w, jobset=JobSet.for_gemm(1 + i, 32, 16, d, 16,
                                             name=f"hi{i}"),
                tile=(16, 16, 16), qos=QosTag(10, 5.0)))
        got = lo.result(60)          # completes: no starvation
        solo = _DelayEngine("solo", max_delay_s=0.0)
        assert np.array_equal(
            np.asarray(got),
            np.asarray(panel_reference(solo, a_lo, w, 16)))
        assert sum(x["jobs"] for x in lo.accounting.values()) \
            == lo.jobset.num_jobs
        for f in his:
            f.result(60)
