"""Hypothesis property tests for deterministic fault injection (ISSUE 9
satellite c).

For RANDOM retryable fault plans (raise / corrupt / slowdown at seeded
call indices) layered over a mixed fp32/int8-capable pool with seeded
random steal timing:

  * every tile panel completes exactly once — failed attempts retry,
    but never double-merge into the output or the accounting;
  * every GEMM's merged output is bitwise identical to the fault-free
    answer (the keystone invariant: faults cost retries, not ULPs);
  * no :class:`RuntimeFuture` hangs — every submission resolves within
    the timeout and reports done.

The seeded chaos sweep in ``test_faults.py`` covers the same invariants
when the hypothesis dev-dependency is absent.
"""

import random
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need the dev deps
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.job import JobSet                         # noqa: E402
from repro.engines import (CAP_GEMM, CAP_INT8, CostModel,  # noqa: E402
                           Engine)
from repro.soc import (FaultPlan, RetryPolicy,            # noqa: E402
                       SynergyRuntime, wrap_pool)

from _panels import panel_reference                       # noqa: E402


class _ChaosEngine(Engine):
    """Identical fp32 math on every instance (placement-independent,
    bitwise-comparable outputs) plus a seeded random per-panel delay so
    steal timing varies between hypothesis examples."""

    def __init__(self, name, macs_per_s=5e8, *, seed=0, int8=False,
                 max_delay_s=0.002):
        caps = {CAP_GEMM, "epilogue"} | ({CAP_INT8} if int8 else set())
        super().__init__(name, caps, cost=CostModel(macs_per_s=macs_per_s))
        self._rng = random.Random(seed)
        self._max_delay_s = max_delay_s

    def execute(self, a, b, *, bias=None, activation=None, tile=None,
                out_dtype=None, precision=None):
        time.sleep(self._rng.random() * self._max_delay_s)
        y = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        return y.astype(out_dtype or a.dtype)


@settings(max_examples=10, deadline=None)
@given(plan_seed=st.integers(0, 2**16), steal_seed=st.integers(0, 2**16),
       wl_seed=st.integers(0, 2**16))
def test_random_fault_plans_exactly_once_bitwise_no_hangs(plan_seed,
                                                          steal_seed,
                                                          wl_seed):
    rng = random.Random(wl_seed)
    names = ["pf0", "pf1", "pf2"]
    # mixed pool: pf2 advertises int8 so the steal-eligibility filter
    # (int8 thieves only take int8-ok panels) is exercised under faults
    pool = [_ChaosEngine(names[0], seed=steal_seed),
            _ChaosEngine(names[1], 3e8, seed=steal_seed + 1),
            _ChaosEngine(names[2], 4e8, seed=steal_seed + 2, int8=True)]
    plan = FaultPlan.random(plan_seed, names)  # retryable kinds only
    retry = RetryPolicy(max_attempts=6, backoff_s=0.0,
                        avoid_failed_engine=True, check_outputs=True)

    d = 64
    w = jax.random.normal(jax.random.key(3), (d, 48))
    mats = [jax.random.normal(jax.random.key(200 + wl_seed + i),
                              (32 * rng.randint(1, 4), d))
            for i in range(rng.randint(2, 4))]

    solo = _ChaosEngine("solo", max_delay_s=0.0)
    with SynergyRuntime(wrap_pool(pool, plan), name="fprop",
                        retry=retry) as rt:
        futs = [rt.submit_gemm(
            a, w, jobset=JobSet.for_gemm(i, a.shape[0], 48, d, 32,
                                         name=f"fp{i}"),
            tile=(32, 32, 32)) for i, a in enumerate(mats)]
        for f, a in zip(futs, mats):
            got = f.result(120)            # no hung futures
            assert f.done()
            # exactly-once: each panel merged once, accounting books
            # every tile job once, retries never double-count
            assert f.execution_counts == [1] * len(f.execution_counts)
            assert sum(x["jobs"] for x in f.accounting.values()) \
                == f.jobset.num_jobs
            ref = panel_reference(solo, a, w, 32)
            assert np.array_equal(np.asarray(got), np.asarray(ref))
        stats = rt.stats()
    # every injected fault that raised/corrupted was absorbed as a retry
    assert stats["retries"] == sum(
        1 for (_, kind, _) in plan.injected if kind in ("raise", "corrupt"))
    # per-engine counters track BURNED work (failed attempts included),
    # so they bound the exactly-once submission accounting from above
    assert stats["total_jobs"] >= sum(f.jobset.num_jobs for f in futs)
