"""The unified engine registry: numeric agreement across backends,
capability-filtered dispatch, telemetry/trace consistency, legacy-impl
shim, and zero-call-site-edit rerouting via a mock engine."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_cnns import PAPER_CNNS
from repro.core.clusters import F_PE
from repro.core.job import JobSet
from repro.core.synergy_mm import SynergyTrace, synergy_matmul
from repro.engines import (CAP_GEMM, CostModel, Dispatcher, Engine,
                           SimPEEngine, get_engine, list_engines,
                           registered, resolve_op)
from repro.models.cnn import cnn_forward, init_cnn

from _gemms import MODEL_GEMMS


def _ab(m, k, n, seed=0):
    ka, kb = jax.random.split(jax.random.key(seed))
    return (jax.random.normal(ka, (m, k)), jax.random.normal(kb, (k, n)))


# ------------------------------------------------------------------ registry

def test_builtin_engines_registered():
    names = {e.name for e in list_engines()}
    assert {"xla", "pallas", "reference", "F-PE", "S-PE", "NEON",
            "ARM"} <= names


@pytest.mark.parametrize("shape", [(64, 64, 64),      # tile-aligned
                                   (70, 45, 33),      # border tiles
                                   (1, 257, 129)] + [
    (m, k, n) for m in (1, 196, 200) for k in (75, 147)   # no 128 multiple
    for n in (10, 64, 1000)])
def test_engines_agree_numerically(shape):
    """XLA, Pallas (interpret off-TPU), and the reference oracle compute
    the same GEMM, bias and activation included."""
    m, k, n = shape
    a, b = _ab(m, k, n)
    bias = jax.random.normal(jax.random.key(2), (n,))
    kw = dict(bias=bias, activation=jax.nn.relu, tile=(32, 32, 32))
    ref = get_engine("reference").execute(a, b, **kw)
    for name in ("xla", "pallas"):
        got = get_engine(name).execute(a, b, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_sim_engines_are_executable():
    a, b = _ab(16, 8, 8)
    ref = get_engine("reference").execute(a, b)
    got = get_engine("F-PE").execute(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------- dispatcher

class _FastMock(Engine):
    """Implausibly fast mock engine: auto-dispatch must pick it."""

    def __init__(self, name="mock", caps=(CAP_GEMM, "epilogue")):
        super().__init__(name, set(caps), cost=CostModel(macs_per_s=1e18))
        self.calls = 0

    def execute(self, a, b, *, bias=None, activation=None, tile=None,
                out_dtype=None, precision=None):
        self.calls += 1
        y = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32))
        if bias is not None:
            y = y + bias
        if activation is not None:
            y = activation(y)
        return y.astype(out_dtype or a.dtype)


def test_dispatcher_ranks_by_cost_model():
    js = JobSet.for_gemm(0, 64, 64, 64, 32)
    with registered(_FastMock()) as (mock,):
        assert Dispatcher().select(js) is mock
    # once unregistered the default choice returns
    assert Dispatcher().select(js).name != "mock"


def test_dispatcher_respects_capabilities():
    js = JobSet.for_gemm(0, 64, 64, 64, 32)
    no_gemm = _FastMock(name="mock-nogemm", caps=("epilogue",))
    with registered(no_gemm):
        eng = Dispatcher().select(js)
        assert eng.name != "mock-nogemm"     # lacks CAP_GEMM, never picked
    with pytest.raises(ValueError):
        Dispatcher().select(js, engine=no_gemm)   # explicit is still checked
    # sim engines are excluded from AUTO selection but usable explicitly
    assert Dispatcher().select(js).name not in ("F-PE", "S-PE", "NEON")
    assert Dispatcher().select(js, engine="F-PE").name == "F-PE"


def test_mock_engine_reroutes_with_zero_callsite_edits():
    """Registering an engine reroutes a whole model's GEMMs — no edits to
    cnn_forward or any call site."""
    cfg = PAPER_CNNS["MNIST"]
    params = init_cnn(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1),
                          (1, cfg.input_hw, cfg.input_hw, cfg.cin))
    baseline = cnn_forward(cfg, params, x)
    mock = _FastMock()
    with registered(mock):
        rerouted = cnn_forward(cfg, params, x)
    assert mock.calls > 0, "mock engine never selected"
    assert mock.telemetry.gemms == mock.calls
    np.testing.assert_allclose(np.asarray(rerouted), np.asarray(baseline),
                               rtol=1e-3, atol=1e-3)


# ----------------------------------------------------------------- telemetry

def test_trace_aggregates_per_engine_telemetry():
    a, b = _ab(70, 45, 33)
    tr = SynergyTrace()
    with tr.activate():
        synergy_matmul(a, b, tile=32, name="g0")
        synergy_matmul(a, b, tile=32, name="g1", engine="reference")
    assert sum(t.jobs for t in tr.engine_stats.values()) == tr.num_jobs
    assert sum(t.gemms for t in tr.engine_stats.values()) == len(tr.jobsets)
    assert "reference" in tr.engine_stats
    for t in tr.engine_stats.values():
        assert t.busy_s > 0 and t.bytes_moved > 0


def test_engine_global_telemetry_advances():
    eng = get_engine("reference")
    before = eng.telemetry.snapshot()
    a, b = _ab(32, 32, 32, seed=3)
    synergy_matmul(a, b, tile=32, engine="reference")
    assert eng.telemetry.gemms == before.gemms + 1
    assert eng.telemetry.jobs == before.jobs + 1


# ------------------------------------------------------- legacy shim + ops

def test_impl_string_shim_warns_and_works():
    a, b = _ab(16, 8, 8)
    with pytest.warns(DeprecationWarning):
        y = synergy_matmul(a, b, impl="xla")
    ref = get_engine("reference").execute(a, b)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    with pytest.warns(DeprecationWarning):
        synergy_matmul(a, b, impl="auto")   # auto -> dispatcher


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_impl_shim_routes_to_same_engine_as_new_api(impl):
    """The legacy string and the new engine= spelling must land on the
    SAME registered engine (trace-visible routing identity)."""
    a, b = _ab(16, 8, 8, seed=11)
    tr_old, tr_new = SynergyTrace(), SynergyTrace()
    with tr_old.activate(), pytest.warns(DeprecationWarning):
        synergy_matmul(a, b, tile=8, impl=impl)
    with tr_new.activate():
        synergy_matmul(a, b, tile=8, engine=impl)
    assert set(tr_old.engine_stats) == set(tr_new.engine_stats) == {impl}
    # explicit engine= wins over a conflicting legacy string
    tr = SynergyTrace()
    with tr.activate(), pytest.warns(DeprecationWarning):
        synergy_matmul(a, b, tile=8, impl=impl, engine="reference")
    assert set(tr.engine_stats) == {"reference"}


def test_engine_scope_nesting_restores_outer_pin():
    from repro.engines import current_scope_engine, engine_scope
    a, b = _ab(16, 8, 8, seed=12)
    assert current_scope_engine() is None
    with engine_scope("reference"):
        with engine_scope("xla"):
            assert current_scope_engine() == "xla"
            tr = SynergyTrace()
            with tr.activate():
                synergy_matmul(a, b, tile=8)
            assert set(tr.engine_stats) == {"xla"}
        assert current_scope_engine() == "reference"
        tr = SynergyTrace()
        with tr.activate():
            synergy_matmul(a, b, tile=8)
        assert set(tr.engine_stats) == {"reference"}
        # engine_scope(None) re-enables dispatcher auto-selection inside
        # an outer pin
        with engine_scope(None):
            assert current_scope_engine() is None
    assert current_scope_engine() is None


def test_resolve_op_variants():
    # auto resolves to an available variant; explicit names resolve even
    # when unavailable for auto (Pallas interpret off-TPU)
    assert resolve_op("flash_attention") is resolve_op(
        "flash_attention",
        "pallas" if jax.default_backend() == "tpu" else "xla")
    with pytest.raises(KeyError):
        resolve_op("flash_attention", "nope")
    with pytest.raises(KeyError):
        resolve_op("no_such_op")


@pytest.mark.parametrize("arch,op", [("granite-3-2b", "attention_scores"),
                                     ("mamba2-130m", "ssd")])
def test_training_skips_variants_without_vjp(arch, op):
    """The Pallas variants have no VJP.  Made available (as on a TPU),
    auto resolution must pick them for inference and skip them wherever
    the train step differentiates the op (jit + remat + scan)."""
    from repro.configs import ARCHS, reduced
    from repro.engines import op_variants, register_op_impl
    from repro.models import init_model, lm_loss
    saved = {v.name: v for v in op_variants(op)}["pallas"]
    fallback = {v.name: v for v in op_variants(op)}[
        "flash_xla" if op == "attention_scores" else "xla"].fn
    calls = []

    def probe(*args, **kw):
        calls.append(1)
        return fallback(*args, **kw)

    register_op_impl(op, "pallas", probe, priority=saved.priority,
                     grad=False, override=True)
    try:
        cfg = reduced(ARCHS[arch])
        assert cfg.remat
        params = init_model(cfg, jax.random.key(0))
        batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
                 "labels": jnp.ones((2, 16), jnp.int32)}
        loss = lambda p: lm_loss(cfg, p, batch)
        jax.jit(loss)(params)
        assert calls, "auto resolution skipped an available variant"
        n = len(calls)
        grads = jax.jit(jax.grad(loss))(params)
        assert len(calls) == n
        assert all(bool(jnp.all(jnp.isfinite(g)))
                   for g in jax.tree.leaves(grads))
    finally:
        register_op_impl(op, "pallas", saved.fn, priority=saved.priority,
                         available=saved.available, grad=saved.grad,
                         override=True)


@pytest.mark.parametrize("tile,block", [
    (32, (32, 128, 128)), ((256, 256, 256), (256, 256, 256)),
    ((8, 16, 200), (32, 128, 256)), ((40, 130, 128), (64, 256, 128))])
def test_kernel_block_is_tpu_tileable(tile, block):
    from repro.kernels import kernel_block
    assert kernel_block(tile) == block


@pytest.mark.parametrize("shape", sorted(
    {s for shapes in MODEL_GEMMS.values() for s in shapes}
    | {(1, 4096, 4096), (8, 2048, 49155), (70, 45, 33), (1, 257, 129),
       (100_000, 100_003, 7)}))
def test_gemm_block_is_tpu_tileable_and_fits_vmem(shape):
    """At every operand width and job tile, each edge of the block the
    tiled GEMM runs is the whole dimension or a multiple of the chip's
    tiling, no less than the job tile's block where the dimension is
    larger; K and N need padding only up to a multiple of that block;
    A, B, the output and the f32 accumulator fit the kernel's VMEM."""
    from repro.kernels import (_GEMM_VMEM_BUDGET, _gemm_vmem, gemm_block,
                               kernel_block)
    m, k, n = shape
    for itemsize in (4, 2, 1):
        for tile in (32, (256, 256, 256)):
            blk = gemm_block(m, k, n, itemsize, tile)
            for d, b, b0, mult in zip((m, n, k), blk, kernel_block(tile),
                                      (32, 128, 128)):
                assert b == d or (b % mult == 0 and b >= b0), (d, blk)
                assert b == d or b <= -(-d // b0) * b0, (d, blk)
            assert _gemm_vmem(*blk, itemsize) <= _GEMM_VMEM_BUDGET, blk


# ------------------------------------------------- scheduler/registry view

def test_accelerators_are_registry_views():
    """Re-registering a kind's engine re-rates every Accelerator view —
    including accelerators built BEFORE the re-registration, and kinds
    other than the F-PE base."""
    from repro.core.clusters import S_PE, default_synergy_clusters
    base = F_PE(0).macs_per_s
    boosted = SimPEEngine("F-PE", CostModel(macs_per_s=2 * base,
                                            dispatch_s=30e-6))
    with registered(boosted):
        assert F_PE(0).macs_per_s == pytest.approx(2 * base)
    assert F_PE(0).macs_per_s == pytest.approx(base)

    spe = S_PE(0).macs_per_s
    clusters = default_synergy_clusters()      # built with the old rate
    with registered(SimPEEngine("S-PE", CostModel(macs_per_s=2 * spe,
                                                  dispatch_s=30e-6))):
        assert S_PE(0).macs_per_s == pytest.approx(2 * spe)
        pre_built = clusters[0].accelerators[2]   # an S-PE view
        assert pre_built.macs_per_s == pytest.approx(2 * spe)


def test_engine_scope_pins_auto_dispatch():
    from repro.engines import engine_scope
    a, b = _ab(16, 8, 8, seed=5)
    tr = SynergyTrace()
    with tr.activate(), engine_scope("reference"):
        synergy_matmul(a, b, tile=8)
        # explicit engine still beats the scope
        synergy_matmul(a, b, tile=8, engine="xla")
    assert set(tr.engine_stats) == {"reference", "xla"}
