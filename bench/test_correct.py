"""What decides ``correct``, driven at a small size on the CPU: a whole run
of the cells' driver (the chip check skipped) comes out correct, comes
out not correct with its timed path broken underneath, and each control
(the program's own int8 path, the reference at int8 and at fp8) fails
the cells' limits."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import core, run

PEAKS = core.peaks_for("TPU v5 lite")
CNN = core.load_json(f"{core.BENCH}/configs/cifar_full.json")
OPTS = {"engines": ["xla", "reference"], "peaks": PEAKS, "cache": False}


def _run(workload, seconds=1.0):
    return run.execute(workload, 2**31 + 11, seconds, False,
                       options=dict(OPTS), require_tpu=False,
                       t_start=time.perf_counter())


def _break(monkeypatch, fault):
    """``answer``: one logit of every call altered where it is produced;
    ``half``: the second half of every call's frames left out, the first
    half's logits returned in their place."""
    import repro.models.cnn as cnn
    orig = cnn.cnn_forward
    if fault == "answer":
        broken = lambda *a, **kw: orig(*a, **kw).at[0, 0].add(1.0)  # noqa: E731
    else:
        def broken(cfg, params, x, **kw):
            half = orig(cfg, params, x[: x.shape[0] // 2], **kw)
            return jnp.concatenate([half, half])
    monkeypatch.setattr(cnn, "cnn_forward", broken)


@pytest.mark.parametrize("workload,fault", [
    ("cifar_full.stream1", None), ("cifar_full.stream1", "answer"),
    ("cifar_full.batch64", "half")])
def test_cnn_run_and_its_fault(monkeypatch, workload, fault):
    if fault:
        _break(monkeypatch, fault)
    res = _run(workload)
    assert res["checks"]["frames_scored"]["value"] > 0
    assert res["correct"] is (fault is None), res["checks"]
    assert list(res)[-1] == "checks"
    assert {"frames_per_s", "setup_s"} <= set(res["metrics"])


def test_no_tpu_no_result():
    with pytest.raises(core.BenchError):
        run.execute("cifar_full.stream1", 1, 1.0, False, options=OPTS)


# ------------------------------------------------------------- controls
@pytest.fixture(scope="module")
def frames():
    """A 64-frame call, the reference's logits, and its bf16 yardstick."""
    ref = core.load_module("reference", "paper_cnn")
    params = ref.make_params(CNN, jax.random.key(5))
    x = jax.random.normal(jax.random.key(6), (64, 32, 32, 3))
    return (params, x, np.asarray(ref.forward(CNN, params, x)),
            np.asarray(ref.forward(CNN, params, x, quant="bf16")))


def test_cnn_yardstick_passes(frames):
    """The reference itself, and the reference on the bf16 grid (the
    configuration's stated precision), pass every limit."""
    cnn_stream = core.load_module("drivers", "cnn_stream")
    _, _, exact, yard = frames
    for got in (exact, yard):
        checks = cnn_stream.compare(got, exact, yard)
        assert all(run._passes(c) for c in checks.values()), checks


@pytest.mark.parametrize("kind", ["int8", "fp8", "program-int8"])
def test_cnn_control_fails_the_limit(frames, kind):
    """Each control, put where the program's logits come from, comes out
    not correct by the harness's own rule."""
    cnn_stream = core.load_module("drivers", "cnn_stream")
    ref = core.load_module("reference", "paper_cnn")
    params, x, exact, yard = frames
    if kind == "program-int8":
        low = cnn_stream.program_int8(cnn_stream.cnn_config(CNN), params,
                                      [x])
    else:
        low = np.asarray(ref.forward(CNN, params, x, quant=kind))
    checks = cnn_stream.compare(low, exact, yard)
    assert not all(run._passes(c) for c in checks.values()), checks
    assert not run._passes(checks["logit_rms_ratio"]), checks
