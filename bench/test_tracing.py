"""The reduction from traces to metrics, on handmade intervals and on a
small trace recorded on the CPU, and the arithmetic of percentiles,
rates, roofline shares and MFU against the peaks table."""

from __future__ import annotations

import math

import numpy as np
import pytest

from bench import core, flops, tracing

PEAKS = core.peaks_for("TPU v5 lite")


# ------------------------------------------------------------ intervals
def test_busy_is_the_union_of_intervals():
    ivs = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 25, 26)]
    assert tracing.merged(ivs, 0, 40) == [(0, 15), (20, 30)]
    assert tracing.busy_ns(ivs, 0, 40) == 25
    assert tracing.busy_ns(ivs, 8, 22) == 9          # clipped to the window
    assert tracing.gaps(ivs, 0, 40) == [(15, 20), (30, 40)]
    assert tracing.gaps([], 3, 7) == [(3, 7)]


def test_kernel_time_by_name_list():
    ivs = [("jit_tiled_matmul(1)", 0, 10), ("jit_vpu_matmul", 10, 14),
           ("jit_add", 14, 20), ("jit_tiled_matmul(2)", 30, 40)]
    assert tracing.matching_ns(ivs, [r"^jit_tiled_matmul"]) == 20
    assert tracing.matching_ns(ivs, [r"^jit_tiled_matmul",
                                     r"^jit_vpu_matmul"]) == 24
    assert tracing.matching_ns(ivs, [r"^jit_tiled_matmul"], 5, 35) == 10
    assert tracing.durations(ivs, [r"matmul"], 0, 35) == [10, 4]


def test_short_op_names():
    assert tracing.short_name(
        "%copy-done.3 = f32[65536,75]{0,1:T(8,128)S(1)} copy-done((f32[65536"
        ",75]{0,1}) %copy-start)") == "copy-done f32[65536,75]"
    assert tracing.short_name(
        "%tiled_matmul.1 = f32[32,128]{1,0:T(8,128)} custom-call(f32[32,896]"
        ")") == "tiled_matmul f32[32,128]"
    assert tracing.short_name("dot_general.1") == "dot_general.1"
    ivs = [("%fusion.1 = f32[8]{0} fusion(x)", 0, 4),
           ("%fusion.2 = f32[8]{0} fusion(y)", 4, 6)]
    assert tracing.top_ops(ivs, 0, 10) == [["fusion f32[8]", 6e-9]]


def test_breakdown_top_ops_and_labelled_gaps():
    ivs = [("x", 0, 10), ("y", 12, 13), ("x", 20, 25)]
    assert tracing.top_ops(ivs, 0, 30) == [["x", 15e-9], ["y", 1e-9]]
    spans = [("bench/window", 0, 30), ("bench/call", 9, 21),
             ("bench/between_calls", 21, 30)]
    gaps = tracing.gaps(ivs, 0, 30)                   # (10,12) (13,20) (25,30)
    labelled = tracing.label_gaps(gaps, spans, k=2)
    assert labelled == [["bench/call", 7e-9],
                        ["bench/between_calls", 5e-9]]
    assert len(tracing.top_ops(ivs * 20, 0, 30, k=10)) <= 10


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench/call"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    pd = tracing.load(str(tmp_path))
    red = tracing.reduce(pd, plane_prefix="/host:CPU", op_line=None,
                         op_line_prefix="tf_XLA", module_line=None)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["breakdown"]["device_ops"]
    assert all(isinstance(n, str) and s > 0
               for n, s in red["breakdown"]["device_ops"])
    assert len(red["breakdown"]["idle_gaps"]) <= 10
    # every op interval the busy time counts lies in the window
    assert red["busy_s"] * 1e9 <= sum(e - s for _, s, e in red["ops"]) + 1
    assert any(n.startswith("bench/call")
               for n, _ in red["breakdown"]["idle_gaps"]) or not \
        red["breakdown"]["idle_gaps"]
    with pytest.raises(ValueError):
        tracing.reduce(pd, plane_prefix="/device:TPU")


# ----------------------------------------------------------- arithmetic
@pytest.mark.parametrize("q", [0, 50, 90, 95, 100])
def test_percentile_over_all_samples(q):
    rng = np.random.default_rng(0)
    xs = list(rng.exponential(size=301))
    assert math.isclose(core.percentile(xs, q), float(np.percentile(xs, q)),
                        rel_tol=1e-12)
    # of all samples, not of chunk medians
    chunks = [xs[i:i + 10] for i in range(0, 300, 10)]
    med = [float(np.median(c)) for c in chunks]
    if q == 95:
        assert core.percentile(xs, q) != core.percentile(med, q)


def test_rate_over_the_whole_window():
    assert core.rate(300, 12.0) == 25.0
    with pytest.raises(ValueError):
        core.rate(1, 0.0)


def test_unknown_device_kind_raises():
    with pytest.raises(core.BenchError):
        core.peaks_for("TPU v99")
    assert PEAKS["bf16_flops"] == 197e12
    assert PEAKS["hbm_bytes_per_s"] == 819e9


def test_roofline_share_and_mfu_against_the_peaks():
    cfg = core.load_json(f"{core.BENCH}/configs/cifar_full.json")
    # conv0 of one frame: m=1024, k=75, n=32, memory-bound on a v5e
    t, bound = flops.gemm_least_time(1024, 75, 32, PEAKS)
    assert bound == "memory"
    assert math.isclose(t, 4 * (1024 * 75 + 75 * 32 + 32 + 1024 * 32)
                        / 819e9)
    t, bound = flops.gemm_least_time(4096, 4096, 4096, PEAKS)
    assert bound == "compute" and math.isclose(t, 2 * 4096 ** 3 / 197e12)
    assert math.isclose(flops.cnn_flops_per_frame(cfg), 24_596_480)
    least = flops.cnn_call_least_time(cfg, 64, PEAKS)
    roof = core.load_module("metrics", "gemm_roofline.cnn")
    ctx = {"calls": 10, "frames_per_call": 64, "config": cfg,
           "peaks": PEAKS,
           "trace": {"lo": 0, "hi": 1e9,
                     "modules": [("jit_tiled_matmul(3)", 0, 2e6),
                                 ("jit_add", 2e6, 9e6)]}}
    assert math.isclose(roof.read(ctx), 100 * 10 * least / 2e-3)
    ctx["trace"]["modules"] = [("jit_add", 0, 1)]
    assert roof.read(ctx) is None           # nothing to read: no number
    mfu = core.load_module("metrics", "mfu.cnn")
    got = mfu.read({"window_s": 2.0, "frames": 100, "config": cfg,
                    "peaks": PEAKS})
    assert math.isclose(got, 100 * 50 * 24_596_480 / 197e12)


def test_idle_share_and_counter_metrics():
    idle = core.load_module("metrics", "device_idle_share.cnn")
    assert math.isclose(idle.read({"trace": {"busy_s": 1.0,
                                             "window_s": 4.0}}), 75.0)
    assert idle.read({"trace": None}) is None
    jobs = core.load_module("metrics", "runtime_jobs_per_frame.cnn")
    assert jobs.read({"runtime_jobs": 450, "frames": 10}) == 45.0
    assert jobs.read({"runtime_jobs": 0, "frames": 0}) is None
