"""The ResNet-50 cell on the CPU: a whole run of ``resnet50.batch64`` at a
small size (widths / 16, 32 x 32 frames, 4-frame calls, the chip check
skipped) comes
out correct, and not correct with one logit altered; the GEMM count and
FLOPs of ``bench/resnet_flops.py`` at the published widths, against the
program's own count; and the two per-layer metrics of the cell."""

from __future__ import annotations

import math
import time

import pytest

from bench import core, resnet_flops, run

PEAKS = core.peaks_for("TPU v5 lite")
RESNET = core.load_json(f"{core.BENCH}/configs/resnet50.json")
OPTS = {"engines": ["xla", "reference"], "peaks": PEAKS, "cache": False}


def small_config(cfg: dict = RESNET) -> dict:
    """The cell's configuration at widths / 16, 32 x 32 frames and 10
    classes, with one block per stage and two in the second: both
    shortcut kinds run."""
    return dict(cfg, input_hw=32, num_classes=10, layers=[
        ["conv", 4, 7, 2, 3], ["maxpool", 3, 2, 1],
        ["bottleneck", 4, 16, 1],
        ["bottleneck", 8, 32, 2], ["bottleneck", 8, 32, 1],
        ["bottleneck", 16, 64, 2], ["bottleneck", 32, 128, 2],
        ["gap"], ["fc", 10]])


@pytest.mark.parametrize("fault", [None, "answer"])
def test_resnet_run_and_its_fault(monkeypatch, fault):
    if fault:
        import repro.models.cnn as cnn
        orig = cnn.cnn_forward
        monkeypatch.setattr(cnn, "cnn_forward", lambda *a, **kw: orig(
            *a, **kw).at[0, 0].add(1.0))
    traffic = dict(core.load_traffic("batch64"), frames_per_call=4,
                   bank_calls=2)
    res = run.execute("resnet50.batch64", 2**31 + 17, 0.5, False,
                      options=dict(OPTS, traffic=traffic),
                      config=small_config(),
                      require_tpu=False, t_start=time.perf_counter())
    assert res["checks"]["frames_scored"]["value"] > 0
    assert res["correct"] is (fault is None), res["checks"]
    assert {"frames_per_s", "setup_s"} <= set(res["metrics"])


def test_gemm_count_and_flops_at_published_widths():
    from repro.models.cnn import cnn_flops_per_frame
    cnn_stream = core.load_module("drivers", "cnn_stream")
    gemms = resnet_flops.resnet_gemms(RESNET, 1)
    assert len(gemms) == 54
    assert sum(n.endswith("_proj") for n, *_ in gemms) == 4
    assert sum(m * k * n for _, m, k, n in gemms) == 4_089_184_256
    assert resnet_flops.flops_per_frame(RESNET) == \
        cnn_flops_per_frame(cnn_stream.cnn_config(RESNET))
    # the largest GEMM of a 64-frame call, the stem and stage 1's 3x3
    big = max(m * k * n for _, m, k, n in resnet_flops.resnet_gemms(
        RESNET, 64))
    assert big == 7_552_892_928


def test_gemm_count_of_the_small_config_matches_the_program():
    from repro.models.cnn import cnn_flops_per_frame
    cnn_stream = core.load_module("drivers", "cnn_stream")
    cfg = small_config()
    assert len(resnet_flops.resnet_gemms(cfg, 1)) == 1 + 5 * 3 + 4 + 1
    assert resnet_flops.flops_per_frame(cfg) == \
        cnn_flops_per_frame(cnn_stream.cnn_config(cfg))


def test_mfu_and_roofline_metrics():
    least = resnet_flops.call_least_time(RESNET, 64, PEAKS)
    assert 0.010 < least < 0.011          # 10.4 ms, bound by the f32 reads
    roof = core.load_module("metrics", "gemm_roofline.resnet50")
    ctx = {"calls": 3, "frames_per_call": 64, "config": RESNET,
           "peaks": PEAKS,
           "trace": {"lo": 0, "hi": 1e9,
                     "modules": [("jit_tiled_matmul(3)", 0, 2e8),
                                 ("jit_gather", 2e8, 9e8)]}}
    assert math.isclose(roof.read(ctx), 100 * 3 * least / 0.2)
    ctx["trace"]["modules"] = [("jit_gather", 0, 1)]
    assert roof.read(ctx) is None
    mfu = core.load_module("metrics", "mfu.resnet50")
    got = mfu.read({"window_s": 2.0, "frames": 640, "config": RESNET,
                    "peaks": PEAKS})
    assert math.isclose(got, 100 * 320 * 2 * 4_089_184_256 / 197e12)
    assert mfu.read({"window_s": 2.0, "frames": 0, "config": RESNET,
                     "peaks": PEAKS}) is None
