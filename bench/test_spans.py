"""The program-span reduction (``bench/spans.py``): self time and the idle
partition on handmade spans, the five readings of the CNN cells, and a
trace of ``cnn_forward`` on the runtime recorded on the CPU."""

from __future__ import annotations

import math

import pytest

from bench import spans, tracing

C, W1, W2 = "caller", "w1", "w2"


def _s(name, s, e, thread):
    return (name, s, e, thread)


# ------------------------------------------------------------ self time
def test_self_time_subtracts_children_on_the_same_thread():
    sp = [_s("repro/panel/xla", 0, 10, W1),
          _s("repro/device_wait", 2, 7, W1),
          _s("repro/panel/pallas", 0, 10, W2),        # no child
          _s("repro/device_wait", 3, 4, W2 + "x"),    # another thread
          _s("repro/panel/xla", 20, 30, W1),
          _s("repro/device_wait", 21, 22, W1),
          _s("repro/device_wait", 24, 26, W1)]
    got = sorted(spans.nested(sp, spans.PANEL, spans.DEVICE_WAIT))
    assert got == [(10, []), (10, [1, 2]), (10, [5])]
    assert sorted(spans.self_ns(sp, spans.PANEL, spans.DEVICE_WAIT)) == \
        [5, 7, 10]
    # only the parents inside the window count
    assert spans.self_ns(sp, spans.PANEL, spans.DEVICE_WAIT, 15, 35) == [7]


def test_program_span_names_are_cut_at_metadata():
    class E:
        def __init__(self, name, s, d):
            self.name, self.start_ns, self.duration_ns = name, s, d

    class L:
        def __init__(self, events):
            self.events = events

    class P:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    class PD:
        planes = [P("/host:CPU", [L([E("repro/panel/xla#gemm=c0#", 5, 3),
                                     E("bench/call", 0, 9)]),
                                  L([E("repro/cnn/forward", 1, 8)])]),
                  P("/device:TPU:0", [L([E("repro/x", 0, 1)])])]

    assert spans.program_spans(PD()) == [
        ("repro/panel/xla", 5, 8, "/host:CPU/0"),
        ("repro/cnn/forward", 1, 9, "/host:CPU/1")]


# ------------------------------------------------------ idle partition
#: the caller: forward over [10, 100), inside it im2col [12, 20),
#: submit [20, 25), wait [25, 80), pool [80, 90), its self time elsewhere
CALLER = [_s("repro/cnn/forward", 10, 100, C),
          _s("repro/cnn/im2col", 12, 20, C),
          _s("repro/runtime/submit", 20, 25, C),
          _s("repro/runtime/wait", 25, 80, C),
          _s("repro/cnn/pool", 80, 90, C)]
#: two workers while the caller waits: w1 panel [26, 50) waits [30, 45);
#: w2 panel [40, 75) waits [42, 60), merges [62, 70); nothing in [75, 80)
WORKERS = [_s("repro/panel/xla", 26, 50, W1),
           _s("repro/device_wait", 30, 45, W1),
           _s("repro/panel/pallas", 40, 75, W2),
           _s("repro/device_wait", 42, 60, W2),
           _s("repro/runtime/merge", 62, 70, W2)]


@pytest.mark.parametrize("gap, want", [
    ((0, 10), {spans.OUTSIDE: 10}),                    # before the call
    ((100, 105), {spans.OUTSIDE: 5}),                  # after it
    ((10, 12), {"cnn/forward": 2}),                    # forward self time
    ((14, 20), {"cnn/im2col": 6}),
    ((20, 25), {"runtime/submit": 5}),
    ((85, 95), {"cnn/pool": 5, "cnn/forward": 5}),
    ((25, 26), {spans.NO_PANEL: 1}),                   # wait, no panel yet
    ((26, 30), {spans.PANEL_HOST: 4}),                 # w1 dispatching
    ((30, 40), {spans.WAITING: 10}),                   # w1 waits alone
    # w2 dispatches while w1 waits: panel host comes first
    ((40, 42), {spans.PANEL_HOST: 2}),
    ((42, 45), {spans.WAITING: 3}),                    # both wait
    ((45, 50), {spans.PANEL_HOST: 5}),                 # w1 after its wait
    ((60, 62), {spans.PANEL_HOST: 2}),
    ((62, 70), {spans.MERGING: 8}),
    ((70, 80), {spans.PANEL_HOST: 5, spans.NO_PANEL: 5}),
])
def test_idle_partition_rules(gap, want):
    assert spans.idle_by_span([gap], CALLER + WORKERS) == want


def test_merge_outranks_device_wait_and_panel_host_outranks_merge():
    both = [_s("repro/panel/a", 0, 10, W1), _s("repro/device_wait", 0, 10, W1),
            _s("repro/panel/b", 0, 10, W2), _s("repro/runtime/merge", 0, 10,
                                               W2)]
    caller = [_s("repro/cnn/forward", 0, 20, C),
              _s("repro/runtime/wait", 0, 20, C)]
    assert spans.idle_by_span([(0, 10)], caller + both) == \
        {spans.MERGING: 10}
    host = both[:3]                         # w2 in its panel, not merging
    assert spans.idle_by_span([(0, 10)], caller + host) == \
        {spans.PANEL_HOST: 10}


def test_idle_partition_sums_to_the_idle_time():
    ops = [("op", 11, 14), ("op", 28, 33), ("op", 47, 66), ("op", 90, 92)]
    idle = tracing.gaps(ops, 0, 110)
    got = spans.idle_by_span(idle, CALLER + WORKERS)
    assert sum(got.values()) == sum(e - s for s, e in idle)
    assert got[spans.OUTSIDE] == 10 + 10           # [0,10) and [100,110)
    # no program spans at all: everything is outside the program
    assert spans.idle_by_span(idle, []) == \
        {spans.OUTSIDE: sum(e - s for s, e in idle)}
    assert spans.idle_by_span([], CALLER + WORKERS) == {}


# ------------------------------------------------------------ readings
def test_readings_of_the_cnn_cells():
    sp = CALLER + WORKERS
    assert spans.panel_host_ms(sp, 0, 110) == pytest.approx(
        ((24 - 15) + (35 - 18)) / 2 / 1e6)
    assert spans.panel_device_wait_ms(sp, 0, 110) == pytest.approx(
        16.5 / 1e6)
    assert spans.panel_host_ms(sp, 100, 110) is None
    assert spans.caller_host_share(sp, 0, 100) == pytest.approx(
        100 * (90 - 55) / 100)
    assert spans.caller_host_share([], 0, 100) is None
    assert spans.queue_wait_ms(0.5, 1000) == 0.5
    assert spans.queue_wait_ms(None, 1000) is None
    assert spans.queue_wait_ms(0.0, 0) is None
    part = {spans.PANEL_HOST: 3.0, spans.WAITING: 1.0, spans.OUTSIDE: 4.0}
    assert spans.idle_in_panel_host_share(part) == 37.5
    assert spans.idle_in_panel_host_share({spans.OUTSIDE: 4.0}) is None
    assert spans.idle_in_panel_host_share({}) is None


# ---------------------------------------------------- a recorded trace
def test_recorded_cnn_forward_trace_on_the_runtime(tmp_path):
    import jax
    from repro.models.cnn import CNNConfig, cnn_forward, init_cnn
    from repro.soc import SynergyRuntime

    net = CNNConfig(name="tiny", input_hw=8, cin=1, tile=8, layers=(
        ("conv", 4, 3, 1, 1), ("pool", 2), ("conv", 8, 3, 1, 1),
        ("fc", 10)))
    params = init_cnn(net, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (4, 8, 8, 1))
    rt = SynergyRuntime(["xla", "reference"], name="traced").start()
    try:
        jax.block_until_ready(cnn_forward(net, params, x, runtime=rt))
        p0 = rt.stats()["total_panels"]
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
                for _ in range(2):
                    jax.block_until_ready(
                        cnn_forward(net, params, x, runtime=rt))
        finally:
            rt.shutdown()               # every worker has left its panel
            jax.profiler.stop_trace()
        panels = rt.stats()["total_panels"] - p0
    finally:
        rt.shutdown()
    sp = spans.program_spans(tracing.load(str(tmp_path)))
    names = {n for n, *_ in sp}
    callers = {t for n, _, _, t in sp if n == spans.FORWARD}
    assert len(callers) == 1
    on_caller = {n for n, _, _, t in sp if t in callers}
    on_workers = {n for n, _, _, t in sp if t not in callers}
    assert on_caller == {"repro/cnn/forward", "repro/cnn/im2col",
                         "repro/cnn/pool", "repro/runtime/submit",
                         "repro/runtime/wait"}
    assert {"repro/device_wait", "repro/runtime/merge"} <= on_workers
    assert {n for n in on_workers if n.startswith(spans.PANEL)} <= \
        {"repro/panel/xla", "repro/panel/reference"}
    assert names - on_caller - on_workers == set()
    # 2 calls x 3 GEMMs, every panel holds exactly one device wait
    assert sum(n == spans.FORWARD for n, *_ in sp) == 2
    assert sum(n == "repro/runtime/wait" for n, *_ in sp) == 6
    kids = spans.nested(sp, spans.PANEL, spans.DEVICE_WAIT)
    assert [len(k) for _, k in kids] == [1] * len(kids)
    assert len(kids) == panels > 0
    assert sum(n == spans.MERGE for n, *_ in sp) == 6
    # the partition of a window with no device plane: all host time
    lo, hi = tracing.window_bounds(tracing.host_spans(tracing.load(
        str(tmp_path))))
    part = spans.idle_by_span([(lo, hi)], sp)
    assert math.isclose(sum(part.values()), hi - lo)
    assert part.get(spans.PANEL_HOST, 0) > 0
