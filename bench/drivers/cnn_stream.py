"""Driver of the CNN frame-stream cells: ``cnn_forward(runtime=rt)`` on the
runtime's pool, called back to back by one client.

Set-up makes the weights and a bank of calls' frames from the seed, and
warms every shape: a call on each engine alone (so a steal of any panel
by any engine finds its program compiled; up to 32 frames give every
panel shape of the cell, 32 rows each) and a call on the whole pool.  In
the window the client calls on the bank's frames in turn; the window
closes when the call in flight at ``--seconds`` returns, so the rate is
all frames over all the time.  Afterwards the reference scores the
outputs of a sample of the calls drawn from the seed (``compare``)."""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import core, gen

#: frames scored: whole calls drawn from the seed until this many
REF_FRAMES = 256
#: limit on the widest per-frame logit error over the frame's largest
#: reference logit (an answer altered where it is produced), set between
#: the program's largest reading and the fp8 control's smallest
ERR_LIMIT = 0.04
#: limit on the RMS logit error over every scored frame, in units of the
#: RMS error that rounding every GEMM operand to bf16 (the configuration's
#: stated precision) gives the reference on the same frames; set between
#: the program's largest reading and the int8 controls' smallest.
#: PERF.md gives the readings of both limits.
RMS_LIMIT = 2.0


def cnn_config(cfg: dict):
    from repro.models.cnn import CNNConfig
    return CNNConfig(name=cfg["name"], input_hw=int(cfg["input_hw"]),
                     cin=int(cfg["cin"]),
                     layers=tuple(tuple(x) for x in cfg["layers"]),
                     num_classes=int(cfg["num_classes"]),
                     tile=int(cfg["tile"]))


def _check_layout(net, params) -> None:
    import jax
    from repro.models.cnn import init_cnn
    want = jax.eval_shape(lambda k: init_cnn(net, k), jax.random.key(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise core.BenchError(f"the program's CNN layout changed: "
                              f"{want} != {got}")


def compare(got, want, yard) -> dict:
    """The numbers ``correct`` compares: logits ``got`` (N, classes)
    against the reference's ``want``, where ``yard`` is the reference with
    every GEMM operand on the bf16 grid."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    yard = np.asarray(yard, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        widest = ratio = float("inf")
    else:
        err = got - want
        scale = np.maximum(np.abs(want).max(-1), 1e-30)
        widest = float((np.abs(err).max(-1) / scale).max())
        ratio = float(np.sqrt(np.mean(err ** 2))
                      / np.sqrt(np.mean((yard - want) ** 2)))
    return {"logit_error": {"value": widest, "limit": ERR_LIMIT,
                            "rule": "at most"},
            "logit_rms_ratio": {"value": ratio, "limit": RMS_LIMIT,
                                "rule": "at most"}}


def program_int8(net, params, calls):
    """The program's own int8 path switched on, on the same calls: a pool
    of the xla engine's int8 twin (per-channel int8 weights, calibrated
    per-tensor int8 activations, int32 accumulation), with every GEMM in
    the ``decode`` job class, which admits int8 engines."""
    from repro.engines import get_engine
    from repro.models.cnn import cnn_forward
    from repro.quant.engine import QuantizedEngine
    from repro.soc import SynergyRuntime
    with SynergyRuntime([QuantizedEngine(get_engine("xla"))],
                        name="int8") as rt:
        return np.concatenate([np.asarray(cnn_forward(
            net, params, c, runtime=rt, job_class="decode")) for c in calls])


def run(cell) -> dict:
    import jax
    from repro.models.cnn import cnn_forward
    from repro.soc import SynergyRuntime

    cfg, traffic = cell.config, cell.traffic
    ref = core.load_module("reference", cfg["reference"])
    net = cnn_config(cfg)
    key = core.seed_key(cell.seed)
    engines = cell.options.get("engines")

    # ---------------------------------------------------------- set-up
    params = ref.make_params(cfg, key)
    bank = gen.frame_bank(traffic, cell.seed,
                          (net.input_hw, net.input_hw, net.cin))
    jax.block_until_ready((params, bank))
    _check_layout(net, params)
    rt = SynergyRuntime(engines) if engines else SynergyRuntime()
    rt.start()
    solo_frames = bank[0][: min(bank.shape[1], net.tile)]
    for name in rt.engine_names:
        with SynergyRuntime([rt.find_engine(name)], name=f"warm-{name}") \
                as solo:
            jax.block_until_ready(cnn_forward(net, params, solo_frames,
                                              runtime=solo))
    jax.block_until_ready(cnn_forward(net, params, bank[0], runtime=rt))

    # ---------------------------------------------------------- window
    seconds = cell.seconds
    n_bank = bank.shape[0]
    frames_per_call = bank.shape[1]
    outs = []
    c0 = cell.counter.snapshot()
    jobs0 = rt.stats()["total_jobs"]
    tracer = cell.start_trace() if cell.trace else None
    calls = 0
    call_s = []
    t0 = time.perf_counter()
    cell.mark_window_start(t0)
    now = t0
    with cell.annotate("bench/window"):
        while now - t0 < seconds:
            with cell.annotate("bench/call"):
                y = jax.block_until_ready(
                    cnn_forward(net, params, bank[calls % n_bank],
                                runtime=rt))
            t = time.perf_counter()
            call_s.append(t - now)
            now = t
            outs.append(y)
            calls += 1
    t_end = now
    trace = cell.stop_trace(tracer) if tracer else None
    c1 = cell.counter.snapshot()
    jobs1 = rt.stats()["total_jobs"]
    window_s = t_end - t0
    rt.shutdown()
    peak = core.memory_peak(cell.devices)
    frames = calls * frames_per_call
    print(f"cnn: window {window_s:.3f} s; calls {calls} of "
          f"{frames_per_call} frames; call seconds median "
          f"{core.percentile(call_s, 50):.4f} p95 "
          f"{core.percentile(call_s, 95):.4f}; runtime jobs "
          f"{jobs1 - jobs0}; programs compiled in the window "
          f"{c1[0] - c0[0] - (c1[1] - c0[1])}, loaded from the cache "
          f"{c1[1] - c0[1]} ({c1[2] - c0[2]:.3f} s for both); peak bytes "
          f"{peak}", flush=True)
    metrics = {"frames_per_s": core.rate(frames, window_s)}
    layer_ctx = {"window_s": window_s, "calls": calls, "frames": frames,
                 "frames_per_call": frames_per_call,
                 "runtime_jobs": jobs1 - jobs0, "trace": trace}

    # -------------------------------------------------------- correctness
    pick = gen.sample_indices(cell.seed, calls,
                              max(1, REF_FRAMES // frames_per_call), 3)
    got = np.concatenate([np.asarray(outs[i]) for i in pick])
    x_calls = [bank[i % n_bank] for i in pick]
    del rt, outs, params
    gc.collect()
    params = ref.make_params(cfg, key)
    x = jax.numpy.concatenate(x_calls)
    forward = jax.jit(lambda p, x, q: ref.forward(cfg, p, x, quant=q),
                      static_argnums=2)
    want = np.asarray(forward(params, x, None))
    yard = np.asarray(forward(params, x, "bf16"))
    checks = compare(got, want, yard)
    scored = int(want.shape[0])
    control = {}
    for kind in cell.options.get("control", ()):
        try:
            low = (program_int8(net, params, x_calls)
                   if kind == "program-int8"
                   else np.asarray(forward(params, x, kind)))
        except Exception as e:  # a control that crashes has failed
            control[kind] = {"error": repr(e)}
            continue
        control[kind] = {k: c["value"]
                         for k, c in compare(low, want, yard).items()}
    print(f"correct: {len(pick)} calls, {scored} frames scored; "
          + "; ".join(f"{k} {c['value']!r} (limit {c['limit']})"
                      for k, c in checks.items()), flush=True)
    checks = {"frames_scored": {"value": scored, "limit": 1,
                                "rule": "at least"}, **checks}
    return {"metrics": metrics, "layer_ctx": layer_ctx, "checks": checks,
            "control": control, "attempted": calls, "failed": 0,
            "memory_peak": peak, "window_s": window_s}
