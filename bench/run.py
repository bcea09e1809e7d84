"""Run one cell of BENCHMARK.json once, on the chips of the machine it is
started on, and print the result as the last line of standard output.

    python bench/run.py --workload cifar_full.batch64 --seed 7 --seconds 30 --trace 0

It loads the cell's configuration and traffic by name, makes weights and
inputs from ``--seed``, warms up (all of it ``setup_s``), measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints ``{"correct", "attempted", "failed", "metrics",
"device", ["breakdown"], "checks"}``.  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` traces the window with the JAX profiler
and reports its per-layer metrics instead.  Where JAX finds no TPU, or
fewer chips than the cell asks for, it exits non-zero and prints no
result."""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                              # noqa: E402
import dataclasses                                           # noqa: E402
import os                                                    # noqa: E402
import shutil                                                # noqa: E402
import sys                                                   # noqa: E402
import tempfile                                              # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import core, tracing                              # noqa: E402


@dataclasses.dataclass
class Cell:
    """What a driver gets: the cell's files, its seed and length, the
    devices, and the hooks that time and trace the window."""

    name: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    counter: core.CompileCounter
    options: dict
    window_start: float | None = None

    def annotate(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def mark_window_start(self, t: float) -> None:
        self.window_start = t

    def start_trace(self) -> str:
        """The profiler on, with the device's operations and the host's
        annotations (the benchmark's ``bench/...`` spans) but no Python
        function events, which would slow the host-bound loops several
        fold and swell the trace."""
        import jax
        d = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(d, profiler_options=opts)
        return d

    def stop_trace(self, logdir: str) -> dict:
        import jax
        jax.profiler.stop_trace()
        try:
            pd = tracing.load(logdir)
            for line in tracing.describe(pd):
                print(f"trace: {line}", file=sys.stderr)
            red = tracing.reduce(pd)
            for name, sec in tracing.top_ops(red["modules"], red["lo"],
                                             red["hi"], 15):
                print(f"trace: program {name} {sec!r} s", file=sys.stderr)
            return red
        finally:
            shutil.rmtree(logdir, ignore_errors=True)


def _passes(check: dict) -> bool:
    v, lim = check["value"], check["limit"]
    if v is None:
        return False
    return v >= lim if check["rule"] == "at least" else v <= lim


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            root: str = ROOT, options: dict | None = None,
            config: dict | None = None, require_tpu: bool = True,
            t_start: float = T_START) -> dict:
    """One run of ``workload``; returns the result object.  ``config``,
    ``options`` and ``require_tpu=False`` let a test drive the same run at
    a small size on the CPU."""
    options = dict(options or {})
    try:
        import repro  # noqa: F401  the system under test
    except ImportError:
        raise core.BenchError(f"the program (src/repro) is not in {root}")
    manifest = core.load_manifest(root)
    entry = core.find_cell(manifest, workload)
    cfg = config if config is not None else core.load_config(
        manifest, entry["config"], root)
    traffic = options.get("traffic") or core.load_traffic(entry["traffic"])
    devices = core.require_devices(int(entry["chips"]),
                                   require_tpu=require_tpu)
    peaks = options.get("peaks") or core.peaks_for(devices[0].device_kind)
    if options.get("cache", True):
        path = core.enable_cache(root)
        print(f"device: {devices[0].device_kind} x{len(devices)}; compile "
              f"cache {path}", file=sys.stderr, flush=True)
    cell = Cell(workload, cfg, traffic, seed, seconds, trace, devices,
                core.CompileCounter(), options)
    driver = core.load_module("drivers", cfg["driver"])
    out = driver.run(cell)
    setup_s = cell.window_start - t_start

    metrics = {}
    if trace:
        ctx = dict(out["layer_ctx"], config=cfg, peaks=peaks,
                   cell=workload)
        for m in core.cell_metrics(manifest, workload, "per_layer"):
            value = core.load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(out["metrics"], setup_s=setup_s)
        for m in core.cell_metrics(manifest, workload, "end_to_end"):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    print(f"setup_s {setup_s!r}; window_s {out['window_s']!r}",
          file=sys.stderr, flush=True)
    device = core.device_info(devices, out["memory_peak"])
    result = {"correct": all(_passes(c) for c in out["checks"].values()),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    tr = out["layer_ctx"].get("trace")
    if trace and tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = tr["breakdown"]
    if out.get("control"):
        result["control"] = out["control"]
    result["checks"] = out["checks"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = execute(args.workload, args.seed, args.seconds,
                         bool(args.trace))
    except core.BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    core.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
