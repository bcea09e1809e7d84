"""Benchmark harness: one function per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV per the harness contract, and dumps
full rows to a timestamped ``results/benchmarks-<UTC stamp>.json`` (plus a
``results/latest.json`` pointer) so successive runs never clobber each
other.

``--filter SUBSTR[,SUBSTR...]`` runs only benchmarks whose name contains
any listed substring; ``--smoke`` shrinks the simulated frame counts for
CI smoke jobs (``--filter quant,qmm --smoke`` is the CI benchmark-smoke
invocation, gated afterwards by ``check_regression.py`` against the
committed ``results/latest.json`` baseline).
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
import time

_ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)   # make `benchmarks.*` importable as a script

#: frame count substituted for paper_figs.FRAMES under --smoke
_SMOKE_FRAMES = 8


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--filter", default="", metavar="SUBSTR[,SUBSTR...]",
                        help="run only benchmarks whose name contains any "
                             "of these comma-separated substrings")
    parser.add_argument("--smoke", action="store_true",
                        help="shrink frame counts (CI smoke mode)")
    parser.add_argument("--trace", default="", metavar="OUT.json",
                        help="record every live-runtime benchmark on one "
                             "tracer and write a Chrome trace_event JSON "
                             "(open in chrome://tracing or ui.perfetto.dev)")
    args = parser.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(_ROOT)

    # SIGTERM mid-benchmark (CI timeout, operator ctrl) gracefully drains
    # any durable server a benchmark has live — clean final snapshot and
    # a closed journal instead of a dead pool and a torn tail
    from repro.soc import install_sigterm_handler
    install_sigterm_handler()

    tracer = None
    if args.trace:
        # process-default tracer: benchmarks construct their runtimes
        # internally, so installing the default is how --trace sees them
        from repro.obs.trace import Tracer, set_default_tracer
        tracer = Tracer(capacity=1_000_000)
        set_default_tracer(tracer)

    from benchmarks import paper_figs
    if args.smoke:
        paper_figs.FRAMES = _SMOKE_FRAMES
    # drop empty segments: a trailing comma must not silently select ALL
    # benchmarks (the smoke gate would then compare FRAMES=8 DES fps
    # against the full-frame baseline and fail spuriously)
    tokens = [t for t in args.filter.split(",") if t] or [""]
    selected = {name: fn for name, fn in paper_figs.ALL.items()
                if any(tok in name for tok in tokens)}
    if not selected:
        parser.error(f"--filter {args.filter!r} matches no benchmark "
                     f"(known: {sorted(paper_figs.ALL)})")

    os.makedirs("results", exist_ok=True)
    full = {}
    print("name,us_per_call,derived")
    for name, fn in selected.items():
        t0 = time.perf_counter()
        rows, derived = fn()
        dt_us = (time.perf_counter() - t0) * 1e6
        full[name] = {"rows": rows, "derived": derived,
                      "us_per_call": dt_us}
        key = next(iter(derived))
        val = derived[key]
        if isinstance(val, dict):
            val = json.dumps(val).replace(",", ";")
        print(f"{name},{dt_us:.0f},{key}={val}")

    # roofline summary (reads results/dryrun if present; a name filter
    # means a targeted run — skip the cross-cutting summary)
    if not args.filter:
        try:
            from benchmarks.roofline import build_table
            rows = build_table(mesh="16x16")
            cells = [r for r in rows if "skipped" not in r]
            if cells:
                mean_frac = (sum(r["roofline_fraction"] for r in cells)
                             / len(cells))
                full["roofline"] = {"rows": rows}
                print(f"roofline_16x16,0,mean_fraction={mean_frac:.3f} "
                      f"over {len(cells)} cells")
        except Exception as e:  # dry-run not yet executed
            print(f"roofline_16x16,0,unavailable({type(e).__name__})")

    # per-engine telemetry accumulated by the unified dispatch surface AND
    # the work-stealing runtime (same counters the Table-6 metric reads)
    from repro.engines import list_engines
    engines = {}
    for eng in list_engines():
        t = eng.telemetry
        if t.gemms or t.jobs:
            engines[eng.name] = {"gemms": t.gemms, "jobs": t.jobs,
                                 "busy_s_est": t.busy_s,
                                 "bytes_moved": t.bytes_moved,
                                 "steals": t.steals,
                                 "wall_busy_s": t.wall_busy_s,
                                 "idle_s": t.idle_s,
                                 "busy_fraction": t.busy_fraction}
            print(f"engine_{eng.name},0,jobs={t.jobs};steals={t.steals}")
    full["engine_telemetry"] = engines

    if tracer is not None:
        n_ev = tracer.export_chrome_trace(args.trace)
        counts = ";".join(f"{k}={v}" for k, v in
                          sorted(tracer.counts().items()))
        full["trace"] = {"path": args.trace, "trace_events": n_ev,
                         "dropped": tracer.dropped,
                         "counts": tracer.counts()}
        print(f"trace,0,path={args.trace};events={n_ev};{counts}")

    stamp = datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y%m%dT%H%M%SZ")
    out_path = os.path.join("results", f"benchmarks-{stamp}.json")
    with open(out_path, "w") as f:
        json.dump(full, f, indent=1, default=str)
    # stable pointer for tooling that wants "the most recent run"
    with open(os.path.join("results", "latest.json"), "w") as f:
        json.dump({"path": out_path, "stamp": stamp}, f, indent=1)
    print(f"results_path,0,{out_path}")


if __name__ == "__main__":
    main()
