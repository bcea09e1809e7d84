"""The control of a cell's correctness check, on the chip: for each seed,
one run of the cell at its own size and load, the numbers it compares
beside their limits, and the same numbers for each control on the same
frames: ``program-int8`` (the program with its own int8 path switched
on) and the reference at a lower precision (``int8``, ``fp8``) in the
program's place.  All seeds run in one process.

    python bench/control.py --workload cifar_full.batch64 --seeds 1,2,3 \\
        --seconds 8 --kinds int8,fp8,program-int8

Prints one JSON line per seed; the benchmark's own runs never run it."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--kinds", default="int8,fp8,program-int8")
    args = ap.parse_args(argv)
    kinds = [k for k in args.kinds.split(",") if k]
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.execute(args.workload, seed, args.seconds, False,
                          options={"control": kinds},
                          t_start=time.perf_counter())
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "checks": {k: c["value"] for k, c in res["checks"].items()},
            "control": res.get("control", {}),
            "metrics": {k: m["value"] for k, m in res["metrics"].items()},
            "peak": res["device"]["memory_peak_bytes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
