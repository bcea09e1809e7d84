"""Shared pieces of the benchmark: the manifest and the files it names, the
device check, the peaks table, the arithmetic of percentiles and rates,
compile counting and the result line.

Everything a cell needs is found by name: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``drivers/<driver>.py`` (named by the
configuration), ``reference/<reference>.py`` (likewise) and
``metrics/<metric>.py`` for each per-layer metric."""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class BenchError(RuntimeError):
    """A cell cannot be run as asked: no result is printed."""


# ------------------------------------------------------------------ files
def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchError(f"no BENCHMARK.json in {root}")
    return load_json(path)


def find_cell(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"no workload {name!r} in BENCHMARK.json; known: "
                     f"{[c['name'] for c in manifest['workloads']]}")


def config_entry(manifest: dict, name: str) -> dict:
    for cfg in manifest["configs"]:
        if cfg["name"] == name:
            return cfg
    raise BenchError(f"no config {name!r} in BENCHMARK.json")


def load_config(manifest: dict, name: str, root: str = ROOT) -> dict:
    return load_json(os.path.join(root, config_entry(manifest, name)["file"]))


def load_traffic(name: str) -> dict:
    return load_json(os.path.join(BENCH, "traffic", f"{name}.json"))


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold ``.`` and
    ``-``, so they are loaded by path, not imported)."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.exists(path):
        raise BenchError(f"no {kind} file {path}")
    modname = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(manifest: dict, cell: str, section: str) -> list[dict]:
    """The metrics of ``section`` (``end_to_end`` | ``per_layer``) that
    ``cell`` reports: those without a ``workloads`` key, and those whose
    key lists the cell."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]]


# ------------------------------------------------------------------ peaks
def load_peaks() -> dict:
    return load_json(os.path.join(BENCH, "peaks.json"))


def peaks_for(kind: str, table: dict | None = None) -> dict:
    """Published peaks of one device kind; an unknown kind is an error."""
    table = table if table is not None else load_peaks()
    try:
        return table["devices"][kind]
    except KeyError:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json "
                         f"(known: {sorted(table['devices'])})") from None


# ---------------------------------------------------------------- devices
def require_devices(n: int, *, require_tpu: bool = True):
    """The first ``n`` devices JAX sees; fails where there is no TPU or
    fewer than ``n`` chips."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU found (JAX sees {devs[0].platform}); "
                         "nothing was run")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def memory_peak(devices) -> int | None:
    """Peak bytes in use on the fullest of ``devices`` (None where the
    backend keeps no such counter)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def seed_key(seed: int):
    """A JAX key from any whole-number seed (more than 32 bits too):
    the low 31 bits seed the key and the rest is folded in."""
    import jax
    if seed < 0:
        raise BenchError(f"seed must be >= 0: {seed}")
    key = jax.random.key(seed & 0x7FFFFFFF)
    hi = seed >> 31
    while hi:
        key = jax.random.fold_in(key, hi & 0x7FFFFFFF)
        hi >>= 31
    return key


# ------------------------------------------------------------- arithmetic
def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) of all ``values``, by linear
    interpolation between the closest ranks."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(count: float, seconds: float) -> float:
    """Work per second over the whole window."""
    if seconds <= 0:
        raise ValueError(f"rate over a window of {seconds} s")
    return count / seconds


# -------------------------------------------------------------- compiles
class CompileCounter:
    """Counts XLA compilations (and persistent-cache loads among them)
    through JAX's monitoring events, so a window can show that nothing
    compiled inside it."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self._COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == self._HIT:
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, int, float]:
        return self.compiles, self.cache_hits, self.compile_s


def enable_cache(root: str) -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``,
    whatever ``JAX_COMPILATION_CACHE_DIR`` the environment held (the
    program takes the directory the benchmark gives it), caching every
    program however small (the eager ops of the runtime's panels compile
    in milliseconds each, many of them)."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    if enable_compile_cache(root) != path:
        raise BenchError("the program chose another compilation cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ----------------------------------------------------------------- result
def device_info(devices, peak_bytes) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak_bytes}


def print_result(result: dict) -> None:
    """The compared numbers beside their limits as the last lines of
    standard error, then the result as the last line of standard output
    (``checks`` last)."""
    checks = result.get("checks", {})
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"({c.get('rule', '')})", file=sys.stderr, flush=True)
    out = {k: v for k, v in result.items() if k != "checks"}
    out["checks"] = checks
    print(json.dumps(out), flush=True)
