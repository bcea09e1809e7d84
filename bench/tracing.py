"""Reduction of a JAX profiler trace to the numbers the benchmark reports:
device busy time as the union of operation intervals, time of named
kernels, the top operations, and the idle gaps labelled by what the
benchmark's loop was doing (its ``bench/...`` host spans).

Traces are read with ``jax.profiler.ProfileData`` alone.  An interval is
``(name, start_ns, end_ns)``; everything below works on such lists, so it
can be checked on handmade ones as well as on a recorded trace."""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

#: the benchmark's host span around the traced window
WINDOW_SPAN = "bench/window"

_HLO_OP = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)* = ")
_SHAPE = re.compile(r"[a-z]+\d*\[[\d,]*\]")


def short_name(name: str) -> str:
    """An HLO op as the trace names it (``%copy-done.3 = f32[64,75]{...}
    copy-done(...)``), cut to its kind and result shape
    (``copy-done f32[64,75]``); other names pass unchanged."""
    m = _HLO_OP.match(name)
    if not m:
        return name
    shape = _SHAPE.search(name, m.end())
    return f"{m.group(1)} {shape.group(0)}" if shape else m.group(1)


def load(logdir: str):
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return ProfileData.from_file(paths[-1])


def line_events(pd, plane_prefix: str, line_name: str | None = None,
                line_prefix: str | None = None) -> dict[str, list]:
    """Per plane whose name starts with ``plane_prefix``, the intervals of
    the line named ``line_name`` (or of every line whose name starts with
    ``line_prefix``)."""
    out: dict[str, list] = {}
    for plane in pd.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        evs = []
        for line in plane.lines:
            if line_name is not None and line.name != line_name:
                continue
            if line_prefix is not None and not line.name.startswith(
                    line_prefix):
                continue
            for e in line.events:
                if e.duration_ns > 0:
                    evs.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
        out[plane.name] = evs
    return out


def host_spans(pd, prefix: str = "bench/") -> list:
    """The benchmark's own host spans (``TraceAnnotation`` names starting
    with ``prefix``) on any host thread."""
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns))
    return spans


def window_bounds(spans: list) -> tuple[float, float]:
    ws = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not ws:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return min(s for s, _ in ws), max(e for _, e in ws)


def merged(intervals: list, lo: float, hi: float) -> list:
    """The union of the intervals clipped to [lo, hi], as disjoint sorted
    (start, end) pairs."""
    out: list = []
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(intervals: list, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(intervals, lo, hi))


def gaps(intervals: list, lo: float, hi: float) -> list:
    """The idle stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in merged(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def matching_ns(intervals: list, patterns: list[str], lo: float = -1e30,
                hi: float = 1e30) -> float:
    """Summed duration (clipped to [lo, hi]) of the intervals whose name
    matches any of the regular expressions ``patterns``."""
    rx = [re.compile(p) for p in patterns]
    return sum(max(0.0, min(e, hi) - max(s, lo)) for n, s, e in intervals
               if any(r.search(n) for r in rx))


def durations(intervals: list, patterns: list[str], lo: float = -1e30,
              hi: float = 1e30) -> list[float]:
    """Durations in ns of the matching intervals that lie in [lo, hi]."""
    rx = [re.compile(p) for p in patterns]
    return [e - s for n, s, e in intervals
            if s >= lo and e <= hi and any(r.search(n) for r in rx)]


def top_ops(intervals: list, lo: float, hi: float, k: int = 10) -> list:
    """The ``k`` operations (by :func:`short_name`) that took most device
    time, in seconds."""
    tot: dict[str, float] = defaultdict(float)
    for n, s, e in intervals:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            tot[short_name(n)] += d
    best = sorted(tot.items(), key=lambda x: -x[1])[:k]
    return [[n, ns / 1e9] for n, ns in best]


def label_gaps(idle: list, spans: list, k: int = 10) -> list:
    """The ``k`` longest idle gaps, each named by the innermost benchmark
    span that covers most of it (``bench/window`` where no other does)."""
    out = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:k]:
        best, cover = WINDOW_SPAN, 0.0
        for n, a, b in spans:
            if n == WINDOW_SPAN:
                continue
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = n, c
        out.append([best, (e - s) / 1e9])
    return out


def reduce(pd, *, plane_prefix: str = "/device:TPU",
           op_line: str | None = "XLA Ops", op_line_prefix: str | None = None,
           module_line: str | None = "XLA Modules") -> dict:
    """The traced window's numbers, averaged over the device planes:
    ``window_s``, ``busy_s``, the operation and module intervals of the
    first plane, and the breakdown (top operations, labelled idle gaps)."""
    spans = host_spans(pd)
    lo, hi = window_bounds(spans)
    ops = line_events(pd, plane_prefix, op_line, op_line_prefix)
    ops = {p: v for p, v in ops.items() if v}
    if not ops:
        raise ValueError(f"no device operations on planes {plane_prefix!r}")
    mods = (line_events(pd, plane_prefix, module_line)
            if module_line else {})
    first = sorted(ops)[0]
    busy = [busy_ns(v, lo, hi) for v in ops.values()]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "lo": lo, "hi": hi,
        "ops": ops[first],
        "modules": mods.get(first, []),
        "breakdown": {
            "device_ops": top_ops(ops[first], lo, hi),
            "idle_gaps": label_gaps(gaps(ops[first], lo, hi), spans),
        },
    }


def describe(pd) -> list[str]:
    """One line per plane and line with its event count (for reading a
    trace by hand)."""
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            n = sum(1 for _ in line.events)
            if n:
                out.append(f"{plane.name} | {line.name} | {n}")
    return out
