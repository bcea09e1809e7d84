"""The program's own spans (``repro/...``, opened by
``repro.obs.trace.annotate``) in a JAX profiler trace, and what they say
about the traced window: a parent span's time less its children's, and
the device's idle time split by what the host was doing meanwhile.

A span is ``(name, start_ns, end_ns, thread)``, its name cut at any
``#`` metadata suffix; an idle gap is ``(start_ns, end_ns)`` as
:func:`bench.tracing.gaps` gives it.  Everything below works on such
lists, so it can be checked on handmade ones as well as on a recorded
trace.

The CNN path opens these spans.  On the caller's thread:
``repro/cnn/forward`` around the call, and inside it ``repro/cnn/im2col``,
``repro/cnn/pool``, ``repro/runtime/submit`` (split and seed one GEMM)
and ``repro/runtime/wait`` (the caller blocked on that GEMM's panels).
On each runtime worker: ``repro/panel/<engine>`` around one executed
panel, and inside it ``repro/device_wait`` (blocked on the panel's
result) and, on the worker that finishes a GEMM, ``repro/runtime/merge``.
"""

from __future__ import annotations

import bisect
import statistics
from collections import defaultdict

PREFIX = "repro/"
FORWARD = "repro/cnn/forward"
WAIT = "repro/runtime/wait"
PANEL = "repro/panel/"
DEVICE_WAIT = "repro/device_wait"
MERGE = "repro/runtime/merge"

#: labels of the idle partition other than the caller's own spans (which
#: are named by their span, less the ``repro/`` prefix: ``cnn/im2col``,
#: ``cnn/pool``, ``runtime/submit``, ``cnn/forward`` for its self time)
OUTSIDE = "outside_program"
PANEL_HOST = "panel_host"
MERGING = "merge"
WAITING = "device_wait"
NO_PANEL = "no_panel_open"


def program_spans(pd, prefix: str = PREFIX) -> list:
    """The spans whose name starts with ``prefix`` on every host line of
    the trace ``pd`` (a ``jax.profiler.ProfileData``).  The thread is
    named by plane and line index, since the profiler may give every
    Python thread's line the same name."""
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}/{i}"
            for e in line.events:
                if e.name.startswith(prefix):
                    spans.append((e.name.split("#", 1)[0], e.start_ns,
                                  e.start_ns + e.duration_ns, thread))
    return spans


def nested(spans: list, parent: str, child: str, lo: float = -1e30,
           hi: float = 1e30) -> list:
    """For each span whose name starts with ``parent`` and that lies in
    [lo, hi]: ``(duration, [durations of the spans whose name starts
    with child, on the same thread, inside it])``."""
    out = []
    by_thread = defaultdict(lambda: ([], []))
    for n, s, e, t in spans:
        if n.startswith(parent) and s >= lo and e <= hi:
            by_thread[t][0].append((s, e))
        elif n.startswith(child):
            by_thread[t][1].append((s, e))
    for parents, children in by_thread.values():
        parents.sort()
        starts = [s for s, _ in parents]
        kids = [[] for _ in parents]
        for s, e in children:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and e <= parents[i][1]:
                kids[i].append(e - s)
        out.extend((e - s, k) for (s, e), k in zip(parents, kids))
    return out


def self_ns(spans: list, parent: str, child: str, lo: float = -1e30,
            hi: float = 1e30) -> list:
    """Each ``parent`` span's duration less its ``child`` spans'."""
    return [d - sum(k) for d, k in nested(spans, parent, child, lo, hi)]


def idle_by_span(idle: list, spans: list) -> dict:
    """Every ns of the device-idle gaps ``idle``, labelled by what the
    host was doing:

    - outside any ``repro/cnn/forward`` of the caller: ``outside_program``;
    - the caller in ``repro/runtime/wait``: by the workers, in this order,
      ``panel_host`` if any worker is inside a panel but in neither
      ``repro/device_wait`` nor ``repro/runtime/merge``, else ``merge`` if
      any worker merges, else ``device_wait`` if any worker waits, else
      ``no_panel_open``;
    - otherwise the caller's innermost span, less ``repro/``.

    The caller is the thread (or threads) that opens
    ``repro/cnn/forward``; every other thread is a worker.  The labels'
    ns sum to the idle time exactly."""
    callers = {t for n, _, _, t in spans if n == FORWARD}
    events = []
    for k, (_, s, e, _) in enumerate(spans):
        events.append((s, 1, k))
        events.append((e, 0, k))
    for s, e in idle:
        events.append((s, 1, -1))
        events.append((e, 0, -1))
    events.sort(key=lambda x: (x[0], x[1]))      # ends first at a tie

    open_caller: set = set()
    worker = defaultdict(lambda: [0, 0, 0])      # panel, wait, merge open
    busy = {PANEL_HOST: 0, MERGING: 0, WAITING: 0}
    in_idle = 0
    out: dict = defaultdict(float)

    def state(c):
        if c[2]:
            return MERGING
        if c[1]:
            return WAITING
        return PANEL_HOST if c[0] else None

    def label():
        if not open_caller:
            return OUTSIDE
        # innermost: the latest start, then the earliest end, then the
        # later event of the line (a handmade tie)
        n = spans[max(open_caller,
                      key=lambda k: (spans[k][1], -spans[k][2], k))][0]
        if n != WAIT:
            return n[len(PREFIX):]
        for lab in (PANEL_HOST, MERGING, WAITING):
            if busy[lab]:
                return lab
        return NO_PANEL

    t_prev = None
    for t, start, k in events:
        if in_idle and t > t_prev:
            out[label()] += t - t_prev
        t_prev = t
        step = 1 if start else -1
        if k < 0:
            in_idle += step
            continue
        n, _, _, thread = spans[k]
        if thread in callers:
            (open_caller.add if start else open_caller.discard)(k)
            continue
        slot = (0 if n.startswith(PANEL) else 1 if n == DEVICE_WAIT
                else 2 if n == MERGE else None)
        if slot is None:
            continue
        c = worker[thread]
        before = state(c)
        c[slot] += step
        after = state(c)
        if before != after:
            if before:
                busy[before] -= 1
            if after:
                busy[after] += 1
    return dict(out)


# ------------------------------------------- readings of the CNN cells
def panel_host_ms(spans: list, lo: float, hi: float):
    """Median over the window's panels of the panel's time less its
    ``repro/device_wait``: host dispatch of one panel, in ms."""
    xs = self_ns(spans, PANEL, DEVICE_WAIT, lo, hi)
    return statistics.median(xs) / 1e6 if xs else None


def panel_device_wait_ms(spans: list, lo: float, hi: float):
    """Median ``repro/device_wait`` in the window, in ms."""
    xs = [e - s for n, s, e, _ in spans
          if n == DEVICE_WAIT and s >= lo and e <= hi]
    return statistics.median(xs) / 1e6 if xs else None


def queue_wait_ms(wait_s, panels):
    """Mean time a panel waited in a queue, in ms, from the window's
    deltas of the runtime's ``total_queue_wait_s`` and ``total_panels``
    (None where the program keeps no such counters)."""
    if wait_s is None or not panels:
        return None
    return 1e3 * wait_s / panels


def caller_host_share(spans: list, lo: float, hi: float):
    """The caller's ``repro/cnn/forward`` time outside
    ``repro/runtime/wait``, in % of the window [lo, hi]."""
    xs = self_ns(spans, FORWARD, WAIT, lo, hi)
    return 100.0 * sum(xs) / (hi - lo) if xs and hi > lo else None


def idle_in_panel_host_share(partition: dict):
    """The ``panel_host`` share of the idle partition, in % of all the
    window's device-idle time (None where no program span was seen)."""
    total = sum(partition.values())
    if total <= 0 or set(partition) == {OUTSIDE}:
        return None
    return 100.0 * partition.get(PANEL_HOST, 0.0) / total
