"""From a profiler trace to the numbers the per-layer metrics read.

``Tracer`` records a window with ``jax.profiler`` and turns the
``.xplane.pb`` into plain event records (``events_from_xplane``); the rest
of this module works on those records only, so a small recorded trace in
``bench/testdata`` checks it without a chip.

An event record is ``[kind, name, start_ns, dur_ns, module]``: kind "op"
for an operation on the device, "module" for one execution of a compiled
program on the device, "host" for a span on the host's Python thread (the
benchmark's own ``TraceAnnotation`` spans and JAX's dispatch spans).  Only
device 0's events are kept: every cell drives its chips in lockstep.
"""
from __future__ import annotations

import glob
import os
import shutil
import time
from collections import defaultdict

WINDOW_SPAN = "bench.window"


class Tracer:
    def __init__(self, directory: str):
        self.dir = directory

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self._t0 = time.perf_counter()

    def stop(self) -> dict:
        import jax
        self._span.__exit__(None, None, None)
        host_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        events = events_from_xplane(path)
        shutil.rmtree(self.dir, ignore_errors=True)
        return {"events": events, "host_s": host_s}


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...), ...`` -> ``fusion.12``."""
    head = text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def events_from_xplane(path: str) -> list:
    """Event records of the first TPU and of the host's Python thread.  An
    operation is tagged with the program whose execution contains it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = {p.name: p for p in pd.planes}
    tpus = sorted((n for n in planes if n.startswith("/device:TPU:")),
                  key=lambda n: int(n.rsplit(":", 1)[1]))
    out, mods, ops = [], [], []
    if tpus:
        for line in planes[tpus[0]].lines:
            if line.name == "XLA Modules":
                mods = [(float(e.start_ns), float(e.duration_ns), e.name)
                        for e in line.events]
            elif line.name == "XLA Ops":
                ops = [(float(e.start_ns), float(e.duration_ns),
                        op_name(e.name)) for e in line.events]
    mods.sort()
    for s, d, n in mods:
        out.append(["module", n, s, d, n])
    j = 0
    for s, d, n in sorted(ops):
        while j + 1 < len(mods) and mods[j + 1][0] <= s:
            j += 1
        inside = mods and mods[j][0] <= s < mods[j][0] + mods[j][1]
        out.append(["op", n, s, d, mods[j][2] if inside else ""])
    host = planes.get("/host:CPU")
    for line in (host.lines if host else ()):
        if line.name.startswith("python"):
            out += [["host", e.name, float(e.start_ns), float(e.duration_ns),
                     ""] for e in line.events]
    return out


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def window_bounds(events) -> tuple:
    """The benchmark's window span on the trace clock, or the extent of
    the device events when the span is missing."""
    for k, n, s, d, _ in events:
        if k == "host" and n == WINDOW_SPAN:
            return s, s + d
    dev = [(s, s + d) for k, _, s, d, _ in events if k == "op"]
    return (min(a for a, _ in dev), max(b for _, b in dev)) if dev else (0, 0)


def busy(events, lo, hi) -> list:
    """Device-busy intervals (union of operations) clipped to [lo, hi)."""
    iv = [(max(s, lo), min(s + d, hi)) for k, _, s, d, _ in events
          if k == "op" and s + d > lo and s < hi]
    return _union([(a, b) for a, b in iv if b > a])


def module_runs(events, module: str) -> list:
    """[start, end) of each device execution of a compiled program whose
    name contains ``module`` (``jit__step_paged``, ``jit__chunk``, ...)."""
    return sorted((s, s + d) for k, n, s, d, _ in events
                  if k == "module" and module in n)


def ops_named(events, needle: str, module: str | None = None,
              exact: bool = False) -> list:
    """Device operations whose name contains ``needle`` (or, ``exact``,
    whose kind is ``needle``), optionally only inside programs whose name
    contains ``module``: [(start, dur)]."""
    hit = (lambda n: op_kind(n) == needle) if exact else (lambda n: needle in n)
    return [(s, d) for k, n, s, d, m in events
            if k == "op" and hit(n) and (module is None or module in m)]


def idle_gaps(events, lo, hi) -> list:
    """[start, end) of the device's idle stretches inside [lo, hi)."""
    b = busy(events, lo, hi)
    gaps, cur = [], lo
    for s, e in b:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def gaps_between(events, runs, lo, hi) -> list:
    """Device-idle nanoseconds between consecutive runs of one program."""
    b = busy(events, lo, hi)
    out = []
    for (s0, e0), (s1, _) in zip(runs, runs[1:]):
        if s1 <= e0:
            out.append(0.0)
            continue
        covered = sum(max(0.0, min(e, s1) - max(s, e0)) for s, e in b)
        out.append((s1 - e0) - covered)
    return out


def host_at(events, t) -> str:
    """The innermost host span covering trace time ``t``."""
    best = None
    for k, n, s, d, _ in events:
        if k == "host" and s <= t < s + d and n != WINDOW_SPAN:
            if best is None or d < best[1]:
                best = (n, d)
    return best[0] if best else "host idle"


def self_times(events, lo, hi) -> dict:
    """Device nanoseconds per operation kind (``fusion.12`` counts as
    ``fusion``) inside [lo, hi), each operation's own time only: a loop
    or call that contains other operations keeps what they do not cover."""
    ops = sorted(((s, d, n) for k, n, s, d, _ in events
                  if k == "op" and s + d > lo and s < hi),
                 key=lambda o: (o[0], -o[1]))
    own = defaultdict(float)
    stack = []                           # [end, kind]
    for s, d, n in ops:
        while stack and stack[-1][0] <= s:
            stack.pop()
        kind = op_kind(n)
        if stack and s + d <= stack[-1][0]:
            own[stack[-1][1]] -= d
        own[kind] += d
        stack.append([s + d, kind])
    return dict(own)


def op_kind(name: str) -> str:
    base, _, num = name.rpartition(".")
    return base if num.isdigit() and base else name


def summary(events, host_s: float) -> dict:
    """busy_s, window_s and the breakdown the result line carries."""
    lo, hi = window_bounds(events)
    b = busy(events, lo, hi)
    busy_ns = sum(e - s for s, e in b)
    top = sorted(self_times(events, lo, hi).items(),
                 key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle_gaps(events, lo, hi), key=lambda g: g[0] - g[1])[:10]
    return {
        "busy_s": busy_ns * 1e-9,
        "window_s": (hi - lo) * 1e-9 if hi > lo else host_s,
        "breakdown": {
            "device_ops": [[n, d * 1e-9] for n, d in top],
            "idle_gaps": [[host_at(events, (s + e) / 2), (e - s) * 1e-9]
                          for s, e in gaps]},
    }
