"""The device's idle time between two executions of the compiled SFL round,
split by the training loop's host span that covers it.

``Trainer.fit`` marks each global round with profiler spans on the host's
Python thread: the round (``train.round``), its dispatch
(``train.dispatch``: batches to the device and the enqueue) and the pull
(``train.pull``: the host's waits on the round it dispatched).  Each gap
that ``trace_reduce.gaps_between`` measures is cut into the part inside a
pull span, the part inside a dispatch span, and the rest (bookkeeping,
the callback, no span), so the three parts add up to the gap.  A program
that records no ``train.round`` span reads nothing.

The trace stamps device and host events on clocks that differ by a lead
of a fraction of a millisecond to over one, which moves during a trace.
A program cannot start on the device before the host launches it
(``PJRT_LoadedExecutable_Execute``), so launch minus start bounds the
device's lead from below.  Each gap is read with the host spans moved by
the largest such bound among the programs that start in it."""
from __future__ import annotations

import trace_reduce as tr

MODULE = "_train_round_part"
ROUND, PULL, DISPATCH = "train.round", "train.pull", "train.dispatch"
LAUNCH = "PJRT_LoadedExecutable_Execute"


def _spans(events, name: str) -> list:
    """[start, end) of every host span called ``name``."""
    return [(s, s + d) for k, n, s, d, _ in events
            if k == "host" and n == name]


def _overlap(a, b) -> float:
    """Time shared by two lists of intervals, each without overlaps."""
    return sum(max(0.0, min(e, f) - max(s, t)) for s, e in a for t, f in b)


def leads(events) -> list:
    """(device start, host launch - device start) of each program run.
    The device runs programs in the order the host launches them, so the
    k-th launch is the k-th run; a trace whose counts differ gives none."""
    launches = sorted(s for k, n, s, _, _ in events
                      if k == "host" and n.startswith(LAUNCH))
    runs = sorted(s for k, _, s, _, _ in events if k == "module")
    if len(launches) != len(runs):
        return []
    return [(r, l - r) for l, r in zip(launches, runs)]


def gap_split(events) -> dict | None:
    """Device-idle nanoseconds per gap between rounds, as the mean over
    the window's gaps of the parts in ``pull``, in ``dispatch`` and
    ``other``; None without a ``train.round`` span, a gap, or launches
    that pair with the device's runs."""
    lo, hi = tr.window_bounds(events)
    if not any(s < hi and e > lo for s, e in _spans(events, ROUND)):
        return None
    runs = tr.module_runs(events, MODULE)
    gaps = tr.gaps_between(events, runs, lo, hi)
    lead = leads(events)
    if not gaps or not lead:
        return None
    idle = tr.idle_gaps(events, lo, hi)
    pull, dispatch = _spans(events, PULL), _spans(events, DISPATCH)
    parts = {"pull": 0.0, "dispatch": 0.0, "other": 0.0}
    for (_, e0), (s1, _), gap in zip(runs, runs[1:], gaps):
        if s1 <= e0:
            continue
        here = [(max(s, e0), min(e, s1)) for s, e in idle
                if s < s1 and e > e0]
        shift = max(d for r, d in lead if e0 < r <= s1)
        p = _overlap(here, [(s - shift, e - shift) for s, e in pull])
        d = _overlap(here, [(s - shift, e - shift) for s, e in dispatch])
        parts["pull"] += p
        parts["dispatch"] += d
        parts["other"] += gap - p - d
    return {k: v / len(gaps) for k, v in parts.items()}
