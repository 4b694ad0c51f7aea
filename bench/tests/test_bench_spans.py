"""The split of each gap between SFL rounds by the training loop's host
spans (``bench/spans.py`` and its three readers), on hand-made events and
on a trace recorded on a TPU v5e (bench/testdata/train_trace.json.gz:
three rounds of the GPT2-S cell's window)."""
import bench_tiny  # noqa: F401 — puts bench/ and src/ on the path
import gzip
import json

import pytest

import spans
import spec
import trace_reduce as tr
from common import BENCH

REC = json.load(gzip.open(BENCH / "testdata/train_trace.json.gz", "rt"))["events"]

READERS = {"train_gap_pull_ms": "pull", "train_gap_dispatch_ms": "dispatch",
           "train_gap_other_ms": "other"}
R = "jit__train_round_part(7)"


def _events(pull=((8, 6), (38, 12)), dispatch=((24, 5), (60, 9)),
            launch_lag=(0.0, 0.0, 0.0, 0.0)):
    """Three rounds at [0, 10), [30, 40) and [70, 80) on the device, a
    small program at [20, 22) in the first gap, the host's spans, and a
    launch of each program ``launch_lag`` after its start on the device."""
    ev = [["host", tr.WINDOW_SPAN, 0.0, 100.0, ""]]
    for s in (0.0, 30.0, 70.0):
        ev += [["module", R, s, 10.0, R], ["op", "fusion.1", s, 10.0, R]]
    ev += [["module", "jit_broadcast_in_dim(3)", 20.0, 2.0, ""],
           ["op", "broadcast.2", 20.0, 2.0, "jit_broadcast_in_dim(3)"]]
    ev += [["host", spans.LAUNCH + " linkage", s + lag, 0.5, ""]
           for s, lag in zip((0.0, 20.0, 30.0, 70.0), launch_lag)]
    ev += [["host", "train.round", s, 30.0, ""] for s in (5.0, 35.0, 65.0)]
    ev += [["host", "train.pull", s, d, ""] for s, d in pull]
    ev += [["host", "train.dispatch", s, d, ""] for s, d in dispatch]
    return ev


def _gaps(ev):
    lo, hi = tr.window_bounds(ev)
    return tr.gaps_between(ev, tr.module_runs(ev, spans.MODULE), lo, hi)


def test_split_of_each_gap():
    ev = _events()
    assert _gaps(ev) == [18.0, 30.0]
    # gap 1: 18 idle, of it pull [10, 14), dispatch [24, 29), the rest 9;
    # gap 2: 30 idle, pull [40, 50), dispatch [60, 69), the rest 11
    assert spans.gap_split(ev) == {"pull": 7.0, "dispatch": 7.0, "other": 10.0}


@pytest.mark.parametrize("pull, dispatch", [
    (((8, 6), (38, 12)), ((24, 5), (60, 9))),
    (((8, 12),), ((21, 9),)),             # the small program splits no span
    (((21, 30),), ((0, 20), (60, 40))),   # spans over busy time count not
    ((), ()),
])
def test_parts_add_up_to_the_gap(pull, dispatch):
    ev = _events(pull, dispatch)
    gaps = _gaps(ev)
    split = spans.gap_split(ev)
    assert all(v >= 0 for v in split.values())
    assert sum(split.values()) == pytest.approx(sum(gaps) / len(gaps))


def test_host_spans_move_by_the_lead_of_each_gap():
    # the small program starts on the device 3 before its launch, the
    # second round 2 and the third 1 before theirs: the host's spans move 3
    # earlier in gap 1 (its largest lead) and 1 earlier in gap 2
    ev = _events(launch_lag=(0.0, 3.0, 2.0, 1.0))
    # gap 1: pull [5, 11) gives [10, 11), dispatch [21, 26) gives [22, 26);
    # gap 2: pull [37, 49) gives [40, 49), dispatch [59, 68) all
    assert spans.gap_split(ev) == {"pull": 5.0, "dispatch": 6.5,
                                   "other": 12.5}


@pytest.mark.parametrize("drop", [0, 3])
def test_no_split_when_launches_do_not_pair(drop):
    ev = _events()
    launches = [e for e in ev if e[1].startswith(spans.LAUNCH)]
    ev.remove(launches[drop])
    assert spans.gap_split(ev) is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_readers(metric):
    reader = spec.load_reader(metric)
    assert reader.read({"events": _events()}) == pytest.approx(
        spans.gap_split(_events())[READERS[metric]] * 1e-6)
    # a program without spans reads nothing, not 0
    bare = [e for e in _events() if not e[1].startswith("train.")]
    assert reader.read({"events": bare}) is None
    # nor does a window with spans but a single round
    one = [e for e in _events() if not (e[0] == "module" and e[2] > 0)]
    assert reader.read({"events": one}) is None


def _named(ev, name):
    return sorted((s, s + d) for k, n, s, d, _ in ev
                  if k == "host" and n == name)


def test_recorded_trace_spans_and_rounds():
    runs = tr.module_runs(REC, spans.MODULE)
    assert len(runs) == 3
    for name in ("train.round", "train.dispatch", "sfl.put", "sfl.enqueue",
                 "train.pull", "train.callback"):
        assert len(_named(REC, name)) == 3
    # one before the first round, then a prefetch in every round
    assert len(_named(REC, "train.stage")) == 4


def test_recorded_trace_spans_on_the_device_clock():
    runs = tr.module_runs(REC, spans.MODULE)
    leads = spans.leads(REC)
    assert len(leads) == len(tr.module_runs(REC, ""))
    puts, enqueues = _named(REC, "sfl.put"), _named(REC, "sfl.enqueue")
    pulls = _named(REC, "train.pull")
    prev = float("-inf")
    for (s, e), put, enq, pull in zip(runs, puts, enqueues, pulls):
        # the launch paired with the round lies inside its enqueue
        (launch,) = [s + d for r, d in leads if r == s]
        assert enq[0] <= launch <= enq[1]
        # the host's clock moved onto the device's by the lead bound of the
        # programs that start between the previous round and this one
        shift = max(d for r, d in leads if prev < r <= s)
        # a round runs after its batches went up and its enqueue began,
        # and ends before the host has its losses back
        assert put[0] - shift < s and enq[0] - shift <= s
        assert e < pull[1] - shift
        prev = e
    # pull and dispatch, on one host thread, share no time
    assert spans._overlap(pulls, _named(REC, "train.dispatch")) == 0


def test_recorded_trace_split():
    lo, hi = tr.window_bounds(REC)
    gaps = tr.gaps_between(REC, tr.module_runs(REC, spans.MODULE), lo, hi)
    split = spans.gap_split(REC)
    assert len(gaps) == 2 and all(v > 0 for v in split.values())
    assert sum(split.values()) == pytest.approx(sum(gaps) / 2, rel=1e-9)
    ctx = {"events": REC}
    got = {m: spec.load_reader(m).read(ctx) for m in READERS}
    assert got == pytest.approx({"train_gap_pull_ms": 2.0702005,
                                 "train_gap_dispatch_ms": 3.239661,
                                 "train_gap_other_ms": 0.145965})
