"""The CI bench-regression gates (``benchmarks/check_regression.py``) stay
wired to what exists: each gate's two rows are in its committed baseline,
and the suite that regenerates its snapshot is a ``benchmarks.run`` suite.
No gate divides a device wall-clock row: device speed is measured on the
chip by ``bench/``, so every gated row is counted in deterministic units,
except the resource allocator's wall time (host code, timed on the host
that runs it)."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import check_regression as cr  # noqa: E402
from benchmarks.run import SNAPSHOTS, SUITES  # noqa: E402

BASELINES = ROOT / "benchmarks" / "baselines"
# the only wall-clock rows a gate may read: the allocator runs on the host
HOST_WALL_ROWS = ("resource/bcd_wall_",)


def _baseline_rows(fname: str) -> dict:
    data = json.loads((BASELINES / fname).read_text())
    return {r["name"]: r for r in data["rows"]}


@pytest.mark.parametrize("gate", cr.GATES, ids=[g[1] for g in cr.GATES])
def test_gate_rows_in_baseline_and_suite_exists(gate):
    fname, _, row, ref = gate
    rows = _baseline_rows(fname)
    assert row in rows and ref in rows
    assert rows[ref]["us_per_call"] > 0
    # benchmarks.run writes the snapshot, and a trip can re-run its suite
    assert fname in SNAPSHOTS
    assert all(name.startswith(SNAPSHOTS[fname]) for name in (row, ref))
    for suite in cr.SUITE_FOR_FILE[fname].split(","):
        assert suite in SUITES


def test_no_gate_reads_a_device_wall_clock_row():
    assert len(cr.GATES) == 9
    for fname, gate_id, row, ref in cr.GATES:
        rows = _baseline_rows(fname)
        for name in (row, ref):
            counted = rows[name]["derived"].startswith("unit=")
            assert counted or name.startswith(HOST_WALL_ROWS), (gate_id, name)
    # no snapshot is left without a gate to re-run it for
    assert set(cr.SUITE_FOR_FILE) == {g[0] for g in cr.GATES}
