"""The trace reduction, on a small trace recorded on a TPU v5e.

``data/trace`` was written by ``record_trace.py`` on the chip: three
"engine steps" of a jitted matmul and one paged decode kernel call
each, with a 5 ms sleep between them in a ``perfbench.idle`` span, all
inside ``perfbench.window``.
"""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import trace  # noqa: E402

DATA = BENCH / "tests" / "data" / "trace"


@pytest.fixture(scope="module")
def summary():
    return trace.summarize(trace.load(DATA))


def test_one_device_is_busy_for_part_of_the_window(summary):
    assert len(summary.busy_s) == 1
    busy = next(iter(summary.busy_s.values()))
    # three sleeps of 5 ms are idle, so at most ~half is busy
    assert 0 < busy < summary.window_s - 0.015
    assert summary.busy_mean_s() == busy


def test_kernel_time_is_found_by_its_stable_name(summary):
    assert summary.op_calls["paged_decode_attention"] == 3
    assert summary.op_s["paged_decode_attention"] > 0


def test_the_sleeps_are_the_longest_gaps_and_are_named(summary):
    names = [g[0] for g in summary.gaps[:3]]
    assert names == ["perfbench.idle"] * 3
    assert all(g[1] >= 0.005 for g in summary.gaps[:3])
    idle = summary.idle_by_span()
    assert idle["perfbench.idle"] >= 0.015
    # busy plus idle is the window
    total_idle = sum(g[1] for g in summary.gaps)
    busy = next(iter(summary.busy_s.values()))
    assert total_idle + busy == pytest.approx(summary.window_s, rel=1e-6)


def test_breakdown_keeps_ten_entries_at_most(summary):
    b = summary.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in b["device_ops"])


def test_union_merges_overlapping_ops():
    assert trace._union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
