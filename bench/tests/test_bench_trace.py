"""The trace reduction on a recorded fixture.

``fixtures/v5e_async_1x512.json.gz`` is cut from the plain form
(``trace.load``) of a real trace of ``stablelm-async-1x512`` on a TPU v5
lite, from when its configuration had a tied head: the first ticks of a
traced window, with their device ops (line "XLA Ops") and the harness's
host spans.  Its expected numbers are checked against a brute-force sweep
written here, and against the values the reduction gave when it was cut.
"""

from __future__ import annotations

import gzip
import json
from pathlib import Path

import pytest

FIXTURE = Path(__file__).parent / "fixtures" / "v5e_async_1x512.json.gz"
EXPECTED = FIXTURE.with_suffix("").with_suffix(".expected.json")


@pytest.fixture(scope="module")
def plain():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


def _sweep_busy_ns(events, lo, hi) -> float:
    """Busy time by a sweep over start/end points, clipped to the window."""
    points = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            points += [(a, 1), (b, -1)]
    busy, depth, last = 0.0, 0, None
    for t, step in sorted(points, key=lambda p: (p[0], -p[1])):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_busy_time_matches_a_sweep(plain):
    from bench import trace as tr

    red = tr.Reduced(plain)
    lo, hi = plain["window_ns"]
    assert red.busy_s("0") == pytest.approx(_sweep_busy_ns(plain["devices"]["0"], lo, hi) * 1e-9, rel=1e-12)
    ops = [e for e in plain["devices"]["0"] if tr.op_class(e[0]) == "update"]
    assert red.class_s("update") == pytest.approx(_sweep_busy_ns(ops, lo, hi) * 1e-9, rel=1e-12)


def test_reduction_gives_the_recorded_numbers(plain):
    from bench import trace as tr

    expected = json.loads(EXPECTED.read_text())
    red = tr.Reduced(plain)
    assert red.window_s == pytest.approx(expected["window_s"], rel=1e-12)
    assert red.mean_busy_s() == pytest.approx(expected["busy_s"], rel=1e-12)
    for cls in ("update", "collective", "other"):
        assert red.class_s(cls) == pytest.approx(expected["class_s"][cls], rel=1e-12, abs=1e-15)
        assert red.class_count(cls) == expected["class_count"][cls]
    assert [n for n, _ in red.top_ops(3)] == expected["top_ops"]
    assert [n for n, _ in red.idle_gaps(3)] == expected["idle_gap_spans"]


def test_update_launches_are_one_a_tick(plain):
    from bench import trace as tr

    red = tr.Reduced(plain)
    ticks = sum(1 for n, _, _ in plain["host"] if n == "wait")  # ticks seen finished
    assert red.class_count("update") == ticks
    assert tr.op_class("%fused_tick_call.1") == "update"
    assert tr.op_class("%fused_chain_call") == "update"
    assert tr.op_class("%all-reduce.7") == "collective"
    assert tr.op_class("%fusion.435") == "other"
    assert tr.short_name("%while.55 = (s32[]) while(%tuple.1), body=%b") == "%while.55"
