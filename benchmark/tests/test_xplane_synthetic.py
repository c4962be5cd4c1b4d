"""The trace reduction on a hand-made trace with known answers."""

import pytest

from benchmark.harness import xplane
from benchmark.harness.xplane import Event

CATEGORY = {"fusion.1": "fusion", "fusion.2": "matmul_conv",
            "while.1": "control", "custom-call.1": "pallas_kernel",
            "all-reduce-start.1": "collective",
            "all-reduce-done.1": "collective", "all-reduce.2": "collective"}


def classify(name):
    return CATEGORY.get(name, "unattributed")


# one chip, a window of 10 s:
#   0-2   fusion.1
#   2-6   while.1, holding custom-call.1 3-5 (so 2 s of its own)
#   6-6.5 all-reduce-start.1
#   6.5-8 fusion.2 (hides the collective while it is in flight)
#   8-9   idle
#   9-10  all-reduce-done.1
# and on its in-flight line the collective from 6 to 10: 4 s, of which
# 1.5 s under fusion.2
CHIP0 = [Event("fusion.1", 0.0, 2.0), Event("while.1", 2.0, 6.0),
         Event("custom-call.1", 3.0, 5.0),
         Event("all-reduce-start.1", 6.0, 6.5), Event("fusion.2", 6.5, 8.0),
         Event("all-reduce-done.1", 9.0, 10.0)]
ASYNC0 = [Event("all-reduce-start.1", 6.0, 10.0),
          Event("copy-start.7", 0.0, 9.5)]  # a prefetch is no collective
HOST = [Event("dispatch", 0.0, 0.5), Event("block", 0.5, 8.4),
        Event("between_windows", 8.4, 9.1), Event("dispatch", 9.1, 9.2)]


def test_merge_and_covered():
    merged = xplane.merge([(3, 4), (0, 1), (0.5, 2), (4, 5)])
    assert merged == [(0, 2), (3, 5)]
    assert xplane.covered((1, 4), merged) == pytest.approx(2.0)


def test_self_time_is_innermost_wins():
    got = {e.name: s for e, s in xplane.self_times(CHIP0)}
    assert got["while.1"] == pytest.approx(2.0)
    assert got["custom-call.1"] == pytest.approx(2.0)
    assert sum(got.values()) == pytest.approx(9.0)  # the busy union


def test_one_chip_known_answers():
    r = xplane.reduce_chip(CHIP0, ASYNC0, HOST, classify)
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s"] == pytest.approx(9.0)
    assert r["idle_s"] == pytest.approx(1.0)
    assert r["by_category"]["pallas_kernel"] == pytest.approx(2.0)
    assert r["by_category"]["control"] == pytest.approx(2.0)
    assert r["by_category"]["collective"] == pytest.approx(1.5)
    assert r["collective_in_flight_s"] == pytest.approx(4.0)
    assert r["collective_exposed_s"] == pytest.approx(2.5)
    # the one gap, 8-9, lies mostly under between_windows (8.4-9.1)
    assert r["gaps"] == [("between_windows", pytest.approx(1.0))]


def test_synchronous_collective_is_its_own_window():
    events = [Event("fusion.1", 0.0, 1.0), Event("all-reduce.2", 1.0, 3.0)]
    r = xplane.reduce_chip(events, [], [], classify)
    assert r["collective_in_flight_s"] == pytest.approx(2.0)
    assert r["collective_exposed_s"] == pytest.approx(2.0)


def test_worst_chip_and_average():
    chip1 = [Event("fusion.1", 0.0, 5.0), Event("mystery", 9.0, 10.0)]
    s = xplane.reduce({"chips": {0: CHIP0, 1: chip1},
                       "async": {0: ASYNC0}, "host": HOST}, classify)
    assert s["busy_s"] == pytest.approx((9.0 + 6.0) / 2)
    assert s["window_s"] == pytest.approx(10.0)
    assert s["idle_share"] == pytest.approx(0.4)          # chip 1
    assert s["exposed_collective_share"] == pytest.approx(0.25)  # chip 0
    assert s["category_share"]["pallas_kernel"] == pytest.approx(0.2)
    assert s["unattributed"] == ["mystery"]
    assert s["named_share"] == pytest.approx(14.0 / 15.0)
    assert s["by_name"]["fusion.1"] == pytest.approx((2.0 + 5.0) / 2)


def test_empty_trace_reduces_to_nothing():
    assert xplane.reduce({"chips": {}, "host": []}, classify) == {
        "chips": {}}
