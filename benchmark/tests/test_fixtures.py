"""The trace reduction and the HLO readers on what the chip recorded.

``fixtures/lm-d768-1chip`` and ``fixtures/lm-d768-4chip`` are three steps of
the ``lm-d768`` step (one to a window) on one v5e chip and on four, taken in
PR 22 by ``--trace 1`` runs and cut down to the lines the reducer reads
(``fixtures/README.txt``). The numbers pinned here are the ones those runs
printed on the chip, so the reduction off the chip equals the one on it.
"""

import gzip
import os

import pytest
from jax.profiler import ProfileData

from benchmark.harness import hlo, xplane

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")


def read(name):
    with gzip.open(os.path.join(FIXTURES, name)) as f:
        return f.read()


@pytest.fixture(scope="module", params=["lm-d768-1chip", "lm-d768-4chip"])
def recorded(request):
    stem = request.param
    text = read(stem + ".hlo.txt.gz").decode()
    table = hlo.instruction_table(text)
    trace = xplane.parse(ProfileData.from_serialized_xspace(
        read(stem + ".xplane.pb.gz")))
    summary = xplane.reduce(trace, lambda name: table.get(name, {}).get(
        "category", "unattributed"))
    return stem, text, table, trace, summary


# what the recording runs printed on the chip (PR 22)
ON_CHIP = {
    "lm-d768-1chip": dict(chips=1, window_s=0.574568749,
                          busy_s=0.574514724, idle_share=9.402704e-05,
                          pallas=0.437013148, matmul=0.353500881,
                          exposed=0.0),
    # its eight all-reduces compiled to synchronous operations: nothing
    # runs beside them, so all their time is exposed
    "lm-d768-4chip": dict(chips=4, window_s=0.6313917805,
                          busy_s=0.63132677425, idle_share=1.0563165e-04,
                          pallas=0.401212156, matmul=0.315157088,
                          exposed=0.05427227662),
}


def test_every_device_second_is_named(recorded):
    stem, _, table, trace, summary = recorded
    assert summary["named_share"] == 1.0 and not summary["unattributed"]
    assert len(summary["chips"]) == ON_CHIP[stem]["chips"]
    for events in trace["chips"].values():
        assert all(e.name in table for e in events)


def test_reduction_equals_the_chip_runs(recorded):
    stem, _, _, _, summary = recorded
    want = ON_CHIP[stem]
    assert summary["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    assert summary["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert summary["idle_share"] == pytest.approx(want["idle_share"],
                                                  rel=1e-3)
    share = summary["category_share"]
    assert share["pallas_kernel"] == pytest.approx(want["pallas"], rel=1e-6)
    assert share["matmul_conv"] == pytest.approx(want["matmul"], rel=1e-6)
    assert summary["exposed_collective_share"] == pytest.approx(
        want["exposed"], rel=1e-6, abs=1e-12)


def test_busy_union_self_time_and_idle_are_consistent(recorded):
    _, _, _, trace, summary = recorded
    for chip, r in summary["chips"].items():
        # innermost-wins self time adds up to the busy union: the chip's
        # core runs one operation at a time
        assert sum(r["by_category"].values()) == pytest.approx(
            r["busy_s"], rel=1e-9)
        assert r["busy_s"] + r["idle_s"] == pytest.approx(r["window_s"])
        assert 0 <= r["collective_exposed_s"] <= r["collective_in_flight_s"]
        # three steps, 12 layers, a forward and two backward kernels each
        kernels = [e for e in trace["chips"][chip]
                   if e.name.startswith("attn.")]
        assert len(kernels) == 3 * 36


def test_host_spans_share_the_device_clock(recorded):
    _, _, _, trace, summary = recorded
    spans = [e.name for e in trace["host"]]
    # a window that fills the queue, one timed, one that drains it
    assert spans.count("dispatch") == 3 and spans.count("block") == 3
    first_op = min(e.start for ev in trace["chips"].values() for e in ev)
    last_op = max(e.end for ev in trace["chips"].values() for e in ev)
    assert trace["host"][0].start < first_op
    blocks = [e for e in trace["host"] if e.name == "block"]
    assert 0 < blocks[-1].end - last_op < 0.05


def test_collective_bytes_of_the_step(recorded):
    stem, text, table, _, _ = recorded
    if stem.endswith("1chip"):
        # nothing crosses a chip: the optimizer's all-reduces compiled away
        assert hlo.collective_axis_bytes(text, (1,), ("data",)) == {}
        assert "collective" not in {v["category"] for v in table.values()}
        return
    by_axis = hlo.collective_axis_bytes(text, (4,), ("data",))
    assert set(by_axis) == {"data"}
    # 162,220,800 float32 gradients and two scalars (loss, token count)
    assert by_axis["data"]["bytes"] == 162220800 * 4 + 8
    assert by_axis["data"]["calls"] == 8
    assert by_axis["data"]["ops"] == {"all-reduce": 648883208}
