"""The trace reduction: busy union, idle share, collective-exposed time,
idle gaps named by host spans."""
from pathlib import Path

import pytest

from benchlib import tracefile
from benchlib.tracefile import Event, Trace

from conftest import DATA


def test_interval_algebra():
    assert tracefile.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]
    assert tracefile.length([(0, 2), (1, 3), (10, 11)]) == 4
    assert tracefile.clip([(0, 5), (6, 9)], 2, 7) == [(2, 5), (6, 7)]
    # [0,10) minus [2,3) and [5,12): 2 + 2
    assert tracefile.subtract([(0, 10)], [(2, 3), (5, 12)]) == 4
    assert tracefile.subtract([(0, 1), (4, 6)], []) == 3
    assert tracefile.gaps([(2, 3), (5, 6)], 0, 8) == [(0, 2), (3, 5), (6, 8)]


def _trace():
    # device 0: a loop (0-90) holds compute 0-40, a collective 30-50 (10
    # exposed) and compute 60-90; the loop itself is no compute
    ops = [Event("%while.3 = (f32[4], s32[]) while(..)", 0, 90),
           Event("%fusion.1 = f32[8]{0} fusion(..)", 0, 40),
           Event("%psum.2 = (f32[8], u32[]) all-reduce-start(..)", 30, 50),
           Event("%fusion.7 = f32[8]{0} fusion(%all-gather-done.2)", 60, 90)]
    tracefile.nest(ops)
    host = [Event("bench.window", 0, 100), Event("bench.run", 0, 55),
            Event("bench.wait", 55, 95), Event("bench.submit", 95, 100)]
    return Trace({0: ops, 1: [Event("fusion.1", 0, 100)]}, host)


def test_nesting_and_names():
    loop, f1, coll, f7 = _trace().devices[0]
    assert not loop.leaf and loop.inner == 40 + 20 + 30
    assert f1.leaf and coll.leaf and f7.leaf
    assert (f1.op, f1.label) == ("fusion.1", "fusion.1 f32[8]")
    assert (loop.opcode, coll.opcode) == ("while", "all-reduce-start")
    # a fusion that reads a collective's result is no collective
    assert f7.opcode == "fusion"


def test_device_summary_by_hand():
    s = tracefile.device_summary(_trace().devices[0], 0, 100)
    assert s["busy_s"] == pytest.approx(90e-9)
    assert s["collective_s"] == pytest.approx(20e-9)
    assert s["collective_exposed_s"] == pytest.approx(10e-9)
    assert s["by_op"]["fusion.1 f32[8]"] == pytest.approx(40e-9)
    assert s["by_op"]["psum.2 (f32[8],"] == pytest.approx(20e-9)
    # the loop's self time is what its body leaves uncovered: 90 - 90
    assert s["by_op"]["while.3 (f32[4],"] == pytest.approx(0.0)


def test_summarize_names_gaps_by_host_span():
    s = tracefile.summarize(_trace(), [0, 1])
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx((90e-9 + 100e-9) / 2)
    # the one gap, 90-100, is half wait and half submit: a tie goes to
    # the shorter (innermost) span
    assert s["breakdown"]["idle_gaps"] == [
        ["bench.submit", pytest.approx(10e-9)]]
    assert s["breakdown"]["device_ops"][0][0] == "fusion.1 f32[8]"


RECORDED = sorted(DATA.glob("*.xplane.pb"))


@pytest.mark.parametrize("path", RECORDED, ids=[p.name for p in RECORDED])
def test_recorded_chip_trace(path: Path):
    """Short traces recorded on v5e chips (a small scan with a halo-like
    ppermute, an all-gather and a psum on each of four chips; the stitch
    kernel alone on one): ops on TPU planes, the window span on the host,
    busy inside the window, collectives found by opcode."""
    t = tracefile.read(str(path))
    assert t.devices and all(t.devices.values())
    ids = sorted(t.devices)
    s = tracefile.summarize(t, ids)
    for d in s["devices"].values():
        assert 0 < d["busy_s"] <= d["window_s"]
        assert 0 <= d["collective_exposed_s"] <= d["collective_s"]
    ops = {e.opcode for e in t.devices[ids[0]]}
    assert "custom-call" in ops          # the latent_blend kernel
    if len(ids) == 4:
        assert {"all-gather", "all-reduce", "collective-permute-done"} <= ops
        # in this program the collectives run alone: all exposed
        d0 = s["devices"][ids[0]]
        assert d0["collective_s"] > 0
        assert d0["collective_exposed_s"] == pytest.approx(
            d0["collective_s"], rel=0.05)
    assert s["breakdown"]["device_ops"]
