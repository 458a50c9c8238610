"""The benchmark's trace reduction, kernel byte counts and metric readers
on synthetic inputs: no chip, no trace file."""
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, kernel_bytes, trace_reduce as tr  # noqa: E402
from bench.metrics import (common, device_idle_share,  # noqa: E402
                           frozen_lane_share, host_ms_per_call,
                           pallas_ms_per_batch_tick, scatter_gather_share)

SCATTER = 'fusion.7 = u32[8,126]{1,0} fusion(...), kind=kCustom'
KERNEL = ('vmap_jit_nack_mark__.20 = u32[8,126,128] custom-call(...), '
          'custom_call_target="tpu_custom_call"')


def test_union_merges_overlapping_and_touching_spans():
    spans = [(10, 20), (15, 30), (30, 35), (50, 60), (0, 5)]
    assert tr.union(spans) == [(0, 5), (10, 35), (50, 60)]
    assert tr.union_ns(spans) == 5 + 25 + 10


def _plane(events_by_line, name="/device:TPU:0"):
    lines = [SimpleNamespace(name=ln, events=[
        SimpleNamespace(name=n, start_ns=s, duration_ns=d)
        for n, s, d in evs]) for ln, evs in events_by_line.items()]
    return SimpleNamespace(name=name, lines=lines)


def test_device_plane_leaves_containers_out_and_classes_ops():
    plane = _plane({
        "XLA Modules": [("jit_run", 0, 100)],
        "XLA Ops": [("%while.3 = (...) while(...)", 0, 100),
                    ("%cond.1 = (...) conditional(...)", 5, 90),
                    ("%" + SCATTER, 10, 30),
                    ("%" + KERNEL, 40, 10),
                    ("%dynamic-update-slice.4 = s32[] dynamic-update-slice()",
                     60, 5),
                    ("%fusion.9 = f32[8] fusion(...), kind=kLoop", 70, 5),
                    ("%copy.2 = s32[8] copy(...)", 80, 5)],
    })
    d = tr.device_plane(plane)
    assert d["modules"] == [(0, 100)]
    c = tr.by_class(d["ops"])
    assert "while" not in " ".join(c) and "cond" not in " ".join(c)
    assert c["custom fusion (scatter/gather)"] == 30
    assert c["pallas nack_mark"] == 10
    assert c["dynamic-update-slice"] == 5
    assert c["loop/other fusion"] == 5
    assert c["copy"] == 5
    assert tr.kernel_of("pallas nack_mark") == "nack_mark"
    assert tr.kernel_of("custom fusion (scatter/gather)") is None


@pytest.mark.parametrize("kernel,flows,lanes,want", [
    # a 64-rank tree all-reduce: 126 flows, 16 ring words,
    # 320 + 2*126 NACK lanes, B=8
    ("sack_fused", 126, 8, 8 * (5 * 4 * 126 * 16 + 3 * 4 * 126)),
    ("sack_advance", 126, 8, 8 * (2 * 4 * 126 * 16 + 3 * 4 * 126)),
    ("nack_mark", 126, 8, 8 * (2 * 4 * 126 * 16 + 9 * (320 + 2 * 126))),
    # the 16-rank ring all-reduce: 480 flows, 320 + 2*480 NACK lanes, B=8
    ("sack_fused", 480, 8, 8 * (5 * 4 * 480 * 16 + 3 * 4 * 480)),
    ("nack_mark", 480, 8, 8 * (2 * 4 * 480 * 16 + 9 * (320 + 2 * 480))),
    # the 32-rank all-to-all: 992 flows, 320 + 2*992 NACK lanes, B=4
    ("sack_fused", 992, 4, 4 * (5 * 4 * 992 * 16 + 3 * 4 * 992)),
    ("nack_mark", 992, 4, 4 * (2 * 4 * 992 * 16 + 9 * (320 + 2 * 992))),
])
def test_kernel_bytes_at_the_configurations_shapes(kernel, flows, lanes,
                                                   want):
    got = kernel_bytes.bytes_per_call(kernel, lanes, flows, 16,
                                      320 + 2 * flows)
    assert got == want


def test_roofline_share_and_its_ceiling():
    one = kernel_bytes.bytes_per_call("sack_fused", 8, 126, 16, 572)
    # exactly one call's bytes at the peak rate: 100%
    assert kernel_bytes.roofline("sack_fused", 8, 126, 16, 572, 1,
                                 one / 819e9, 819e9) == pytest.approx(100)
    assert kernel_bytes.roofline("sack_fused", 8, 126, 16, 572, 10,
                                 100 * one / 819e9, 819e9) == \
        pytest.approx(10)
    with pytest.raises(ValueError, match="over 105%"):
        kernel_bytes.roofline("sack_fused", 8, 126, 16, 572, 2,
                              one / 819e9, 819e9)


def _ctx(horizons, devices=1, trace=None, batch=None):
    calls = [SimpleNamespace(horizons=np.asarray(h)) for h in horizons]
    cell = SimpleNamespace(traffic={"batch": batch or len(horizons[0])})
    return {"cell": cell, "calls": calls, "trace": trace,
            "devices": devices, "peaks": None}


def test_frozen_lane_share_pools_calls():
    ctx = _ctx([[100, 200, 400, 400], [300, 300, 300, 300]])
    useful, paid = 1100 + 1200, 4 * 400 + 4 * 300
    assert frozen_lane_share.read(ctx) == pytest.approx(1 - useful / paid)


def test_block_ticks_split_the_batch_by_device():
    ctx = _ctx([[1, 2, 5, 3, 7, 7, 2, 9], [4, 4, 4, 4, 8, 1, 1, 1]],
               devices=4)
    assert list(common.block_ticks(ctx)) == [2 + 4, 5 + 4, 7 + 8, 9 + 1]
    assert list(common.block_ticks(_ctx([[1, 2]]))) == [2]


def test_trace_readers_on_a_synthetic_trace():
    """One traced call of 1000 ns on the host clock; the device ran one
    800 ns module holding three ops with a 100 ns gap between them."""
    trace = {
        "spans": [("bench.call.0", 5000, 6000)],
        "devices": [{"name": "/device:TPU:0",
                     "modules": [(100, 900)],
                     "ops": [("custom fusion (scatter/gather)", 100, 400),
                             ("loop/other fusion", 300, 500),
                             ("pallas nack_mark", 600, 900)]}],
    }
    ctx = _ctx([[5, 5]], trace=trace)
    assert common.window_ns(trace) == 1000
    assert host_ms_per_call.read(ctx) == pytest.approx(200 / 1e6)
    assert scatter_gather_share.read(ctx) == pytest.approx(300 / 800)
    # busy: union [100, 500) + [600, 900) = 700 of 1000
    assert device_idle_share.read(ctx) == pytest.approx(0.3)
    assert pallas_ms_per_batch_tick.read(ctx) == pytest.approx(300 / 5 / 1e6)
    b = harness.breakdown(trace)
    assert b["device_ops"][0] == ["custom fusion (scatter/gather)", 3e-7]
    assert b["idle_gaps"] == [
        ["simulate_batch: host outside the device ops", 2e-7],
        ["simulate_batch: device idle between ops", 1e-7]]
