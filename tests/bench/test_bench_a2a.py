"""DeepSeek-V3's EP32 MoE dispatch (``fig2_dsv3_prefill_ep32``, cell
``a2a_ep32``): the configuration at full size on the CPU, the program
against the plain reference at a small size with the configuration's
112-packet flows, and the cell's readers."""
import copy
import sys
from collections import Counter
from pathlib import Path
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import check, harness, sweep  # noqa: E402
from bench.metrics import (device_ms_per_batch_tick,  # noqa: E402
                           dispatch_ms_per_batch_tick, live_flow_share)
from bench.reference import FatTree, Model, run_reference  # noqa: E402

CONFIG = "fig2_dsv3_prefill_ep32"
PER_PEER = 112          # packets of 4 KiB each rank sends each peer


def test_configuration_builds_at_full_size():
    """32 ranks on hosts 0-31, four on each leaf of pods 0 and 1; 31
    chained rounds of 32 flows; every rank's host receives 31 peers'
    shares and no other host receives anything."""
    from repro.network import topology
    from repro.network.collectives import CollectiveSpec, build_workload

    cfg = sweep.load_json("configs", CONFIG)
    topo, coll = cfg["topology"], cfg["collective"]
    assert (coll["ranks"], coll["size_pkts"]) == (32, 32 * PER_PEER)
    g = getattr(topology, topo["family"])(topo["k"], topo["pods"])
    tree = FatTree(topo["k"], topo["pods"])
    assert g.num_queues == tree.num_queues == 320
    assert g.num_hosts == tree.hosts == 64
    wl = build_workload(CollectiveSpec(coll["kind"],
                                       tuple(sweep.rank_hosts(coll)),
                                       coll["size_pkts"]), coll["algo"])
    ft = sweep.flow_table(coll)
    assert len(ft["src"]) == 992 and (ft["size"] == PER_PEER).all()
    for k in ("src", "dst", "size", "dep"):
        assert np.array_equal(np.asarray(getattr(wl, k)), ft[k]), k
    rx = sweep.expected_host_rx(cfg)
    assert list(rx) == [31 * PER_PEER if h < 32 else 0 for h in range(64)]
    leaves = Counter(h // tree.half for h in sweep.rank_hosts(coll))
    assert leaves == {leaf: 4 for leaf in range(8)}


@pytest.fixture(scope="module")
def small():
    """k=4 fat tree (2 pods, 8 hosts), 8 ranks, 112 packets per peer, two
    healthy lanes with seeds of their own: program, reference, control."""
    cfg = copy.deepcopy(sweep.load_json("configs", CONFIG))
    cfg["topology"].update(k=4, pods=2)
    cfg["collective"].update(ranks=8, size_pkts=8 * PER_PEER)
    cfg["params"]["ticks"] = 8192
    traffic = dict(sweep.load_json("traffic", "healthy_b8"), batch=2,
                   checked_lanes=2)
    cell = harness.Cell("small", 1, cfg, traffic, [], [])
    lanes = sweep.call_lanes(cfg, traffic, 2 ** 31 + 15, 0)
    assert len({ln["seed"] for ln in lanes}) == 2
    results = harness.Program(cell).call(lanes, cell.budget)
    model = Model.from_config(cfg)
    ref = run_reference(model, lanes, cell.budget)
    ctl = run_reference(model, lanes, cell.budget, fdtype=jnp.bfloat16)
    return SimpleNamespace(cell=cell, lanes=lanes, results=results, ref=ref,
                           ctl=ctl)


def test_program_equals_the_reference(small):
    for r, want in zip(small.results, small.ref):
        assert check.differences(check.program_outcome(r), want) == []
        assert want["clash_ticks"] == 0
    counts = check.guarantee_counts(
        [small.results], 2, small.cell.budget,
        sweep.flow_table(small.cell.cfg["collective"])["dst"],
        sweep.expected_host_rx(small.cell.cfg))
    assert counts == {"missing_results": 0, "unfinished_lanes": 0,
                      "wrong_payload_lanes": 0}


def test_control_in_bfloat16_is_caught(small):
    differing = [check.differences(c, w) for c, w in zip(small.ctl,
                                                         small.ref)]
    assert all("cwnd" in d for d in differing)


def _ctx(small, results, trace=None):
    return {"cell": small.cell, "trace": trace, "devices": 1, "peaks": None,
            "calls": [harness.Call(0.0, 1.0, small.lanes, results)]}


def test_live_flow_share_is_completion_less_release(small):
    """Each flow is live from its ``dep``'s source completion (or tick 0)
    to its own: with 7 chained rounds about one flow in seven is."""
    dep = sweep.flow_table(small.cell.cfg["collective"])["dep"]
    live = paid = 0
    for r in small.results:
        done = r.source_completion_ticks()
        for f in range(len(done)):
            live += done[f] - (done[dep[f]] if dep[f] >= 0 else 0)
        paid += len(done) * r.horizon
    got = live_flow_share.read(_ctx(small, small.results))
    assert got == pytest.approx(live / paid)
    assert 0.05 < got < 0.2
    unfinished = [SimpleNamespace(
        horizon=r.horizon,
        source_completion_ticks=lambda: np.full(56, -1))
        for r in small.results]
    assert live_flow_share.read(_ctx(small, unfinished)) is None


def test_dispatch_reader_reads_module_time_per_batch_tick(small):
    assert dispatch_ms_per_batch_tick.read(_ctx(small, small.results)) \
        is None
    ticks = max(r.horizon for r in small.results)
    trace = {"devices": [{"name": "/device:TPU:0",
                          "modules": [(0, 3 * ticks * 10 ** 6)],
                          "ops": [(0, 0, 3 * ticks * 10 ** 6)]}],
             "spans": [("bench.call.0", 0, 4 * ticks * 10 ** 6)]}
    ctx = _ctx(small, small.results, trace)
    assert dispatch_ms_per_batch_tick.read(ctx) == pytest.approx(3.0)
    assert dispatch_ms_per_batch_tick.read(ctx) == \
        device_ms_per_batch_tick.read(ctx)
