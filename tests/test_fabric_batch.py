"""Batched scenario engine + fused SACK kernel: equivalence and parity.

* the fused record/advance/shift kernel (interpret mode) and its jnp
  oracle agree with the pds reference on edge cases (empty ring, full
  ring, base wrap-around), and interpret mode agrees with the compiled
  kernel (compiled only on TPU);
* `simulate_batch` lanes are bitwise identical to serial `simulate`
  calls across mixed workloads, seeds, and failure masks;
* the enqueue write's dense TPU path gives the scatter's runs bit for
  bit.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pds
from repro.core.lb.schemes import LBScheme
from repro.core.link import LinkConfig
from repro.kernels import ops, ref
from repro.kernels.queue_write import queue_write
from repro.kernels.sack_fused import sack_fused
from repro.network import fabric
from repro.network.collectives import CollectiveSpec, build_workload
from repro.network.fabric import SimParams, Workload, simulate, simulate_batch
from repro.network.faults import FaultSchedule
from repro.network.profile import TransportProfile
from repro.network.topology import fat_tree3, leaf_spine

RNG = np.random.default_rng(11)


def _ref(ring, base, rtx, mask):
    """pds-composed reference: record (OR) -> advance -> shift both rings."""
    ring = ring | mask
    adv = pds.trailing_ones(ring)
    return (pds.shift_ring(ring, adv), base + adv.astype(jnp.uint32),
            pds.shift_ring(rtx, adv), adv)


def _fused(use_pallas):
    """The Pallas kernel (interpret mode) or its jnp oracle."""
    return (functools.partial(sack_fused, interpret=True) if use_pallas
            else ref.sack_fused_ref)


def _assert_fused_matches(ring, base, rtx, mask, use_pallas):
    got = _fused(use_pallas)(ring, base, rtx, mask)
    want = _ref(ring, base, rtx, mask)
    for g, w, name in zip(got, want, ("ring", "base", "rtx", "adv")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_sack_fused_empty_ring(use_pallas):
    n, w = 9, 8
    ring = jnp.zeros((n, w), jnp.uint32)
    rtx = jnp.asarray(RNG.integers(0, 2 ** 32, (n, w), dtype=np.uint32))
    base = jnp.asarray(RNG.integers(0, 10000, n, dtype=np.uint32))
    mask = jnp.zeros((n, w), jnp.uint32)
    _assert_fused_matches(ring, base, rtx, mask, use_pallas)
    # empty ring + empty mask: nothing advances, nothing shifts
    r, b, x, a = _fused(use_pallas)(ring, base, rtx, mask)
    assert int(np.asarray(a).sum()) == 0
    np.testing.assert_array_equal(np.asarray(x), np.asarray(rtx))
    np.testing.assert_array_equal(np.asarray(b), np.asarray(base))


@pytest.mark.parametrize("use_pallas", [False, True])
def test_sack_fused_full_ring(use_pallas):
    n, w = 5, 16
    ring = jnp.full((n, w), 0xFFFFFFFF, jnp.uint32)
    rtx = jnp.asarray(RNG.integers(0, 2 ** 32, (n, w), dtype=np.uint32))
    base = jnp.asarray(RNG.integers(0, 10000, n, dtype=np.uint32))
    mask = jnp.zeros((n, w), jnp.uint32)
    _assert_fused_matches(ring, base, rtx, mask, use_pallas)
    r, b, x, a = _fused(use_pallas)(ring, base, rtx, mask)
    np.testing.assert_array_equal(np.asarray(a), w * 32)  # full window
    assert int(np.asarray(r).sum()) == 0                  # fully drained
    assert int(np.asarray(x).sum()) == 0                  # rtx shifted out


@pytest.mark.parametrize("use_pallas", [False, True])
def test_sack_fused_base_wraparound(use_pallas):
    """base sits just below 2^32: the CACK advance must wrap modularly."""
    n, w = 4, 4
    ring = jnp.asarray([[0xFFFFFFFF, 0x1, 0, 0],
                        [0x7, 0, 0, 0],
                        [0, 0, 0, 0],
                        [0xFFFFFFFF] * 4], jnp.uint32)
    base = jnp.full((n,), 0xFFFFFFF0, jnp.uint32)
    rtx = jnp.asarray(RNG.integers(0, 2 ** 32, (n, w), dtype=np.uint32))
    mask = jnp.zeros((n, w), jnp.uint32)
    _assert_fused_matches(ring, base, rtx, mask, use_pallas)
    _, b, _, a = _fused(use_pallas)(ring, base, rtx, mask)
    adv = np.asarray(a).astype(np.uint32)
    np.testing.assert_array_equal(
        np.asarray(b), (np.asarray(base) + adv).astype(np.uint32))
    assert int(adv[3]) == w * 32 and int(np.asarray(b)[3]) < 0xFFFFFFF0


@pytest.mark.parametrize("n,w", [(1, 2), (64, 16), (130, 8)])
def test_sack_fused_random_parity(n, w):
    ring = jnp.asarray(RNG.integers(0, 2 ** 32, (n, w), dtype=np.uint32))
    rtx = jnp.asarray(RNG.integers(0, 2 ** 32, (n, w), dtype=np.uint32))
    mask = jnp.asarray(RNG.integers(0, 2 ** 32, (n, w), dtype=np.uint32))
    base = jnp.asarray(RNG.integers(0, 2 ** 32, n, dtype=np.uint32))
    _assert_fused_matches(ring, base, rtx, mask, use_pallas=True)
    _assert_fused_matches(ring, base, rtx, mask, use_pallas=False)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="compiled Pallas path needs a TPU; interpret "
                           "mode is exercised everywhere else")
def test_sack_fused_interpret_vs_compiled():
    n, w = 96, 16
    ring = jnp.asarray(RNG.integers(0, 2 ** 32, (n, w), dtype=np.uint32))
    rtx = jnp.asarray(RNG.integers(0, 2 ** 32, (n, w), dtype=np.uint32))
    mask = jnp.asarray(RNG.integers(0, 2 ** 32, (n, w), dtype=np.uint32))
    base = jnp.asarray(RNG.integers(0, 2 ** 32, n, dtype=np.uint32))
    a = sack_fused(ring, base, rtx, mask, interpret=True)
    b = sack_fused(ring, base, rtx, mask, interpret=False)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------------------------
# batched scenario engine
# ------------------------------------------------------------------------

def _state_equal(a, b) -> bool:
    return all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda x, y: bool(np.array_equal(np.asarray(x), np.asarray(y))),
        a, b)))


def test_simulate_batch1_equals_simulate():
    g = leaf_spine(leaves=2, spines=4, hosts_per_leaf=4)
    wl = Workload.of([0, 1, 2], [4, 5, 6], 200)
    prof = TransportProfile.ai_full()
    p = SimParams(ticks=300)
    r = simulate(g, wl, prof, p, trace="full")
    rb = simulate_batch(g, Workload.stack([wl]), prof, p, trace="full")[0]
    assert r.horizon == rb.horizon and r.max_ticks == 300
    np.testing.assert_array_equal(r.delivered_per_tick, rb.delivered_per_tick)
    np.testing.assert_array_equal(r.cwnd_per_tick, rb.cwnd_per_tick)
    np.testing.assert_array_equal(r.qlen_max, rb.qlen_max)
    assert _state_equal(r.state, rb.state)


@pytest.mark.slow
def test_simulate_batch8_bitwise_identical_to_serial():
    """Acceptance: 8 mixed scenarios (sizes x seeds x failure masks) in
    one vmapped scan == 8 serial runs, bitwise."""
    g = leaf_spine(leaves=2, spines=4, hosts_per_leaf=8)
    prof = TransportProfile.ai_full(lb=LBScheme.REPS)
    p = SimParams(ticks=400, timeout_ticks=64, ooo_threshold=24)
    wls, masks, seeds, fqs = [], [], [], []
    for i in range(8):
        wls.append(Workload.of(list(range(8)), [8 + j for j in range(8)],
                               600 + 100 * i))
        m = np.zeros((g.num_queues,), bool)
        fq = ()
        if i % 2 == 1:
            q = int(g.up1_table[0, i % 4])
            m[q] = True
            fq = (q,)
        masks.append(m)
        fqs.append(fq)
        seeds.append(0x5EED + i)
    serial = [simulate(g, wls[i], prof, p, failed=fqs[i],
                       seed=seeds[i], trace="full") for i in range(8)]
    batch = simulate_batch(g, Workload.stack(wls), prof, p,
                           failed=np.stack(masks),
                           seeds=np.asarray(seeds, np.uint32),
                           trace="full")
    for i, (a, b) in enumerate(zip(serial, batch)):
        np.testing.assert_array_equal(
            a.delivered_per_tick, b.delivered_per_tick,
            err_msg=f"scenario {i}")
        np.testing.assert_array_equal(a.cwnd_per_tick, b.cwnd_per_tick,
                                      err_msg=f"scenario {i}")
        np.testing.assert_array_equal(a.qlen_max, b.qlen_max,
                                      err_msg=f"scenario {i}")
        assert _state_equal(a.state, b.state), f"scenario {i} state diverged"


def test_simulate_batch_failed_queue_masks_change_outcomes():
    """Failure masks are per-scenario: a dead uplink must show up as
    silent drops in that lane only."""
    g = leaf_spine(leaves=2, spines=2, hosts_per_leaf=2)
    wl = Workload.of([0, 1], [2, 3], 300)
    p = SimParams(ticks=250, timeout_ticks=64)
    masks = np.zeros((2, g.num_queues), bool)
    masks[1, int(g.up1_table[0, 0])] = True
    healthy, degraded = simulate_batch(g, Workload.stack([wl, wl]),
                                       TransportProfile.ai_full(), p,
                                       failed=masks)
    assert int(healthy.state.drops) == 0
    assert int(degraded.state.drops) > 0


def test_record_rx_duplicate_lanes_or_semantics():
    """pds.or_mask's general path: duplicate (pdc, psn) lanes in one
    batch must set the bit once and report both lanes accepted."""
    t = pds.PSNTracker.create(2, 64)
    pdc = jnp.asarray([0, 0, 0, 1], jnp.int32)
    psn = jnp.asarray([3, 3, 4, 3], jnp.uint32)
    valid = jnp.asarray([True, True, True, True])
    t2, fresh = pds.record_rx(t, pdc, psn, valid)
    assert np.asarray(fresh).tolist() == [True, True, True, True]
    assert int(np.asarray(t2.ring)[0, 0]) == (1 << 3) | (1 << 4)
    assert int(np.asarray(t2.ring)[1, 0]) == 1 << 3


def test_record_rx_unique_rows_fast_path_matches_general():
    """unique_rows=True (dedup skipped) must agree with the general path
    whenever the batch really is one-lane-per-PDC."""
    rng = np.random.default_rng(5)
    t = pds.PSNTracker.create(8, 128)
    pdc = jnp.asarray(rng.permutation(8)[:6], jnp.int32)
    psn = jnp.asarray(rng.integers(0, 200, 6), jnp.uint32)  # some OOR
    valid = jnp.asarray([True, True, False, True, True, True])
    a, fa = pds.record_rx(t, pdc, psn, valid, unique_rows=True)
    b, fb = pds.record_rx(t, pdc, psn, valid, unique_rows=False)
    np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ------------------------------------------------------------------------
# dependency lane (Workload.dep) + INC: parity and golden anchoring
# ------------------------------------------------------------------------

def test_dep_gated_batch_vs_serial_bitwise():
    """Dep-scheduled collectives through simulate_batch are bitwise
    identical to serial simulate calls (sizes x seeds vary)."""
    from repro.network import collectives as coll
    g = leaf_spine(leaves=2, spines=2, hosts_per_leaf=2)
    p = SimParams(ticks=350)
    prof = TransportProfile.ai_full()
    wls, seeds = [], []
    for i, s in enumerate((12, 16, 20)):
        spec = coll.CollectiveSpec("all_reduce", (0, 1, 2, 3), s)
        wls.append(coll.build_workload(spec, "ring"))
        seeds.append(0x5EED + i)
    serial = [simulate(g, wls[i], prof, p, seed=seeds[i], trace="full")
              for i in range(3)]
    batch = simulate_batch(g, Workload.stack(wls), prof, p,
                           seeds=np.asarray(seeds, np.uint32),
                           trace="full")
    # scenarios complete at different ticks: each batch lane must freeze
    # at ITS OWN chunk boundary, exactly like its serial run
    for a, b in zip(serial, batch):
        assert a.horizon == b.horizon
    for i, (a, b) in enumerate(zip(serial, batch)):
        np.testing.assert_array_equal(a.delivered_per_tick,
                                      b.delivered_per_tick,
                                      err_msg=f"scenario {i}")
        np.testing.assert_array_equal(a.src_base_per_tick,
                                      b.src_base_per_tick,
                                      err_msg=f"scenario {i}")
        assert _state_equal(a.state, b.state), f"scenario {i} diverged"


def test_inc_batch_vs_serial_bitwise():
    """The INC-enabled executable is batch/serial bitwise-stable too
    (accumulator slots ride the vmapped carry)."""
    from dataclasses import replace

    from repro.network import collectives as coll
    g = leaf_spine(leaves=2, spines=2, hosts_per_leaf=4)
    prof = replace(TransportProfile.ai_full(), inc=True, name="ai_full+inc")
    p = SimParams(ticks=600)
    spec = coll.CollectiveSpec("all_reduce", tuple(range(8)), 24)
    wl = coll.build_workload(spec, "tree")
    a = simulate(g, wl, prof, p, trace="full")
    b = simulate_batch(g, Workload.stack([wl, wl]), prof, p,
                       trace="full")[1]
    assert int(a.state.inc_reduced) > 0
    np.testing.assert_array_equal(a.delivered_per_tick, b.delivered_per_tick)
    np.testing.assert_array_equal(a.src_base_per_tick, b.src_base_per_tick)
    assert _state_equal(a.state, b.state)


def test_explicit_dep_minus_one_matches_golden():
    """Golden anchor: a workload with dep/red lanes explicitly present
    (all -1) reproduces the pre-dep-lane engine bitwise (the golden
    lanes were captured before this PR)."""
    import os
    gold = np.load(os.path.join(os.path.dirname(__file__), "golden",
                                "fabric_golden.npz"))
    g = leaf_spine(leaves=2, spines=4, hosts_per_leaf=4)
    wl = Workload.of([0, 1, 2], [4, 5, 6], 200,
                     dep=np.full(3, -1, np.int32),
                     red=np.full(3, -1, np.int32))
    r = simulate(g, wl, TransportProfile.ai_full(), SimParams(ticks=300),
                 trace="full")
    h = r.horizon
    np.testing.assert_array_equal(r.delivered_per_tick,
                                  gold["a_delivered"][:h])
    assert (gold["a_delivered"][h:] == 0).all()
    np.testing.assert_array_equal(r.cwnd_per_tick, gold["a_cwnd"][:h])
    np.testing.assert_array_equal(r.qlen_max, gold["a_qlen"][:h])
    np.testing.assert_array_equal(np.asarray(r.state.src_track.base),
                                  gold["a_state_src_base"])


def test_run_cache_distinguishes_same_named_graphs():
    """Two topologies with identical name/counts but different wiring
    must not share a compiled executable (routing is baked in)."""
    g1 = leaf_spine(leaves=2, spines=2, hosts_per_leaf=2)
    g2 = leaf_spine(leaves=2, spines=2, hosts_per_leaf=2)
    import dataclasses
    # rewire g2: swap the two uplinks of leaf 0
    up = g2.up1_table.copy()
    up[0] = up[0][::-1]
    g2 = dataclasses.replace(g2, up1_table=up)
    assert g1.name == g2.name
    wl = Workload.of([0, 1], [2, 3], 60)
    prof = TransportProfile.ai_full()
    p = SimParams(ticks=80)
    r1 = simulate(g1, wl, prof, p)
    r2 = simulate(g2, wl, prof, p)
    # both must run on their own wiring (no crash / no silent reuse);
    # delivery totals agree because the rewiring is symmetric
    assert int(r1.state.delivered.sum()) == int(r2.state.delivered.sum())
    from repro.network.fabric import _cache_key
    assert _cache_key(g1, prof, p, 2, False) != _cache_key(g2, prof, p, 2, False)


def _incast(g):
    """Six senders on two leaves into one host: queues overflow."""
    return Workload.stack([Workload.of([0, 1, 2, 4, 5, 6], [3] * 6,
                                       60 + 20 * i) for i in range(2)])


def _enqueue_cases():
    """(graph, workloads, params, simulate_batch kwargs) of the three
    runs the dense queue write must reproduce."""
    p = SimParams(ticks=3000, queue_capacity=8, timeout_ticks=64)
    g = leaf_spine(leaves=2, spines=2, hosts_per_leaf=4)
    ups = [int(g.up1_table[1, i]) for i in range(2)]
    gray = FaultSchedule.healthy(g.num_queues).lossy(ups, 0.05)
    a2a = fat_tree3(k=4, pods=2)
    spec = CollectiveSpec("all_to_all", tuple(range(8)), 16)
    return {
        "trims_gray": (g, _incast(g), p, {"faults": gray}),
        "cbfc": (g, _incast(g), p,
                 {"link": LinkConfig.on(llr=False, cbfc=True)}),
        "a2a": (a2a, Workload.stack([build_workload(spec)] * 2),
                SimParams(ticks=3000, queue_capacity=16), {}),
    }


@pytest.mark.parametrize("case", ["trims_gray", "cbfc", "a2a"])
def test_dense_queue_write_matches_the_scatter(case, monkeypatch):
    """The enqueue write steered to its dense TPU path (one-hot
    contraction) gives the scatter's runs bit for bit: results, counters
    and the final state, q_pkt included. The cases overflow and trim
    under gray-link loss, back-pressure under CBFC, and put two ranks of
    an all-to-all on each leaf."""
    g, wls, p, kw = _enqueue_cases()[case]
    prof = TransportProfile.ai_full()
    want = simulate_batch(g, wls, prof, p, **kw)
    dense = []
    monkeypatch.setattr(fabric, "_RUN_CACHE", {})
    monkeypatch.setattr(ops, "_dense_queue_write", lambda: True)
    monkeypatch.setattr(ops, "_queue_write_dense",
                        lambda *a: dense.append(1) or queue_write(*a))
    got = simulate_batch(g, wls, prof, p, **kw)
    assert dense, "the dense write was not traced"
    busy = {"trims_gray": lambda r: r.trims > 0 and r.drops > 0,
            "cbfc": lambda r: r.credit_stall_ticks > 0,
            "a2a": lambda r: r.completion_tick() > 0}[case]
    assert all(busy(r) for r in want)
    for a, b in zip(want, got):
        assert a.horizon == b.horizon
        np.testing.assert_array_equal(a.stat_src_completion,
                                      b.stat_src_completion)
        np.testing.assert_array_equal(np.asarray(a.state.q_pkt),
                                      np.asarray(b.state.q_pkt))
        assert _state_equal(a.state, b.state)
