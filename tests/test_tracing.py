"""The simulator's own tracing: named phase scopes in the compiled
driver, the driver's chunk counter, and the host spans of
``simulate_batch`` (DESIGN.md, "Spans and counters"); and that the
outcome the benchmark checks is the same bits as before NSCC's IEEE
division.

Scopes are HLO ``op_name`` metadata; the golden and batch == serial
tests hold every lane's bits. conftest.py splits the CPU into 4 virtual
devices for the sharded case.
"""
import dataclasses
import glob
import hashlib
import re

import jax
import numpy as np
import pytest

from repro.network import fabric
from repro.network.collectives import CollectiveSpec, build_workload
from repro.network.fabric import (SimParams, Workload, simulate,
                                  simulate_batch)
from repro.network.faults import FaultSchedule
from repro.network.profile import TransportProfile
from repro.network.telemetry import TelemetrySpec
from repro.network.topology import fat_tree3

CHUNK = 64
SCOPE = re.compile(r"(?:^|[/(])((?:tick|driver)\.[a-z_]+(?:\.[a-z_]+)*)"
                   r"(?=$|[/)])")
#: the scopes every stats-tier batched driver compiles
BASE = {"tick.faults", "tick.control", "tick.grants", "tick.injection",
        "tick.forwarding", "tick.delivery", "tick.enqueue",
        "tick.enqueue.write", "tick.control_tc", "tick.timeouts", "tick.recovery",
        "driver.freeze", "driver.quiescent", "driver.stats"}


@pytest.fixture(scope="module")
def g():
    return fat_tree3(4, 2)


def _batch(sizes):
    return Workload.stack([Workload.of([0, 1, 2], [4, 5, 6], s)
                           for s in sizes])


def _compiled_scopes(g, profile, p, wls, fault, tel=None) -> set:
    init, run = fabric.driver_fns(g, profile, p, wls.src.shape[1], fault,
                                  "stats", batched=True, tel=tel)
    seeds = np.zeros(wls.src.shape[0], np.uint32)
    s0 = jax.eval_shape(init, wls, seeds)
    text = run.lower(s0, wls, fault, np.int32(512), np.int32(0),
                     np.int32(512)).compile().as_text()
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        found.update(SCOPE.findall(op_name))
    return found


@pytest.mark.parametrize("variant", ["lossy", "inc_ooo_telemetry"])
def test_every_compiled_phase_is_named_in_the_hlo(g, variant):
    """Each phase the profile compiles appears in the batched driver's
    compiled op_name metadata; phases it does not compile do not."""
    B = 2
    fault = FaultSchedule.healthy(g.num_queues, batch=B)
    profile, p, tel = TransportProfile.ai_full(), SimParams(), None
    want = set(BASE)
    if variant == "lossy":
        fault = dataclasses.replace(
            fault, loss_p=np.full((B, g.num_queues), 0.01, np.float32))
        want.add("tick.enqueue.loss")
    else:
        profile = TransportProfile.ai_full(inc=True)
        p = SimParams(ooo_threshold=3)
        tel = TelemetrySpec.on()
        want |= {"tick.ooo", "tick.inc", "tick.telemetry"}
    wls = Workload.stack([Workload.of([0, 1, 2], [5, 5, 5], 40,
                                      red=[0, 0, 0])] * B)
    assert _compiled_scopes(g, profile, p, wls, fault, tel) == want


def _expected(horizons):
    """(fast, masked) chunks of one while loop over these lanes."""
    h = np.asarray(horizons)
    return (int(h.min()) // CHUNK, int(h.max() - h.min()) // CHUNK)


def test_driver_chunks_on_even_and_staggered_batches(g):
    p = SimParams(ticks=4000, chunk_ticks=CHUNK)
    prof = TransportProfile.ai_full()
    even = simulate_batch(g, _batch([90] * 4), prof, p)
    h = even[0].horizon
    assert h % CHUNK == 0 and all(r.horizon == h for r in even)
    assert all(r.driver_chunks == (h // CHUNK, 0) for r in even)

    staggered = simulate_batch(g, _batch([40, 90, 140, 300]), prof, p)
    hs = [r.horizon for r in staggered]
    assert len(set(hs)) > 1
    assert all(r.driver_chunks == _expected(hs) for r in staggered)
    fast, masked = staggered[0].driver_chunks
    assert (fast + masked) * CHUNK == max(hs)

    one = simulate(g, Workload.of([0, 1, 2], [4, 5, 6], 140), prof, p)
    assert one.driver_chunks == (one.horizon // CHUNK, 0)


def test_driver_chunks_count_the_budgets_partial_chunk(g):
    """A budget inside a chunk: the last chunk runs the masked body."""
    r = simulate(g, Workload.of([0, 1, 2], [4, 5, 6], 5000),
                 TransportProfile.ai_full(), SimParams(chunk_ticks=CHUNK),
                 max_ticks=100)
    assert r.horizon == 100 and r.driver_chunks == (1, 1)


def test_full_tier_leaves_driver_chunks_unset(g):
    r = simulate(g, Workload.of([0, 1, 2], [4, 5, 6], 40),
                 TransportProfile.ai_full(), SimParams(chunk_ticks=CHUNK),
                 trace="full")
    assert r.driver_chunks is None


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
def test_driver_chunks_are_each_shards_own(g):
    """Each device's loop counts its own lanes' chunks."""
    sizes = [40, 300, 90, 140, 40, 40, 300, 90]
    p = SimParams(ticks=4000, chunk_ticks=CHUNK)
    got = simulate_batch(g, _batch(sizes), TransportProfile.ai_full(), p,
                         devices=4)
    for d in range(4):
        block = got[2 * d:2 * d + 2]
        want = _expected([r.horizon for r in block])
        assert all(r.driver_chunks == want for r in block), d
    assert got[4].driver_chunks[1] == 0          # an even pair: no mask


def test_simulate_batch_host_spans(g, tmp_path):
    """The call's host spans, in order, inside ``fabric.simulate_batch``."""
    p = SimParams(ticks=4000, chunk_ticks=CHUNK)
    wls = _batch([40, 90])
    prof = TransportProfile.ai_full()
    simulate_batch(g, wls, prof, p)
    jax.profiler.start_trace(str(tmp_path))
    try:
        simulate_batch(g, wls, prof, p)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path[0])
    spans = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for pl in pd.planes if pl.name.startswith("/host:")
                    for ln in pl.lines for e in ln.events
                    if e.name.startswith("fabric.")), key=lambda s: s[1])
    assert [s[0] for s in spans] == [
        "fabric.simulate_batch", "fabric.prepare", "fabric.prepare",
        "fabric.init", "fabric.run", "fabric.fetch", "fabric.split"]
    outer = spans[0]
    inner = spans[1:]
    assert all(outer[1] <= s and t <= outer[2] for _, s, t in inner)
    assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))


def _collective(kind, algo, ranks, size):
    return build_workload(CollectiveSpec(kind, tuple(range(ranks)), size),
                          algo)


#: the state fields and stat lanes that the benchmark's check compares
#: with its plain reference (``bench/check.py``'s ``program_outcome``)
OUTCOME_STATE = ("delivered", "next_psn", "inflight", "last_progress",
                 "src_track", "rtx", "dst_track", "cc", "trims", "drops",
                 "dups", "retransmits", "timeouts", "ticks_degraded", "q_len")
OUTCOME_STATS = ("horizon", "stat_completion", "stat_src_completion",
                 "stat_win_delivered", "qlen_peak")
#: their sha256 over the three scenarios below, as the simulator gave it
#: with NSCC's plain ``/`` (equal on a CPU, whose divide is IEEE)
OUTCOME_DIGEST = \
    "1c7f9ea1e81852d8afa38cb447c25f8f95489a7f4a36f5062d42b7de11613036"


def test_ieee_division_leaves_the_compared_outcome_bitwise_unchanged(g):
    """A lossless and a gray-link all-to-all lane, and an incast that
    trims: the compared fields hash as they did with ``/``."""
    p = SimParams(ticks=8192, chunk_ticks=CHUNK)
    prof = TransportProfile.ai_full()
    a2a = _collective("all_to_all", "round_robin", 8, 8 * 24)
    fault = FaultSchedule.healthy(g.num_queues, batch=2)
    loss = np.zeros((2, g.num_queues), np.float32)
    loss[1, 1] = 0.05
    rs = simulate_batch(g, Workload.stack([a2a] * 2), prof, p,
                        faults=dataclasses.replace(fault, loss_p=loss),
                        seeds=np.asarray([3, 4], np.uint32))
    rs.append(simulate(g, Workload.of([0, 2, 4], [6, 6, 6], 300), prof, p))
    assert rs[1].drops > 0 and rs[1].timeouts > 0 and rs[2].trims > 0
    h = hashlib.sha256()
    for r in rs:
        for leaf in jax.tree_util.tree_leaves(
                [getattr(r.state, f) for f in OUTCOME_STATE]
                + [getattr(r, f) for f in OUTCOME_STATS]):
            h.update(np.ascontiguousarray(leaf).tobytes())
    assert h.hexdigest() == OUTCOME_DIGEST
