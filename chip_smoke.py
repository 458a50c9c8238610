#!/usr/bin/env python3
"""Chip smoke: the fabric simulator's main path on a TPU.

    python chip_smoke.py              # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4    # four chips: phase (c) only

(a) kernels: the three Pallas kernels of the fabric tick (``sack_fused``,
    ``nack_mark``, ``sack_advance``), dispatched through
    ``repro.kernels.ops`` as the tick calls them and vmapped over the
    scenario batch, against their jnp oracles on seeded inputs, bitwise.
(b) main path: ``simulate_batch`` of a 64-rank tree all-reduce on the
    paper's Fig. 2 fat tree (k=8, 4 pods, 64 hosts, 320 queues), 32 seeds
    x 4 fault schedules, run cold and warm. Every lane must quiesce inside
    the tick budget, every host must receive exactly its payload, and the
    listed lanes must equal serial ``simulate`` runs bitwise.
(c) sharded: the (b) batch with ``shard=True`` over four chips against
    the same batch on one chip. Every result and final state must be
    bitwise equal, and each shard must run on its own device.

The times printed are smoke readings of one run, not benchmark numbers.
Everything runs in this one process, which holds the chips. The script
exits non-zero, without its result line, when JAX finds no TPU or any
phase fails; on success its last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.compile_cache import enable_persistent_cache  # noqa: E402
from repro.core.link import state_bitwise_equal  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.network import fabric  # noqa: E402
from repro.network.collectives import (CollectiveSpec,  # noqa: E402
                                       build_workload, expected_host_rx)
from repro.network.faults import FaultSchedule  # noqa: E402
from repro.network.profile import TransportProfile  # noqa: E402
from repro.network.topology import QueueGraph, paper_fig2  # noqa: E402

# the (b) configuration: 64 ranks x 64 packets on paper_fig2, 32 seeds x
# 4 fault schedules = 128 scenarios; healthy lanes finish near tick 7200
RANKS = 64
SIZE_PKTS = 64
SEEDS = 32
BUDGET = 16384
FLAP = (2000, 3000)
GRAY_P = 0.01
LISTED = 4

TPU_CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _say(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def _compile_timer():
    """Wall seconds spent tracing, lowering and compiling (or loading
    from the persistent cache) inside the block: the union of those
    events' spans, since traces of inner jitted calls nest."""
    spans, acc = [], {"s": 0.0}

    def on_span(event, start, end, **_):
        if event in _COMPILE_EVENTS:
            spans.append((start, end))

    jax.monitoring.register_event_time_span_listener(on_span)
    try:
        yield acc
    finally:
        jax.monitoring.unregister_event_time_span_listener(on_span)
        reach = float("-inf")
        for start, end in sorted(spans):
            acc["s"] += max(0.0, end - max(start, reach))
            reach = max(reach, end)


def _pack(plane: np.ndarray) -> np.ndarray:
    """[..., W*32] bool -> [..., W] uint32, bit j of word k = plane[32k+j]."""
    words = plane.reshape(plane.shape[:-1] + (-1, 32)).astype(np.uint64)
    return (words << np.arange(32, dtype=np.uint64)).sum(-1).astype(np.uint32)


def _leaves_equal(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def _first_difference(a: fabric.SimResult, b: fabric.SimResult
                      ) -> "str | None":
    """Name of the first SimResult field (or state lane) that differs.
    ``driver_chunks`` is left out: it counts the executable's chunks,
    which a batch and a serial call run differently, not the lane's
    outcome."""
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if f.name == "driver_chunks":
            continue
        if f.name == "state":
            lane = state_bitwise_equal(x, y, skip=())
            if lane is not None:
                return f"state.{lane}"
        elif not _leaves_equal(x, y):
            return f.name
    return None


def _peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats()
    return str(stats["peak_bytes_in_use"]) if stats else "not reported"


def nack_lanes(g: QueueGraph, num_flows: int) -> int:
    """NACK lanes the tick hands ``nack_mark``: the control-event lanes
    (2Q + 2F) past the Q ACK lanes."""
    return g.num_queues + 2 * num_flows


# ---------------------------------------------------------------- (a)

def kernel_phase(num_flows: int, ring_words: int, lanes: int, batch: int,
                 seed: int = 0, expect_kernels: bool = True) -> dict:
    """Each hot-path op, vmapped over `batch` scenarios as the batched
    tick runs it, against its jnp oracle on seeded inputs. With
    `expect_kernels` the executable must hold a Pallas kernel."""
    rng = np.random.default_rng(seed)
    B, F, W, L = batch, num_flows, ring_words, lanes
    mp = W * 32

    def ring():
        # a received prefix of random length, then sparse bits above it
        prefix = rng.integers(0, mp + 1, (B, F, 1))
        return _pack((np.arange(mp) < prefix) | (rng.random((B, F, mp)) < .05))

    base = rng.integers(0, 2 ** 32, (B, F), dtype=np.uint32)
    base[:, :4] = 0xFFFFFFF0                     # CACK wraps past 2^32
    rtx = rng.integers(0, 2 ** 32, (B, F, W), dtype=np.uint32)
    mask = _pack(rng.random((B, F, mp)) < 0.02)
    flow = rng.integers(0, F + 2, (B, L)).astype(np.int32)  # F, F+1: no row
    off = rng.integers(0, mp, (B, L)).astype(np.int32)
    valid = rng.random((B, L)) < 0.3
    dup = min(16, L // 2)                        # repeated (flow, off) lanes
    for a in (flow, off, valid):
        a[:, L - dup:] = a[:, :dup]

    cases = {
        "sack_fused": (ops.sack_fused, ref.sack_fused_ref,
                       (ring(), base, rtx, mask)),
        "nack_mark": (ops.nack_mark, ref.nack_mark_ref,
                      (rtx, flow, off, valid)),
        "sack_advance": (ops.sack_advance, ref.sack_advance_ref,
                         (ring(), base)),
    }
    out = {}
    for name, (op, oracle, args) in cases.items():
        args = tuple(jnp.asarray(a) for a in args)
        fn = jax.jit(jax.vmap(op))
        got = fn(*args)
        want = jax.jit(jax.vmap(oracle))(*args)
        calls = fn.lower(*args).compile().as_text().count(TPU_CUSTOM_CALL)
        equal = _leaves_equal(got, want)
        out[name] = {"bitwise_equal": equal, "tpu_custom_calls": calls}
        _say(f"(a) {name}: batch {B} x [{F}, {W}]"
             + (f", {L} NACK lanes" if name == "nack_mark" else "")
             + f": bitwise equal to its oracle: {equal}; "
               f"tpu_custom_call in executable: {calls}")
        _check(equal, f"{name} differs from its jnp oracle")
        _check(calls > 0 or not expect_kernels,
               f"{name} ran without a Pallas kernel")
    return out


# ---------------------------------------------------------------- (b)

def fault_schedules(g: QueueGraph, flap: "tuple[int, int]",
                    gray_p: float) -> "dict[str, FaultSchedule]":
    """Healthy; leaf 0's first uplink flapped over `flap`; its second
    uplink gray at `gray_p`; both together."""
    up_a, up_b = (int(q) for q in g.up1_table[0, :2])
    healthy = FaultSchedule.healthy(g.num_queues)
    return {
        "healthy": healthy,
        "flap": healthy.flap(up_a, *flap),
        "gray": healthy.lossy(up_b, gray_p),
        "flap+gray": healthy.flap(up_a, *flap).lossy(up_b, gray_p),
    }


@dataclasses.dataclass
class Batch:
    """The scenario batch of phases (b) and (c)."""

    g: QueueGraph
    spec: CollectiveSpec
    profile: TransportProfile
    params: fabric.SimParams
    names: list          # [B] fault schedule name of each lane
    seeds: np.ndarray    # [B] uint32
    faults: list         # [B] per-lane [Q] FaultSchedule

    @property
    def size(self) -> int:
        return len(self.names)

    def workload(self) -> fabric.Workload:
        return build_workload(self.spec, "tree")

    def stacked(self) -> "tuple[fabric.Workload, FaultSchedule]":
        return (fabric.Workload.stack([self.workload()] * self.size),
                FaultSchedule.stack(self.faults))

    def run(self, **kw) -> "list[fabric.SimResult]":
        wls, fault = self.stacked()
        return fabric.simulate_batch(self.g, wls, self.profile, self.params,
                                     faults=fault, seeds=self.seeds, **kw)

    def compiled(self, devs=None):
        """The executable ``simulate_batch`` ran for this batch (sharded
        over `devs` if given): the cached jitted driver that
        ``fabric.driver_fns`` hands ``simulate_batch``, lowered on the
        same arguments, which JAX serves from its in-memory cache."""
        wls, fault = self.stacked()
        init, run = fabric.driver_fns(
            self.g, self.profile, self.params, int(wls.src.shape[1]), fault,
            "stats", batched=True, devs=devs)
        budget = self.params.ticks
        return run.lower(init(wls, jnp.asarray(self.seeds)), wls, fault,
                         jnp.int32(budget), jnp.int32(0),
                         jnp.int32(budget)).compile()

    def run_serial(self, i: int) -> fabric.SimResult:
        return fabric.simulate(self.g, self.workload(), self.profile,
                               self.params, seed=int(self.seeds[i]),
                               faults=self.faults[i])


def make_batch(g: QueueGraph, ranks: int, size_pkts: int, n_seeds: int,
               budget: int, flap: "tuple[int, int]",
               gray_p: float) -> Batch:
    """`n_seeds` seeds x the four fault schedules, schedule-major; a
    lane's seed drives both its LB/EV draws and its gray-link loss."""
    names, seeds, faults = [], [], []
    for name, sched in fault_schedules(g, flap, gray_p).items():
        for k in range(n_seeds):
            seed = 0x5EED + 7919 * k
            names.append(name)
            seeds.append(seed)
            faults.append(sched.with_seed(seed))
    return Batch(g, CollectiveSpec("all_reduce", tuple(range(ranks)),
                                   size_pkts),
                 TransportProfile.ai_full(), fabric.SimParams(ticks=budget),
                 names, np.asarray(seeds, np.uint32), faults)


def main_path_phase(b: Batch, listed: int = LISTED,
                    expect_kernels: bool = True) -> dict:
    """Run `b` cold and warm through ``simulate_batch`` and check it."""
    budget = b.params.ticks
    with _compile_timer() as ct:
        t0 = time.perf_counter()
        cold = b.run()
        cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = b.run()
    warm_s = time.perf_counter() - t0
    calls = b.compiled().as_text().count(TPU_CUSTOM_CALL)
    horizons = np.asarray([r.horizon for r in warm])
    _say(f"(b) smoke reading, not a benchmark: {b.size} scenarios of "
         f"{len(b.workload().src)} flows on {b.g.name}: compile "
         f"{ct['s']:.2f} s, cold call {cold_s:.2f} s, warm call "
         f"{warm_s:.2f} s, {int(horizons.max())} ticks executed by the "
         f"batch ({int(horizons.sum())} lane-ticks to quiescence), "
         f"tpu_custom_call in "
         f"executable: {calls}, peak_bytes_in_use: {_peak_bytes()}")

    for i, (c, w) in enumerate(zip(cold, warm)):
        diff = _first_difference(c, w)
        _check(diff is None, f"lane {i}: cold and warm calls differ in {diff}")
    _check(calls > 0 or not expect_kernels,
           "the batched tick ran without a Pallas kernel")
    late = [i for i, r in enumerate(warm)
            if r.horizon >= budget or r.completion_tick() < 0]
    worst = {}
    for name, h in zip(b.names, horizons):
        worst[name] = max(worst.get(name, 0), int(h))
    _say(f"(b) lanes quiesced inside the {budget}-tick budget: "
         f"{b.size - len(late)}/{b.size}; last horizon per schedule: "
         f"{worst}")
    _check(not late, f"lanes {late} did not quiesce inside the budget")

    wl = b.workload()
    want = np.zeros((b.g.num_hosts,), np.int64)
    want[list(b.spec.hosts)] = expected_host_rx(b.spec, "tree")
    wrong = []
    for i, r in enumerate(warm):
        rx = np.zeros((b.g.num_hosts,), np.int64)
        np.add.at(rx, np.asarray(wl.dst), np.asarray(r.state.delivered))
        if not np.array_equal(rx, want):
            wrong.append(i)
    _say(f"(b) lanes with exact per-host payload: {b.size - len(wrong)}/"
         f"{b.size}")
    _check(not wrong, f"lanes {wrong} delivered the wrong per-host payload")

    per = b.size // len(set(b.names))
    lanes = [k * per + (11 * k) % per for k in range(listed)]
    for i in lanes:
        t0 = time.perf_counter()
        serial = b.run_serial(i)
        serial_s = time.perf_counter() - t0
        diff = _first_difference(serial, warm[i])
        _say(f"(b) lane {i} ({b.names[i]}, seed {int(b.seeds[i])}): batch "
             f"== serial simulate bitwise: {diff is None} (serial call "
             f"{serial_s:.2f} s for {serial.horizon} ticks, with its "
             f"compile where the executable is new)")
        _check(diff is None, f"lane {i}: batch and serial differ in {diff}")
    return {"compile_s": ct["s"], "cold_s": cold_s, "warm_s": warm_s,
            "ticks": int(horizons.max()), "tpu_custom_calls": calls}


# ---------------------------------------------------------------- (c)

def sharded_phase(b: Batch, devices: int) -> dict:
    """`b` sharded over the first `devices` devices against `b` on one."""
    devs = tuple(jax.devices()[:devices])
    _check(len(devs) == devices,
           f"{devices} devices asked for, {len(jax.devices())} present")
    _check(b.size % devices == 0,
           f"batch of {b.size} does not split over {devices} devices")
    t0 = time.perf_counter()
    one = b.run()
    one_s = time.perf_counter() - t0
    with _compile_timer() as ct:
        t0 = time.perf_counter()
        sharded = b.run(devices=list(devs))
        sharded_s = time.perf_counter() - t0
    diffs = {i: d for i, (x, y) in enumerate(zip(one, sharded))
             if (d := _first_difference(x, y)) is not None}
    _say(f"(c) sharded over {devices} devices == one device, bitwise, "
         f"results and final states: {b.size - len(diffs)}/{b.size} lanes")
    _check(not diffs, f"sharded lanes differ: {diffs}")

    # where the sharded executable put each device's scenario lanes
    horizon = b.compiled(devs).output_shardings[2]
    placed = {d.id: (idx[0].start, idx[0].stop) for d, idx
              in horizon.devices_indices_map((b.size,)).items()}
    per = b.size // devices
    _say(f"(c) smoke reading, not a benchmark: one-device cold call "
         f"{one_s:.2f} s; sharded cold call {sharded_s:.2f} s, of it "
         f"compile {ct['s']:.2f} s; scenario lanes per device {placed}")
    _check(sorted(placed) == sorted(d.id for d in devs)
           and sorted(placed.values()) == [(k * per, (k + 1) * per)
                                           for k in range(devices)],
           f"shards did not land one per device: {placed}")
    return {"compile_s": ct["s"], "one_device_s": one_s,
            "sharded_s": sharded_s, "devices": sorted(placed)}


# ---------------------------------------------------------------- main

def _phase(name: str, fn, *args, **kw) -> bool:
    try:
        fn(*args, **kw)
        return True
    except Exception:                    # report every phase, then fail
        _say(f"{name}: FAILED")
        traceback.print_exc()
        return False


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded batch against one chip")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    cache = enable_persistent_cache()
    _say(f"device: {dev.device_kind} x {len(jax.devices())}; compile "
         f"cache: {cache}")

    g = paper_fig2()
    b = make_batch(g, RANKS, SIZE_PKTS, SEEDS, BUDGET, FLAP, GRAY_P)
    if args.chips == 4:
        ok = _phase("(c) sharded", sharded_phase, b, 4)
    else:
        F = len(b.workload().src)
        ring_words = b.params.mp_range // 32
        ok = _phase("(a) kernels", kernel_phase, F, ring_words,
                    nack_lanes(g, F), b.size)
        ok = _phase("(b) main path", main_path_phase, b) and ok
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
