"""Fabric-simulator performance benchmarks: ticks/sec and scenarios/sec.

Measures the hot path of the UET fabric engine in four configurations —

* ``single``         — one compiled scan, one scenario (ticks/sec; the
                       per-tick hot-path number the fused kernels moved);
* ``serial_seed``    — B scenarios the way the *seed* architecture ran a
                       sweep: the failure set was a static tuple closed
                       over by jit, so EVERY scenario paid its own
                       trace+compile before running. This is the baseline
                       the batched engine exists to kill (and the
                       acceptance comparison for scenarios/sec).
* ``serial_shared``  — B sequential ``simulate`` calls on this PR's
                       serial path (failure masks/seeds/workloads are
                       traced, so one warm executable is reused). Reported
                       for transparency: most of the sweep win is the
                       recompile removal, the rest is vmap amortization.
* ``batched``        — the same B scenarios in one ``simulate_batch``
                       (vmapped scan, carry donated), cold and warm.

Also runs the profile-ablation sweep (ai_base / ai_full / hpc plus the
NSCC-only / RCCC-only / hybrid / open-loop CC ablation) as ONE
``simulate_batch`` call — the engine groups the grid by distinct
profile, one executable each, run concurrently — and records
per-profile goodput under ``profile_ablation``. The scenario is the
oversubscribed in-network pattern whose same-leaf victim flow actually
separates the CC policies (asserted — a bench whose ablation axis
reports one number is measuring nothing).

The collective ablation grid (kind x algorithm x INC on/off x profile,
15 dependency-scheduled whole collectives padded into one batch) runs
as ONE ``simulate_batch`` call too and lands under ``collective_sweep``:
per-scenario completion ticks, scenarios/sec, and the in-network-
reduction win (INC-on / INC-off completion ratio for the tree
all-reduce). Both sweeps run the default ``trace="stats"`` tier on the
adaptive-horizon engine: completion ticks stream out of the chunked
while-scan, scenarios exit at quiescence instead of padding to the
budget, and INC on/off rides the traced ``red`` lanes (one executable
per transport profile for the whole grid).

api_version 5 additions (the scale-out engine):

* ``ticks_per_sec_fixed_scan`` — the PR-3 driver reproduced (one
  vmapped fixed-length scan, dense out lanes materialized into
  SimResults) as the same-box head-to-head reference for the chunked
  driver's fast path; ``..._device`` is the device-program-only
  variant (no gather/result build), isolating driver speed from the
  trace tiers;
* ``ticks_per_sec_batched_fastpath`` — the chunked driver with
  ``chunk_ticks`` aligned to divide the budget, so every chunk takes
  the select-free fast body (no masked remainder);
* ``sharded_sweep`` — a heterogeneous-horizon scenario sweep, sorted by
  expected horizon, run unsharded vs ``shard=True`` in the bench's own
  process (a chip belongs to the one process that touched JAX first).
  On CPU, ``--devices`` virtual devices are forced before JAX starts,
  so every block of the bench runs on the split host. Fewer than two
  devices is an error, not a skipped block: ``scenarios_per_sec_sharded``,
  device count, and the speedup;
* ``calibration`` — a fixed tiny scenario re-measured on every box;
  ``scripts/bench_compare.py`` normalizes cross-box regression ratios
  by it so machine drift stops masquerading as engine regressions.

api_version 6 additions (the fault-injection engine): ``fault_sweep``
— the dynamic-fault grid (link flaps, gray links, mid-run death;
``workloads.fault_sweep``) as one batch with per-scenario
FaultSchedules riding the scenario axis, with in-bench gates: liveness
(>= 1 surviving path -> every flow completes), degradation (faults
cost ticks and fire timeouts), and the recovery-loop separation
(``ev_eviction=True`` beats eviction-off under a permanent mid-run
failure of a static path).

api_version 7 additions (the model-driven traffic engine):
``model_sweep`` — the co-design grid (model x sharding layout x
topology x transport profile), every operating point's per-step
collective schedule derived from the REAL sharding rules
(``repro.distributed.plan``), compiled to one dep-chained fabric
workload (``repro.network.traffic``) and priced end-to-end (step time,
tokens/sec) from ONE ``simulate_batch`` call over per-scenario graphs
AND profiles. In-bench gates assert the axes actually separate: the
fsdp_tp decode penalty vs the tp_only serving layout, the hpc-vs-ai
transport separation on the oversubscribed fabric, and topology
monotonicity.

api_version 8 additions (the telemetry plane): ``fabric_health`` — the
flap scenario on the shared victim-share fabric
(``workloads.victim_sweep``) with ``TelemetrySpec.on()`` probes, gated
on outage VISIBILITY (silent-drop rate confined to the fault window,
goodput dip + recovery, the NSCC mark-rate throttle response, the
heal-boundary trim burst) and on non-perturbation (telemetry-on final
state bitwise equals telemetry-off). Prices the plane itself as the
``telemetry_overhead`` warm-time ratio. Telemetry-off runs compile the
identical pre-telemetry program, so every existing guarded metric
doubles as the telemetry-off regression gate.

api_version 9 additions (endpoint-failure resilience):
``resilience_sweep`` — the endpoint-fault grid
(``workloads.host_fault_sweep``: host death, the same death with PDC
liveness off, a healing NIC stall, healthy) as one batch with
per-scenario host-fault lanes, gated on the teardown contract (the
dead-host lane quiesces EARLY with its victim flows abandoned; the
pdc-off twin burns the full budget; the NIC stall completes with
nothing abandoned), plus the priced checkpoint-restart recovery loop:
``traffic.price_recovery`` measures detection (fault ->
``abandon_tick``), sharded-restore and replan-onto-survivors costs for
a train plan, and the Young/Daly closed forms price effective
tokens/sec over an MTBF x checkpoint-interval grid — asserting
in-bench that the Young/Daly interval beats naive fixed intervals at
every MTBF and that availability is monotone in MTBF.

Writes ``BENCH_fabric.json`` at the repo root so the perf trajectory
accumulates across PRs; append each run's headline numbers to
``BENCH_history.jsonl`` with ``python scripts/bench_history.py``.

Usage: PYTHONPATH=src python -m benchmarks.perf_benches [--scenarios 8]
       [--ticks 600] [--devices 4] [--out BENCH_fabric.json]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def _force_host_devices(n: int) -> None:
    """Split the host CPU into n virtual devices for the sharded sweep.
    Only effective before the first jax import (jax locks the backend),
    and only when the user hasn't already forced a count."""
    flags = os.environ.get("XLA_FLAGS", "")
    if n > 1 and "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())


def _bench_config(ticks: int):
    from repro.core.lb.schemes import LBScheme
    from repro.network.fabric import SimParams, Workload
    from repro.network.profile import TransportProfile
    from repro.network.topology import leaf_spine

    g = leaf_spine(leaves=2, spines=4, hosts_per_leaf=8)
    f = 8
    wl = Workload.of(list(range(f)), [f + i for i in range(f)], 100000)
    prof = TransportProfile.ai_full(lb=LBScheme.REPS)
    p = SimParams(ticks=ticks, timeout_ticks=64, ooo_threshold=24)
    return g, wl, prof, p


def _scenarios(g, wl, b: int):
    """B scenarios: scenario i fails leaf-0 uplink (i mod spines) for odd
    i and uses a distinct LB seed — a failure x seed sweep."""
    from repro.network.fabric import DEFAULT_SEED, Workload

    spines = g.up1_table.shape[1]
    masks = np.zeros((b, g.num_queues), bool)
    seeds = np.zeros((b,), np.uint32)
    for i in range(b):
        seeds[i] = DEFAULT_SEED + i
        if i % 2 == 1:
            masks[i, int(g.up1_table[0, i % spines])] = True
    wls = Workload.stack([wl] * b)
    return wls, masks, seeds


def _fixed_scan_batched(g, wls, prof, p, masks, seeds, b: int):
    """The PR-3 batched driver reproduced: ONE vmapped fixed-length
    ``lax.scan`` over the whole tick budget with dense per-tick out
    lanes, materialized into full-trace SimResults — the head-to-head
    reference the chunked driver's fast path is measured against.
    Returns (call, call_device_only): the first materializes results as
    PR-3's simulate_batch did, the second just blocks on the device
    program (isolates driver speed from the trace tier)."""
    import jax
    import jax.numpy as jnp

    from repro.network import fabric

    from repro.network.faults import FaultSchedule

    F = int(wls.src.shape[-1])
    step = fabric.make_step(g, prof, p, F)
    xs = jnp.arange(p.ticks, dtype=jnp.int32)

    def scan_one(s0, wl_, fault):
        def body(s, tick):
            return step(s, tick, wl_, fault)
        return jax.lax.scan(body, s0, xs)

    run = jax.jit(jax.vmap(scan_one), donate_argnums=(0,))
    init = jax.jit(jax.vmap(
        lambda w_, s_: fabric.init_state(g, w_, prof, p, s_)))
    fault = FaultSchedule.from_mask(jnp.asarray(masks))
    sds = jnp.asarray(seeds, jnp.uint32)
    sizes = np.asarray(wls.size)

    def call():
        s0 = init(wls, sds)
        final, outs = run(s0, wls, fault)
        final = jax.device_get(final)
        outs = jax.device_get(outs)
        return [
            fabric._full_result(
                jax.tree_util.tree_map(lambda a: a[i], final),
                {k: v[i] for k, v in outs.items()},
                sizes[i], p.ticks, p.ticks)
            for i in range(b)
        ]

    def call_device_only():
        s0 = init(wls, sds)
        jax.block_until_ready(run(s0, wls, fault))

    return call, call_device_only


def _aligned_chunk(budget: int, target: int = 128) -> int:
    """Divisor of `budget` near `target`: a chunk size under which every
    chunk of the budget takes the driver fast path (no masked
    remainder). Budgets with no usable divisor (e.g. primes) fall back
    to `target` — one masked remainder, same as the default chunking —
    rather than degenerating to a tiny chunk that measures while-loop
    overhead instead of the fast path."""
    k = max(1, round(budget / target))
    while k <= budget and budget % k:
        k += 1
    chunk = budget // k if k <= budget else budget
    return chunk if chunk >= 16 else min(budget, target)


def _seed_style_simulate(g, wl, prof, p, mask, seed):
    """One scenario the way the seed architecture ran it: the failure set
    baked into the executable as a static constant, so this scenario's
    run starts with its own trace+compile (no sharing across the sweep)."""
    import jax
    import jax.numpy as jnp

    from repro.network import fabric

    from repro.network.faults import FaultSchedule

    F = int(wl.src.shape[0])
    step = fabric.make_step(g, prof, p, F)
    fault_const = FaultSchedule.from_mask(jnp.asarray(mask))

    def scan_one(s0, wl_):
        def body(s, tick):
            return step(s, tick, wl_, fault_const)
        return jax.lax.scan(body, s0, jnp.arange(p.ticks, dtype=jnp.int32))

    run = jax.jit(scan_one, donate_argnums=(0,))
    s0 = fabric.init_state(g, wl, prof, p, jnp.uint32(seed))
    final, outs = run(s0, wl)
    return fabric._to_result(final, outs, wl.size)


def run_benches(b: int, ticks: int) -> dict:
    import jax

    from repro.network.fabric import simulate, simulate_batch

    # the sharded sweep runs last: fail before any block rather than
    # throw away every measurement at the end
    ndev = len(jax.devices())
    if ndev < 2:
        raise RuntimeError(
            f"the sharded sweep needs >= 2 devices, {ndev} visible (on "
            f"CPU pass --devices N before JAX starts)")
    g, wl, prof, p = _bench_config(ticks)
    wls, masks, seeds = _scenarios(g, wl, b)
    fq = [tuple(np.nonzero(masks[i])[0].tolist()) for i in range(b)]

    results = {
        "api_version": 10,
        "backend": jax.default_backend(),
        "topology": g.name,
        "flows": int(wl.src.shape[0]),
        "ticks": ticks,
        "scenarios": b,
        "profile": prof.name,
        "profile_spec": prof.describe(),
    }

    # --- single scenario: compile + warm ticks/sec ---
    t0 = time.perf_counter()
    simulate(g, wl, prof, p)
    results["single_cold_s"] = time.perf_counter() - t0
    warm = min(_timed(lambda: simulate(g, wl, prof, p)) for _ in range(5))
    results["single_warm_s"] = warm
    results["ticks_per_sec_single"] = ticks / warm

    # --- seed-style serial sweep: fresh executable per scenario ---
    t0 = time.perf_counter()
    for i in range(b):
        _seed_style_simulate(g, wl, prof, p, masks[i], int(seeds[i]))
    serial_seed = time.perf_counter() - t0
    results["serial_seed_sweep_s"] = serial_seed
    results["scenarios_per_sec_serial"] = b / serial_seed
    results["serial_mode"] = ("per-scenario trace+compile (static failure "
                              "set, the seed architecture)")

    # --- shared-executable serial sweep: the warm serial path ---
    for i in range(2):  # warm
        simulate(g, wl, prof, p, failed=fq[i], seed=int(seeds[i]))
    t0 = time.perf_counter()
    for i in range(b):
        simulate(g, wl, prof, p, failed=fq[i], seed=int(seeds[i]))
    serial_shared = time.perf_counter() - t0
    results["serial_shared_sweep_s"] = serial_shared
    results["scenarios_per_sec_serial_shared"] = b / serial_shared

    # --- batched sweep: one simulate_batch() call ---
    t0 = time.perf_counter()
    simulate_batch(g, wls, prof, p, failed=masks, seeds=seeds)
    batched_cold = time.perf_counter() - t0
    results["batched_cold_s"] = batched_cold
    batched = min(_timed(
        lambda: simulate_batch(g, wls, prof, p, failed=masks, seeds=seeds))
        for _ in range(3))
    results["batched_sweep_s"] = batched
    results["scenarios_per_sec_batched"] = b / batched
    results["ticks_per_sec_batched"] = b * ticks / batched
    # acceptance metric: one batched sweep (incl. its compile) vs the
    # seed architecture's sweep (per-scenario compiles)
    results["batch_speedup_vs_serial"] = serial_seed / batched_cold
    results["batch_speedup_vs_serial_shared_warm"] = serial_shared / batched

    # --- fixed-scan head-to-head: the driver the chunked engine replaced ---
    from dataclasses import replace as _replace
    fixed, fixed_dev = _fixed_scan_batched(g, wls, prof, p, masks, seeds, b)
    fixed()  # compile
    fixed_warm = min(_timed(fixed) for _ in range(3))
    results["fixed_scan_sweep_s"] = fixed_warm
    results["ticks_per_sec_fixed_scan"] = b * ticks / fixed_warm
    # device-program-only variant (block_until_ready, nothing gathered):
    # isolates raw driver speed from each engine's result tier — the
    # fixed scan ships dense [T, B, F] lanes, the chunked default ships
    # streamed stats, and the as-shipped comparison below includes each
    # one's own materialization cost.
    fixed_dev_warm = min(_timed(fixed_dev) for _ in range(3))
    results["ticks_per_sec_fixed_scan_device"] = b * ticks / fixed_dev_warm
    # the acceptance ratio: chunked driver (fast path, stats tier) vs
    # the fixed-scan driver as PR-3 shipped it (dense tier), same box,
    # same sweep, each materializing its own results
    results["fastpath_vs_fixed_scan"] = (
        results["ticks_per_sec_batched"] / results["ticks_per_sec_fixed_scan"])

    # --- fast path with a budget-aligned chunk: no masked remainder ---
    chunk = _aligned_chunk(ticks)
    pf = _replace(p, chunk_ticks=chunk)
    simulate_batch(g, wls, prof, pf, failed=masks, seeds=seeds)
    fast = min(_timed(
        lambda: simulate_batch(g, wls, prof, pf, failed=masks, seeds=seeds))
        for _ in range(3))
    results["fastpath_chunk_ticks"] = chunk
    results["ticks_per_sec_batched_fastpath"] = b * ticks / fast

    results["profile_ablation"] = _profile_ablation(ticks)
    results["collective_sweep"] = _collective_sweep()
    results["fault_sweep"] = _fault_sweep()
    results["resilience_sweep"] = _resilience_sweep()
    results["fabric_health"] = _fabric_health()
    results["corruption_sweep"] = _corruption_sweep()
    results["model_sweep"] = _model_sweep()
    results["sharded_sweep"] = _sharded_sweep()
    results["calibration"] = _calibration()
    return results


def _sharded_sweep(b: int = 32, budget: int = 4096) -> dict:
    """Scenario sharding across devices: a heterogeneous incast-free
    sweep (per-scenario message sizes spanning ~20x, sorted ascending so
    each device gets a contiguous horizon band) run unsharded vs
    ``shard=True``. Sorting matters: the unsharded engine pays the
    max-lane horizon for every lane, while each device's while loop
    exits at its own band's quiescence — the speedup is device
    parallelism times that work saving."""
    import jax

    from repro.core.lb.schemes import LBScheme
    from repro.network.fabric import SimParams, Workload, simulate_batch
    from repro.network.profile import TransportProfile
    from repro.network.topology import leaf_spine

    ndev = len(jax.devices())
    g = leaf_spine(leaves=2, spines=4, hosts_per_leaf=8)
    f = 8
    sizes = np.geomspace(60, 1200, b).astype(int)
    wls = Workload.stack(
        [Workload.of(list(range(f)), [f + i for i in range(f)], int(s))
         for s in sizes])
    prof = TransportProfile.ai_full(lb=LBScheme.REPS)
    p = SimParams(ticks=budget, timeout_ticks=64, ooo_threshold=24)

    t0 = time.perf_counter()
    rs = simulate_batch(g, wls, prof, p)
    unsh_cold = time.perf_counter() - t0
    unsh = min(_timed(lambda: simulate_batch(g, wls, prof, p))
               for _ in range(2))
    t0 = time.perf_counter()
    rs_sh = simulate_batch(g, wls, prof, p, shard=True)
    sh_cold = time.perf_counter() - t0
    sh = min(_timed(lambda: simulate_batch(g, wls, prof, p, shard=True))
             for _ in range(2))
    # the whole point is bitwise-equal lanes: assert it on every run
    for a, c in zip(rs, rs_sh):
        assert a.horizon == c.horizon
        np.testing.assert_array_equal(a.completion_ticks(),
                                      c.completion_ticks())
    return {
        "devices": ndev,
        "scenarios": b,
        "horizon_band": [int(rs[0].horizon), int(rs[-1].horizon)],
        "unsharded_cold_s": unsh_cold,
        "unsharded_warm_s": unsh,
        "sharded_cold_s": sh_cold,
        "sharded_warm_s": sh,
        "scenarios_per_sec_unsharded": b / unsh,
        "scenarios_per_sec_sharded": b / sh,
        "shard_speedup": unsh / sh,
    }


def _calibration() -> dict:
    """Fixed tiny scenario re-measured on every box. bench_compare
    divides cross-box regression ratios by (fresh / committed) of this
    number, so a slower/faster machine shifts every metric AND the
    calibration together and cancels out — the PR-4 27.2k->17.2k
    confusion (box drift read as an engine regression) can't recur.
    Limitation: this scenario runs the engine itself, so an engine-wide
    per-tick regression shifts it too; bench_compare prints a loud
    CALIBRATION-SHIFT warning in that case instead of silently
    normalizing it away."""
    from repro.network.fabric import SimParams, Workload, simulate
    from repro.network.profile import TransportProfile
    from repro.network.topology import leaf_spine

    g = leaf_spine(leaves=2, spines=2, hosts_per_leaf=2)
    wl = Workload.of([0, 1], [2, 3], 10**6)      # never completes
    p = SimParams(ticks=256)
    prof = TransportProfile.ai_full()
    simulate(g, wl, prof, p)                     # compile
    warm = min(_timed(lambda: simulate(g, wl, prof, p)) for _ in range(7))
    return {
        "config": "leafspine_L2_S2_H2 / 2 flows / 256 ticks / ai_full",
        "ticks_per_sec": 256 / warm,
    }


def _profile_ablation(ticks: int) -> dict:
    """The operating-point grid as ONE simulate_batch call: the three
    named profiles + the CC ablation (7 scenarios, grouped by profile
    into one executable each) on the oversubscribed in-network pattern.

    Asserts the realism property the old incast version silently lacked:
    nscc_only / rccc_only / open_loop must visibly diverge on the
    same-leaf victim flow (blind receiver credits cap it at ~50%; NSCC
    pushes it toward the 1 - uplinks/pairs optimum)."""
    from repro.network import workloads
    from repro.network.fabric import SimParams, simulate_batch

    g, wls, profiles, names, exp = workloads.profile_ablation_sweep()
    p = SimParams(ticks=ticks, timeout_ticks=64)
    window = (ticks // 3, ticks)
    run = lambda: simulate_batch(g, wls, profiles, p,  # noqa: E731
                                 goodput_window=window)
    t0 = time.perf_counter()
    rs = run()
    cold = time.perf_counter() - t0
    warm = min(_timed(run) for _ in range(2))
    v = exp["victim_flow"]
    gp = {name: r.goodput(window) for name, r in zip(names, rs)}
    victim = {name: round(float(x[v]), 4) for name, x in gp.items()}
    # realism gate: if the CC axis reports one number, the sweep is
    # differentiating nothing and the bench is broken
    assert victim["nscc_only"] > victim["open_loop"] + 0.05, victim
    assert victim["open_loop"] > victim["rccc_only"] + 0.05, victim
    assert abs(victim["rccc_only"] - exp["rccc_local_share"]) < 0.08, victim
    return {
        "scenarios": len(profiles),
        "distinct_profiles": len(set(profiles)),
        "sweep_cold_s": cold,
        "sweep_warm_s": warm,
        "scenarios_per_sec": len(profiles) / warm,
        "victim_flow_share": victim,
        "victim_share_optimal": exp["optimal_local_share"],
        "goodput_mean": {
            name: round(float(x.mean()), 4) for name, x in gp.items()
        },
    }


def _collective_sweep(ticks: int = 1600) -> dict:
    """The collective ablation grid — kind x algorithm x INC on/off x
    profile, 15 whole dependency-scheduled collectives — as ONE
    ``simulate_batch`` call on the adaptive-horizon engine: INC on/off
    rides the traced ``red`` lanes, so the grid compiles to just 2
    executables (ai_full / ai_base), run concurrently, and every
    scenario exits at quiescence instead of padding to the 1600-tick
    budget (completions land at 71-542 ticks)."""
    from repro.network import collectives as coll
    from repro.network import workloads
    from repro.network.fabric import SimParams, simulate_batch

    g, wls, profiles, names = workloads.collective_sweep()
    p = SimParams(ticks=ticks)
    t0 = time.perf_counter()
    rs = simulate_batch(g, wls, profiles, p)
    cold = time.perf_counter() - t0
    warm = min(_timed(lambda: simulate_batch(g, wls, profiles, p))
               for _ in range(2))
    cts = {name: coll.collective_completion_ticks(r)
           for name, r in zip(names, rs)}
    inc_red = {name: int(r.state.inc_reduced)
               for name, r in zip(names, rs) if "/inc" in name}

    def ratio(prof):
        off = cts[f"{prof}/all_reduce/tree"]
        on = cts[f"{prof}/all_reduce/tree/inc"]
        return round(on / off, 4) if off > 0 and on > 0 else None

    return {
        "scenarios": len(names),
        "flows_padded": int(wls.src.shape[1]),
        "distinct_profiles": len(set(profiles)),
        "ticks": ticks,
        "horizons": sorted({int(r.horizon) for r in rs}),
        "sweep_cold_s": cold,
        "sweep_warm_s": warm,
        "scenarios_per_sec": len(names) / warm,
        "completion_ticks": cts,
        "inc_reduced_pkts": inc_red,
        "inc_tree_allreduce_ratio": ratio("ai_full"),
        "inc_tree_allreduce_ratio_ai_base": ratio("ai_base"),
    }


def _fault_sweep(ticks: int = 4000) -> dict:
    """The dynamic-fault grid (workloads.fault_sweep: flaps, gray links,
    a mid-run permanent death) as ONE ``simulate_batch`` call with the
    per-scenario FaultSchedule riding the scenario axis, plus the
    closed-recovery-loop separation experiment.

    In-bench realism gates (a fault bench whose faults change nothing is
    measuring nothing):

    * every scenario keeps >= 1 healthy uplink, so every flow must
      complete within the budget (the liveness invariant);
    * fault scenarios must actually degrade (timeouts fire, completion
      later than healthy);
    * under a permanent mid-run failure pinned to a static path,
      ``ev_eviction=True`` must complete while eviction-off must be
      slower or stuck (the recovery loop separates).
    """
    from dataclasses import replace as _replace

    from repro.core.lb.schemes import LBScheme
    from repro.network import workloads
    from repro.network.fabric import SimParams, Workload, simulate, \
        simulate_batch
    from repro.network.faults import FaultSchedule
    from repro.network.profile import TransportProfile
    from repro.network.topology import leaf_spine

    g, wls, faults, exp = workloads.fault_sweep()
    prof = TransportProfile.ai_full(lb=LBScheme.REPS)
    p = SimParams(ticks=ticks, timeout_ticks=64, ooo_threshold=24)
    run = lambda: simulate_batch(g, wls, prof, p, faults=faults)  # noqa: E731
    t0 = time.perf_counter()
    rs = run()
    cold = time.perf_counter() - t0
    warm = min(_timed(run) for _ in range(2))
    names = exp["names"]
    cts = {n: int(r.completion_tick()) for n, r in zip(names, rs)}
    # liveness: >= 1 healthy uplink everywhere -> everything completes
    assert all(ct > 0 for ct in cts.values()), cts
    # the faults must bite: timeouts fire, completion degrades
    assert rs[1].timeouts > 0 and cts["flap_1"] > cts["healthy"], cts

    # recovery-loop separation: permanent mid-run death of a STATIC
    # path; eviction-on must migrate off it and beat eviction-off
    g2 = leaf_spine(leaves=2, spines=4, hosts_per_leaf=4)
    wl2 = Workload.of([0, 1, 2, 3], [4, 5, 6, 7], 150)
    dead = FaultSchedule.healthy(g2.num_queues).flap(
        int(g2.up1_table[0, 0]), 100)
    off = TransportProfile.ai_full(lb=LBScheme.STATIC, name="static")
    on = _replace(off, ev_eviction=True, rto_backoff=2.0,
                  name="static_evict")
    p2 = SimParams(ticks=ticks, timeout_ticks=64)
    r_off = simulate(g2, wl2, off, p2, faults=dead)
    r_on = simulate(g2, wl2, on, p2, faults=dead)
    ct_on, ct_off = r_on.completion_tick(), r_off.completion_tick()
    assert ct_on > 0, "eviction must migrate flows off the dead path"
    assert r_on.ev_evictions > 0
    assert ct_off == -1 or ct_on < ct_off, (ct_on, ct_off)

    return {
        "scenarios": len(names),
        "ticks": ticks,
        "sweep_cold_s": cold,
        "sweep_warm_s": warm,
        "scenarios_per_sec": len(names) / warm,
        "completion_ticks": cts,
        "timeouts": {n: int(r.timeouts) for n, r in zip(names, rs)},
        "rtx_packets": {n: int(r.rtx_packets) for n, r in zip(names, rs)},
        "ticks_degraded": {n: int(r.ticks_degraded)
                           for n, r in zip(names, rs)},
        "eviction_separation": {
            "completion_evict_on": ct_on,
            "completion_evict_off": ct_off,
            "ev_evictions": int(r_on.ev_evictions),
        },
    }


def _resilience_sweep() -> dict:
    """Endpoint-failure resilience: the host-fault grid plus the priced
    checkpoint-restart recovery loop.

    In-bench teardown gates (a resilience bench whose dead host changes
    nothing is measuring nothing):

    * the dead-host lane must quiesce EARLY (horizon < budget) with
      exactly its victim flows abandoned and its survivors complete —
      PDC liveness teardown turns a permanent endpoint death from a
      budget burn into an early exit;
    * the pdc-off twin of the SAME scenario must burn the full budget
      with nothing abandoned (the separation the feature buys);
    * the healing NIC stall must complete with nothing abandoned (a
      wedged-but-ACK-live endpoint is not dead);
    * the healthy lane abandons nothing.

    Economics gates (guaranteed by the closed forms, asserted against
    the MEASURED recovery costs): the Young/Daly interval beats naive
    fixed checkpoint intervals at every MTBF, and availability at the
    per-MTBF optimum is monotone non-decreasing in MTBF.
    """
    from repro import configs
    from repro.ckpt.checkpointing import (availability, effective_rate,
                                          young_daly_interval)
    from repro.distributed.plan import derive_plan
    from repro.network import workloads
    from repro.network.fabric import SimParams, simulate_batch
    from repro.network.traffic import checkpoint_seconds, price_recovery

    # --- the endpoint-fault grid: one batched call, host lanes riding ---
    g, wls, scheds, exp = workloads.host_fault_sweep()
    budget = exp["budget"]
    p = SimParams(ticks=budget, timeout_ticks=64)
    run = lambda: simulate_batch(g, wls, exp["profile"], p,  # noqa: E731
                                 faults=scheds)
    t0 = time.perf_counter()
    rs = run()
    cold = time.perf_counter() - t0
    warm = min(_timed(run) for _ in range(2))
    by = dict(zip(exp["names"], rs))

    dead, off = by["host_dead"], by["host_dead_pdc_off"]
    assert dead.flows_abandoned == len(exp["dead_flows"]), \
        int(dead.flows_abandoned)
    assert dead.horizon < budget, (dead.horizon, budget)
    assert int(dead.abandon_tick) > 0 and dead.ticks_unreachable > 0
    cts = dead.completion_ticks()
    assert all(int(cts[i]) == -1 for i in exp["dead_flows"])
    assert all(int(ct) > 0 for i, ct in enumerate(cts)
               if i not in exp["dead_flows"]), cts.tolist()
    assert off.flows_abandoned == 0 and off.horizon == budget, \
        (int(off.flows_abandoned), off.horizon)
    stall = by["nic_stall"]
    assert stall.flows_abandoned == 0 and stall.completion_tick() > 0
    assert by["healthy"].flows_abandoned == 0

    # --- the priced recovery loop: one train plan, one host loss ---
    plan = derive_plan(configs.get("deepseek-coder-33b"), "train_4k",
                       dp=4, tp=4, layout="fsdp_tp")
    t0 = time.perf_counter()
    rc = price_recovery(plan)
    recovery_s = time.perf_counter() - t0
    assert rc.horizon < rc.budget, (rc.horizon, rc.budget)
    write_s = checkpoint_seconds(plan)
    kw = dict(write_s=write_s, detect_s=rc.detect_s,
              restore_s=rc.restore_s, replan_s=rc.replan_s)

    naive = (30.0, 900.0)
    grid = []
    prev_av = 0.0
    for mtbf in (1800.0, 3600.0, 7200.0, 14400.0):
        tau = young_daly_interval(mtbf, write_s)
        av = availability(tau, mtbf, **kw)
        eff = effective_rate(rc.healthy_tokens_per_sec, tau, mtbf, **kw)
        for iv in naive:
            eff_iv = effective_rate(rc.healthy_tokens_per_sec, iv, mtbf,
                                    **kw)
            assert eff > eff_iv, (mtbf, iv, eff, eff_iv)
        assert av >= prev_av, (mtbf, av, prev_av)
        prev_av = av
        grid.append({
            "mtbf_s": mtbf,
            "daly_interval_s": round(tau, 2),
            "availability": round(av, 5),
            "effective_tokens_per_sec": round(eff, 1),
            "naive_effective_tokens_per_sec": {
                str(int(iv)): round(effective_rate(
                    rc.healthy_tokens_per_sec, iv, mtbf, **kw), 1)
                for iv in naive},
        })

    return {
        "scenarios": len(exp["names"]),
        "budget": budget,
        "sweep_cold_s": cold,
        "sweep_warm_s": warm,
        "scenarios_per_sec": len(exp["names"]) / warm,
        "abandon_tick": int(dead.abandon_tick),
        "horizon_pdc_on": int(dead.horizon),
        "horizon_pdc_off": int(off.horizon),
        "ticks_unreachable": int(dead.ticks_unreachable),
        "recovery": {
            "plan": f"{plan.arch} x {plan.shape} dp={plan.dp} tp={plan.tp}",
            "wall_s": recovery_s,
            "detect_ticks": rc.detect_ticks,
            "detect_s": rc.detect_s,
            "restore_s": rc.restore_s,
            "replan_s": rc.replan_s,
            "flows_abandoned": rc.flows_abandoned,
            "healthy_tokens_per_sec": rc.healthy_tokens_per_sec,
            "degraded_tokens_per_sec": rc.degraded_tokens_per_sec,
        },
        "checkpoint_write_s": write_s,
        "availability_grid": grid,
        # headline: availability at the 1h-MTBF Young/Daly optimum
        "availability_mtbf_3600": grid[1]["availability"],
    }


def _fabric_health(ticks: int = 3000) -> dict:
    """The telemetry plane on the PR-6-style flap scenario: the shared
    victim-share fabric (``workloads.victim_sweep``) with 3 of 4 leaf-0
    uplinks flapping over [1000, 1800), probes on.

    In-bench visibility gates (an observability plane that can't see an
    outage is measuring nothing) — the four-signature check shared with
    the ``python -m repro.network.telemetry`` canary:

    * silent-drop rate is confined to [fail_at, heal_at) bit-exactly
      (zero before and after, spiking inside);
    * goodput dips inside the window and climbs back after;
    * the CC response registers: NSCC backs off on the vanishing ACK
      stream, so the in-window ECN-mark rate falls below baseline
      (the naive "trims spike in-window" expectation is exactly what a
      real closed-loop transport does NOT do — the trim spike lands at
      the heal boundary, when the retransmit backlog floods back);
    * probes never perturb: the telemetry-on final state is bitwise the
      telemetry-off state.

    Also prices the plane itself: warm telemetry-on vs telemetry-off
    wall time on the same scenario (``telemetry_overhead`` ratio).
    """
    from dataclasses import replace as _replace

    import jax

    from repro.network.fabric import simulate
    from repro.network.telemetry import (assert_outage_visible,
                                         flap_victim_scenario,
                                         outage_visibility)

    g, wl, prof, p, sched, spec, (fail_at, heal_at) = flap_victim_scenario()
    p = _replace(p, ticks=ticks)
    run_on = lambda: simulate(g, wl, prof, p, faults=sched,  # noqa: E731
                              telemetry=spec)
    run_off = lambda: simulate(g, wl, prof, p, faults=sched)  # noqa: E731
    t0 = time.perf_counter()
    r_on = run_on()
    cold = time.perf_counter() - t0
    r_off = run_off()
    warm_on = min(_timed(run_on) for _ in range(3))
    warm_off = min(_timed(run_off) for _ in range(3))

    eq = jax.tree_util.tree_map(
        lambda a, c: bool(np.array_equal(np.asarray(a), np.asarray(c))),
        r_on.state, r_off.state)
    assert all(jax.tree_util.tree_leaves(eq)), \
        "telemetry must not perturb the simulation"
    tr = r_on.telemetry
    vis = outage_visibility(tr, fail_at, heal_at, ticks)
    assert_outage_visible(vis)

    s = tr.summary()
    rnd = lambda x: round(float(x), 4)  # noqa: E731
    return {
        "ticks": ticks,
        "fault_window": [fail_at, heal_at],
        "probe_every": spec.probe_every,
        "slots": spec.slots,
        "samples": tr.num_samples,
        "sample_spacing_ticks": tr.sample_spacing,
        "telemetry_cold_s": cold,
        "telemetry_on_warm_s": warm_on,
        "telemetry_off_warm_s": warm_off,
        "telemetry_overhead": warm_on / warm_off,
        "drop_rate": [rnd(vis["drop_pre"]), rnd(vis["drop_during"]),
                      rnd(vis["drop_post"])],
        "mark_rate_pre_during": [rnd(vis["mark_pre"]),
                                 rnd(vis["mark_during"])],
        "goodput_pre_during_post": [rnd(vis["goodput_pre"]),
                                    rnd(vis["goodput_during"]),
                                    rnd(vis["goodput_post"])],
        "heal_trim_burst": rnd(vis["trim_burst"]),
        "occ_p99": rnd(s["occ_p99"]),
        "rtt_p99": rnd(s.get("rtt_p99", 0.0)),
    }


def _corruption_sweep() -> dict:
    """Link-layer reliability on a BER-y fabric: the shared
    ``workloads.corruption_sweep`` BER grid run through BOTH arms of the
    LLR-on/off axis (``link=`` is a compile-time static, so the axis is
    two ``simulate_batch`` calls over the same batch), plus the
    LLR+CBFC lossless arm and the PFC-vs-CBFC buffer bill.

    In-bench recovery gates (a reliability layer that doesn't beat the
    recovery path it replaces is measuring nothing):

    * at EVERY nonzero BER, hop-local LLR replay beats end-to-end RTO
      recovery on tail completion AND per-flow goodput — and confines
      the loss: zero end-to-end drops, nonzero replays, all flows
      complete;
    * at BER=0 the LLR-armed run is bitwise the plain run on every
      pre-feature lane (the `lossy`-idiom inertness contract), and
      congestion trims are NOT masked: the clean lane trims end-to-end
      identically under both arms (LLR protects against PHY corruption
      only — trims still NACK end-to-end);
    * the CBFC arm is lossless on the clean congested lane: credit
      exhaustion back-pressures (``credit_stall_ticks > 0``) instead of
      trimming (``trims == 0``), and everything still completes;
    * the Sec. 3.5.2 buffer bill: CBFC's credited buffer undercuts
      PFC's per-(port, priority) headroom by > 2x on this topology.
    """
    from repro.core.link import (fabric_buffer_pricing, state_bitwise_equal)
    from repro.network import workloads
    from repro.network.fabric import simulate_batch

    g, wls, scheds, exp = workloads.corruption_sweep()
    prof, p, budget = exp["profile"], exp["params"], exp["budget"]
    bers, names = exp["bers"], exp["names"]
    run_on = lambda: simulate_batch(g, wls, prof, p, faults=scheds,  # noqa: E731
                                    link=exp["link"])
    run_off = lambda: simulate_batch(g, wls, prof, p, faults=scheds)  # noqa: E731
    t0 = time.perf_counter()
    on = run_on()
    cold = time.perf_counter() - t0
    off = run_off()
    cb = simulate_batch(g, wls, prof, p, faults=scheds, link=exp["cbfc"])
    warm_on = min(_timed(run_on) for _ in range(2))
    warm_off = min(_timed(run_off) for _ in range(2))

    def tail(r):
        ct = r.completion_tick()
        return ct if ct > 0 else budget

    def scenario_goodput(r):
        # delivered packets over the makespan (time for EVERY flow to
        # finish, budget if some never did) — the collective-completion
        # goodput an app sees. Per-flow mean would reward e2e's failure
        # mode (a silent drop hurts one flow; an LLR replay holds the
        # whole queue briefly), but the app waits for the tail.
        return float(np.sum(np.asarray(r.state.delivered))) / tail(r)

    grid = []
    for i, (name, ber) in enumerate(zip(names, bers)):
        t_on, t_off = tail(on[i]), tail(off[i])
        gp_on, gp_off = scenario_goodput(on[i]), scenario_goodput(off[i])
        if ber > 0:
            assert int(on[i].drops) == 0, (name, int(on[i].drops))
            assert on[i].llr_replays > 0, name
            assert on[i].completion_tick() > 0, name
            assert int(off[i].drops) > 0, (name, "BER lane must corrupt")
            assert t_on < t_off, (name, t_on, t_off)
            assert gp_on > gp_off, (name, gp_on, gp_off)
        grid.append({
            "name": name, "ber": ber,
            "completion_llr": int(on[i].completion_tick()),
            "completion_e2e": int(off[i].completion_tick()),
            "llr_replays": on[i].llr_replays,
            "e2e_drops": int(off[i].drops),
            "e2e_timeouts": int(off[i].timeouts),
            "goodput_llr": round(gp_on, 5),
            "goodput_e2e": round(gp_off, 5),
        })

    # clean-lane gates: bitwise inertness + trims not masked
    drift = state_bitwise_equal(on[0].state, off[0].state)
    assert drift is None, f"clean-link LLR run drifted: {drift}"
    assert int(on[0].trims) == int(off[0].trims) > 0, \
        (int(on[0].trims), int(off[0].trims))

    # CBFC losslessness on the clean congested lane
    assert int(cb[0].trims) == 0, int(cb[0].trims)
    assert cb[0].credit_stall_ticks > 0
    assert all(r.completion_tick() > 0 for r in cb)

    pricing = fabric_buffer_pricing(g.num_queues)
    assert pricing["cbfc_total_bytes"] < pricing["pfc_total_bytes"] / 2

    worst = grid[-1]
    return {
        "scenarios": len(names),
        "bers": list(bers),
        "budget": budget,
        "sweep_cold_s": cold,
        "sweep_warm_s": warm_on,
        "sweep_warm_off_s": warm_off,
        "scenarios_per_sec": len(names) / warm_on,
        "llr_overhead_warm": warm_on / warm_off,
        "grid": grid,
        # headline: e2e-recovery tail over LLR tail at the worst BER
        "llr_vs_e2e_recovery": round(
            tail(off[-1]) / tail(on[-1]), 3),
        "worst_ber_completion": [worst["completion_llr"],
                                 worst["completion_e2e"]],
        "cbfc_trims_clean": int(cb[0].trims),
        "cbfc_stall_ticks_clean": cb[0].credit_stall_ticks,
        "cbfc_over_pfc_buffer": round(pricing["cbfc_over_pfc"], 3),
    }


def _model_sweep() -> dict:
    """The model-driven co-design grid: 2 models x 2 sharding layouts x
    2 topologies x 3 transport profiles at decode, every operating
    point's collective schedule derived from the real sharding rules
    and priced end-to-end from ONE ``simulate_batch`` call (scenarios
    carry per-scenario graphs AND profiles; the engine groups them into
    one executable per (topology, profile) pair).

    In-bench separation gates (a co-design sweep whose axes don't move
    the step time is measuring nothing):

    * layout: at decode the fsdp_tp layout pays the ZeRO-3 param-gather
      penalty — strictly slower than the tp_only serving layout at
      EVERY (model, topology, profile) point;
    * profile: on the oversubscribed fabric under fsdp_tp, the hpc
      composition (packet-spray + in-order ROD delivery) prices the DP
      gather stream strictly slower than the ai composition (RUD) —
      the documented transport-driven step-time separation;
    * topology: 2:1 oversubscription can only slow an fsdp_tp point
      down (DP traffic crosses the spine; TP stays intra-leaf).
    """
    from repro.network import traffic

    t0 = time.perf_counter()
    pts = traffic.run_model_sweep()
    elapsed = time.perf_counter() - t0

    by = {(p["arch"], p["layout"], p["topology"], p["profile"]): p
          for p in pts}
    archs = sorted({p["arch"] for p in pts})
    seps = {}
    for a in archs:
        for topo in ("full", "oversub2"):
            for prof in ("ai_base", "ai_full", "hpc"):
                assert (by[(a, "fsdp_tp", topo, prof)]["step_s"]
                        > by[(a, "tp_only", topo, prof)]["step_s"]), \
                    (a, topo, prof)
        hpc = by[(a, "fsdp_tp", "oversub2", "hpc")]["step_s"]
        ai = by[(a, "fsdp_tp", "oversub2", "ai_full")]["step_s"]
        assert hpc > 1.05 * ai, (a, hpc, ai)
        full = by[(a, "fsdp_tp", "full", "ai_full")]["step_s"]
        over = by[(a, "fsdp_tp", "oversub2", "ai_full")]["step_s"]
        assert over >= full, (a, over, full)
        seps[a] = {
            "layout_tp_only_speedup": round(
                by[(a, "fsdp_tp", "oversub2", "ai_full")]["step_s"]
                / by[(a, "tp_only", "oversub2", "ai_full")]["step_s"], 2),
            "profile_hpc_over_ai_oversub2": round(hpc / ai, 3),
            "topology_oversub2_over_full": round(over / full, 3),
        }

    return {
        "scenarios": len(pts),
        "shape": "decode_32k",
        "dp": 16, "tp": 16,
        "sweep_s": elapsed,
        "scenarios_per_sec": len(pts) / elapsed,
        "separations": seps,
        "points": pts,
    }


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenarios", type=int, default=8)
    ap.add_argument("--ticks", type=int, default=600)
    ap.add_argument("--devices", type=int, default=4,
                    help="virtual CPU devices, forced before JAX starts "
                         "(the sharded sweep needs >= 2)")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "BENCH_fabric.json"))
    args = ap.parse_args()

    _force_host_devices(args.devices)
    from repro.compile_cache import enable_persistent_cache
    enable_persistent_cache()
    results = run_benches(args.scenarios, args.ticks)
    results["timestamp"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    out = os.path.abspath(args.out)
    with open(out, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
        f.write("\n")

    print(json.dumps(results, indent=2, sort_keys=True))
    cs = results["collective_sweep"]
    fs = results["fault_sweep"]
    rz = results["resilience_sweep"]
    fh = results["fabric_health"]
    cr = results["corruption_sweep"]
    ms = results["model_sweep"]
    sh = results["sharded_sweep"]
    sh_line = (f"sharded sweep {sh['shard_speedup']:.2f}x on "
               f"{sh['devices']} devices "
               f"({sh['scenarios_per_sec_sharded']:.1f} scen/s)")
    print(f"\nbatched sweep (cold, incl. compile) is "
          f"{results['batch_speedup_vs_serial']:.1f}x the seed-style serial "
          f"sweep; warm-vs-warm against the shared-executable serial loop it "
          f"is {results['batch_speedup_vs_serial_shared_warm']:.2f}x; "
          f"chunked driver vs fixed scan "
          f"{results['fastpath_vs_fixed_scan']:.2f}x "
          f"(aligned-chunk fast path "
          f"{results['ticks_per_sec_batched_fastpath']:.0f} ticks/s); "
          f"{sh_line}; "
          f"collective grid ran {cs['scenarios']} scenarios at "
          f"{cs['scenarios_per_sec']:.2f}/s, INC tree-all-reduce completion "
          f"ratio {cs['inc_tree_allreduce_ratio']}; fault grid "
          f"{fs['scenarios']} scenarios at {fs['scenarios_per_sec']:.2f}/s, "
          f"eviction separation "
          f"{fs['eviction_separation']['completion_evict_on']} vs "
          f"{fs['eviction_separation']['completion_evict_off']}; "
          f"resilience grid {rz['scenarios']} scenarios at "
          f"{rz['scenarios_per_sec']:.2f}/s, dead host detected at tick "
          f"{rz['abandon_tick']} and quiesced at {rz['horizon_pdc_on']} vs "
          f"pdc-off stuck at {rz['horizon_pdc_off']}, 1h-MTBF Young/Daly "
          f"availability {rz['availability_mtbf_3600']:.4f}; "
          f"model sweep {ms['scenarios']} operating points at "
          f"{ms['scenarios_per_sec']:.2f}/s, separations {ms['separations']}; "
          f"fabric health: outage visible (drops "
          f"{fh['drop_rate'][0]} -> {fh['drop_rate'][1]} -> "
          f"{fh['drop_rate'][2]}/tick, heal trim burst "
          f"{fh['heal_trim_burst']}/tick) at "
          f"{fh['telemetry_overhead']:.2f}x telemetry overhead; "
          f"corruption grid {cr['scenarios']} BER points at "
          f"{cr['scenarios_per_sec']:.2f}/s, worst-BER completion LLR "
          f"{cr['worst_ber_completion'][0]} vs e2e "
          f"{cr['worst_ber_completion'][1]} "
          f"({cr['llr_vs_e2e_recovery']:.2f}x recovery win), CBFC buffer "
          f"{cr['cbfc_over_pfc_buffer']:.2f}x of PFC; "
          f"wrote {out}")


if __name__ == "__main__":
    main()
