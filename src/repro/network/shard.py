"""Device-sharded scenario engine: ``simulate_batch(..., shard=True)``.

Shards the scenario axis of a batched run across devices with
``shard_map``: every device runs the SAME hand-batched chunked
while-scan driver (``fabric._build_fns``) on its contiguous slice of
the scenario axis, entirely communication-free. Consequences:

* per-lane trajectories are bitwise identical to the unsharded path
  (and therefore to serial ``simulate`` — lanes never interact);
* each device's while loop exits at ITS lanes' quiescence boundary,
  not the global batch's. The unsharded engine pays the max-lane
  horizon for every lane (frozen lanes still ride the scan), so on a
  heterogeneous sweep sorted by expected horizon, sharding is a
  work-efficiency win on top of the device parallelism;
* ragged scenario counts are padded to a device multiple with inert
  no-op lanes (``workloads.pad_scenarios``) that quiesce at the first
  chunk boundary; the padding is dropped from the gathered results;
* per-profile executable groups compose: ``simulate_batch`` groups by
  profile first, then shards within each group.

Sharded executables live in the same compile cache as the unsharded
ones, keyed additionally on the device-id tuple. The carry is donated
per device shard, and budgets stay traced bounds.

CPU testing: export ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
*before the first jax import* to split the host into N virtual devices
(`scripts/check.sh` runs the 4-device smoke this way; `python -m
repro.network.shard` is that smoke).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.network import fabric

_AXIS = "scenarios"


def resolve_devices(devices, shard: bool):
    """Normalize the ``simulate_batch`` (devices=, shard=) pair to a
    device tuple, or None for the unsharded path (0 or 1 device)."""
    if isinstance(devices, bool):       # devices=True sugar for shard=True
        devices, shard = None, devices or shard
    if devices is None:
        if not shard:
            return None
        devs = tuple(jax.devices())
    elif isinstance(devices, int):
        if devices <= 1:            # 0/1 = sharding disabled
            return None
        avail = jax.devices()
        if devices > len(avail):
            raise ValueError(
                f"devices={devices} requested but {len(avail)} present "
                f"(CPU: set XLA_FLAGS=--xla_force_host_platform_"
                f"device_count=N before the first jax import)")
        devs = tuple(avail[:devices])
    else:
        devs = tuple(devices)
    return devs if len(devs) > 1 else None


def _sharded_fns(g, profile, p, F: int, trace: str, devs: tuple,
                 lossy: bool = False, tel=None, hosty: bool = False,
                 corrupty: bool = False, link=None):
    """Jitted + cached (init, run) pair whose scenario axis is sharded
    over `devs`. Same driver as the unsharded batched engine, wrapped in
    shard_map before jit; cached beside it under the device-id tuple.
    Telemetry lanes (``tel``: a TelemetrySpec) ride inside the stats
    carry, so the partition specs are untouched — every probe-ring leaf
    is sharded on its leading scenario axis like the other stat lanes."""
    key = fabric._cache_key(g, profile, p, F, True, trace,
                            shard=tuple(d.id for d in devs), lossy=lossy,
                            tel=tel, hosty=hosty, corrupty=corrupty,
                            link=link)
    fns = fabric._RUN_CACHE.get(key)
    if fns is None:
        init_fn, run = fabric._build_fns(g, profile, p, F, batched=True,
                                         trace=trace, lossy=lossy, tel=tel,
                                         hosty=hosty, corrupty=corrupty,
                                         link=link)
        mesh = Mesh(np.array(devs), (_AXIS,))
        sc, rep = P(_AXIS), P()
        if trace == "stats":
            # (s0, wl, fault, budget, w0, w1) -> (state, stats, horizon)
            in_specs = (sc, sc, sc, rep, rep, rep)
            out_specs = (sc, sc, sc)
        else:
            # (s0, stopped, tick0, wl, fault, budget)
            #   -> (state, stopped, time-major out lanes [T, B, ...])
            in_specs = (sc, sc, rep, sc, sc, rep)
            out_specs = (sc, sc, P(None, _AXIS))
        run_sh = jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)
        init_sh = jax.shard_map(init_fn, mesh=mesh, in_specs=(sc, sc),
                                out_specs=sc, check_vma=False)
        fns = (jax.jit(init_sh), jax.jit(run_sh, donate_argnums=(0,)))
        fabric._RUN_CACHE[key] = fns
    return fns


def run_sharded(g, wls, profile, p, fault, seeds, trace: str, budget: int,
                goodput_window, devs: tuple, tel=None,
                link=None) -> "list[fabric.SimResult]":
    """One profile group's batch, sharded over `devs`. Called by
    ``fabric._run_batch`` — same inputs/outputs, bitwise-identical
    per-scenario results. ``fault`` is a [B, Q]-leaved FaultSchedule;
    padding lanes get all-healthy schedules (inert, like their no-op
    workloads)."""
    from repro.network.faults import FaultSchedule
    from repro.network.workloads import pad_scenarios

    with fabric._Spans(jax.profiler.TraceAnnotation) as span:
        span("fabric.prepare")
        n = len(devs)
        B, F = wls.src.shape
        profile.delivery_modes(F)
        init, run = fabric.driver_fns(g, profile, p, F, fault, trace,
                                      batched=True, tel=tel, link=link,
                                      devs=devs)
        wls_p, pad = pad_scenarios(wls, n)
        if pad:
            # padding lanes get all-healthy schedules at the batch's own
            # host-lane width (zero-width when no endpoint faults ride)
            fault = jax.tree_util.tree_map(
                lambda a, e: jnp.concatenate([a, e.astype(a.dtype)]),
                fault, FaultSchedule.healthy(g.num_queues, batch=pad,
                                             num_hosts=fault.num_hosts))
            seeds = jnp.concatenate(
                [seeds, jnp.full((pad,), fabric.DEFAULT_SEED, jnp.uint32)])
        sizes = np.asarray(wls.size)
        span("fabric.init")
        s0 = init(wls_p, seeds)
        if trace == "stats":
            w0, w1 = fabric._window_bounds(goodput_window, budget)
            span("fabric.run")
            final, st, horizon = run(s0, wls_p, fault, jnp.int32(budget),
                                     jnp.int32(w0), jnp.int32(w1))
            span("fabric.fetch")
            final = jax.device_get(final)
            st = jax.device_get(st)
            horizon = np.asarray(horizon)
            span("fabric.split")
            return fabric._split_stats_results(final, st, sizes, horizon,
                                               budget, goodput_window, B,
                                               tel=tel)
        span("fabric.run")
        final, outs, horizon = fabric._run_full_host(
            run, s0, wls_p, fault, budget, p.chunk_ticks, batch=B + pad)
        span("fabric.fetch")
        final = jax.device_get(final)
        span("fabric.split")
        return fabric._split_full_results(final, outs, sizes, horizon,
                                          budget, B)


def _smoke() -> int:  # pragma: no cover — CLI smoke for scripts/check.sh
    """Ragged sharded batch vs the unsharded engine: bitwise parity of
    completion ticks, horizons, and the full final state."""
    from repro.network.profile import TransportProfile
    from repro.network.topology import leaf_spine

    ndev = len(jax.devices())
    if ndev < 2:
        print("shard smoke: only 1 device visible — set XLA_FLAGS="
              "--xla_force_host_platform_device_count=4; skipping")
        return 0
    g = leaf_spine(leaves=2, spines=2, hosts_per_leaf=4)
    sizes = [40, 80, 120, 160, 200, 240]      # B=6: ragged on 4 devices
    wls = fabric.Workload.stack(
        [fabric.Workload.of([0, 1, 2, 3], [4, 5, 6, 7], s) for s in sizes])
    p = fabric.SimParams(ticks=2000)
    prof = TransportProfile.ai_full()
    base = fabric.simulate_batch(g, wls, prof, p)
    shd = fabric.simulate_batch(g, wls, prof, p, shard=True)
    for i, (rb, rs) in enumerate(zip(base, shd)):
        assert rb.horizon == rs.horizon, (i, rb.horizon, rs.horizon)
        np.testing.assert_array_equal(rb.completion_ticks(),
                                      rs.completion_ticks(),
                                      err_msg=f"scenario {i}")
        eq = jax.tree_util.tree_map(
            lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
            rb.state, rs.state)
        assert all(jax.tree_util.tree_leaves(eq)), f"scenario {i} state"
    print(f"shard smoke ok: {ndev} devices, B={len(sizes)} (ragged), "
          f"bitwise parity with the unsharded engine")
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(_smoke())
