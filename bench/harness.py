"""One run of one benchmark cell: set-up, a timed window, the check.

A cell (a ``workloads`` entry of ``BENCHMARK.json``) names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); its per-layer metrics are readers
``bench/metrics/<metric>.py``. Everything is found by name, so a new
cell, mix or metric is new files plus a ``BENCHMARK.json`` entry.

Set-up (``setup_s``, from process start): imports, the program's graph
and workload, the persistent compile cache, and one warm-up
``simulate_batch`` call of the cell's exact batch with a budget of one
chunk (the budget is traced, so that executable serves the window).

Window: whole ``simulate_batch`` calls back to back, each of the traffic
mix's scenarios in an order ``--seed`` draws (``bench.sweep``); no call
starts once ``--seconds`` have passed, and every started call counts.
Compiles inside the window are counted: a run with one is not
``correct``.

Then the check (``bench.check``) on the window's own results. With
``--trace 1`` the window is its first call, whole, under the JAX
profiler, and the cell's per-layer metrics are read from the trace and
the call.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib
import json
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import check, sweep
from bench.metrics.common import busy_ns, window_ns
from bench.reference import Model, run_reference

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
WARM_CALL = 0xFFFFF                  # call index of the warm-up's lanes
#: a traced run's window: its first call, whole (the trace holds every
#: leaf op of every tick, so a whole window of calls would be too large)
TRACED_CALLS = 1
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """JAX found no accelerator the benchmark can measure."""


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    @property
    def budget(self) -> int:
        return int(self.cfg["params"]["ticks"])


def load_cell(name: str, spec: "dict | None" = None) -> Cell:
    """The cell `name` of ``BENCHMARK.json`` (or of `spec`), with its
    configuration, traffic and the metrics it reports."""
    if spec is None:
        spec = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    cfg = json.loads((CHECKOUT / cfg_entry["file"]).read_text())

    def applies(m):
        return name in m.get("workloads", [name])

    return Cell(name, int(wl["chips"]), cfg,
                sweep.load_json("traffic", wl["traffic"]),
                [m for m in spec["end_to_end"] if applies(m)],
                [m for m in spec["per_layer"] if applies(m)])


@contextlib.contextmanager
def compile_spans():
    """Spans of tracing, lowering and compiling inside the block."""
    import jax

    spans = []

    def on_span(event, start, end, **_):
        if event in _COMPILE_EVENTS:
            spans.append((event, start, end))

    jax.monitoring.register_event_time_span_listener(on_span)
    try:
        yield spans
    finally:
        jax.monitoring.unregister_event_time_span_listener(on_span)


def span_seconds(spans) -> float:
    total, reach = 0.0, float("-inf")
    for _, start, end in sorted(spans, key=lambda s: s[1]):
        total += max(0.0, end - max(start, reach))
        reach = max(reach, end)
    return total


class Program:
    """The system under test: ``simulate_batch`` on the cell's fabric,
    transport and collective, as a user calls it."""

    def __init__(self, cell: Cell, devices=None):
        from repro.core.cms.nscc import NSCCParams
        from repro.network import fabric, topology
        from repro.network.faults import FaultSchedule
        from repro.network.profile import TransportProfile

        cfg = cell.cfg
        topo, coll, tr = cfg["topology"], cfg["collective"], cfg["transport"]
        self.fabric, self.FaultSchedule = fabric, FaultSchedule
        self.g = getattr(topology, topo["family"])(topo["k"], topo["pods"])
        self.profile = getattr(TransportProfile, tr["profile"])()
        got = {"cc": self.profile.cc.name.lower(),
               "lb": self.profile.lb.name.lower(),
               "delivery": getattr(self.profile.delivery, "name",
                                   "mixed").lower(),
               "inc": bool(self.profile.inc)}
        if got != {k: tr[k] for k in got}:
            raise ValueError(f"profile {tr['profile']!r} is {got}, the "
                             f"configuration states {tr}")
        self.params = fabric.SimParams(**cfg["params"])
        # the program takes its NSCC gains from NSCCParams' defaults (only
        # base_rtt and max_cwnd come from the parameters above)
        gains = {k: getattr(NSCCParams, k) for k in cfg["nscc"]}
        if gains != cfg["nscc"]:
            raise ValueError(f"the program runs NSCC gains {gains}, the "
                             f"configuration states {cfg['nscc']}")
        # the collective's flows as a user hands them to the program;
        # tests/bench shows they equal the program's own build_workload
        ft = sweep.flow_table(coll)
        wl = fabric.Workload.of(ft["src"], ft["dst"], ft["size"],
                                dep=ft["dep"])
        self.batch = int(cell.traffic["batch"])
        self.wls = fabric.Workload.stack([wl] * self.batch)
        self.devices = devices

    def faults(self, lanes: "list[dict]"):
        healthy = self.FaultSchedule.healthy(self.g.num_queues,
                                             batch=len(lanes))
        return dataclasses.replace(
            healthy,
            fail_at=np.stack([ln["fail_at"] for ln in lanes]),
            heal_at=np.stack([ln["heal_at"] for ln in lanes]),
            loss_p=np.stack([ln["loss_p"] for ln in lanes]),
            seed=np.asarray([ln["seed"] for ln in lanes], np.uint32))

    def call(self, lanes: "list[dict]", budget: int) -> list:
        """One ``simulate_batch`` call of the batch: each lane's seed
        drives its spraying and its gray-link loss draws."""
        return self.fabric.simulate_batch(
            self.g, self.wls, self.profile, self.params,
            faults=self.faults(lanes),
            seeds=np.asarray([ln["seed"] for ln in lanes], np.uint32),
            max_ticks=budget, devices=self.devices)


@dataclasses.dataclass
class Call:
    start: float
    end: float
    lanes: list
    results: list

    @property
    def horizons(self) -> np.ndarray:
        return np.asarray([r.horizon for r in self.results], np.int64)


def run_window(program: Program, cell: Cell, seed: int, seconds: float,
               max_calls: "int | None" = None) -> "list[Call]":
    import jax

    calls = []
    t0 = time.perf_counter()
    while not calls or (time.perf_counter() - t0 < seconds
                        and len(calls) != max_calls):
        c = len(calls)
        lanes = sweep.call_lanes(cell.cfg, cell.traffic, seed, c)
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.call.{c}"):
            results = program.call(lanes, cell.budget)
        calls.append(Call(start, time.perf_counter(), lanes, results))
    return calls


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def check_calls(cell: Cell, calls: "list[Call]", seed: int) -> dict:
    """The counts that decide ``correct`` (see ``bench.check``)."""
    import jax

    counts = check.guarantee_counts(
        [c.results for c in calls], int(cell.traffic["batch"]), cell.budget,
        sweep.flow_table(cell.cfg["collective"])["dst"],
        sweep.expected_host_rx(cell.cfg))
    picks = check.sample([c.horizons for c in calls],
                         int(cell.traffic["checked_lanes"]), seed)
    t0 = time.perf_counter()
    # on the host's CPU: on a TPU the reference's scatters over its bool
    # planes take about 40 ms a tick, the whole window's length and more
    with jax.default_device(jax.devices("cpu")[0]):
        want = run_reference(Model.from_config(cell.cfg),
                             [calls[c].lanes[i] for c, i in picks],
                             cell.budget)
    mismatched = 0
    for (c, i), ref in zip(picks, want):
        got = calls[c].results[i] if i < len(calls[c].results) else None
        diff = (["missing"] if got is None
                else check.differences(check.program_outcome(got), ref))
        if ref["clash_ticks"]:
            diff.append("clash_ticks")
        mismatched += bool(diff)
        say(f"check: call {c} lane {i} ({calls[c].lanes[i]['schedule']}, "
            f"seed {calls[c].lanes[i]['seed']}, horizon {ref['horizon']}): "
            + ("equal to the reference" if not diff
               else f"differs from the reference in {diff}"))
    say(f"check: reference ran {len(picks)} lanes in "
        f"{time.perf_counter() - t0:.2f} s")
    counts["reference_mismatch_lanes"] = mismatched
    return counts


def trace_dir(cell: Cell, seed: int) -> Path:
    return CHECKOUT / "bench_out" / "trace" / f"{cell.name}.{seed}"


def read_per_layer(cell: Cell, calls: "list[Call]", red: "dict | None",
                   devices, peaks: "dict | None") -> dict:
    """Each per-layer metric of the cell from its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    ctx = {"cell": cell, "calls": calls, "trace": red,
           "devices": len(devices), "peaks": peaks}
    out = {}
    for m in cell.per_layer:
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(red: dict) -> dict:
    """The device's op classes by time (mean over chips), and the longest
    idle gaps: between device ops inside the call, and the call's host
    time outside the device's first-to-last op."""
    from bench import trace_reduce as tr

    n = len(red["devices"])
    classes = tr.by_class([op for d in red["devices"] for op in d["ops"]])
    gaps = []
    for d in red["devices"]:
        busy = tr.union([(s, t) for _, s, t in d["ops"]])
        gaps += [("simulate_batch: device idle between ops", (b - a) / 1e9)
                 for (_, a), (b, _) in zip(busy, busy[1:])]
        gaps.append(("simulate_batch: host outside the device ops",
                     (window_ns(red) - (busy[-1][1] - busy[0][0])) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[k, v / 1e9 / n]
                           for k, v in classes.most_common(10)],
            "idle_gaps": [[k, v] for k, v in gaps[:10]]}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             program_factory=Program) -> dict:
    """One run of `cell`: its result line."""
    import jax

    from repro.compile_cache import enable_persistent_cache

    devices = jax.devices()
    peaks_all = json.loads((BENCH / "peaks.json").read_text())
    kind = devices[0].device_kind
    if require_chip:
        if devices[0].platform != "tpu":
            raise NoChip(f"JAX found platform {devices[0].platform!r} "
                         f"({kind}), not a TPU")
        if kind not in peaks_all:
            raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
        if len(devices) < cell.chips:
            raise NoChip(f"cell {cell.name} needs {cell.chips} chips, JAX "
                         f"found {len(devices)}")
    used = devices[:cell.chips]
    cache = enable_persistent_cache()
    program = program_factory(cell, list(used) if cell.chips > 1 else None)

    with compile_spans() as setup_spans:
        lanes = sweep.call_lanes(cell.cfg, cell.traffic, seed, WARM_CALL)
        program.call(lanes, int(cell.cfg["params"]["chunk_ticks"]))
    setup_s = time.perf_counter() - t_start
    say(f"setup: {setup_s:.3f} s, of it {span_seconds(setup_spans):.3f} s "
        f"tracing and compiling ({len(setup_spans)} events); compile "
        f"cache {cache}")

    red = None
    tdir = trace_dir(cell, seed)
    with compile_spans() as window_spans:
        if trace:
            shutil.rmtree(tdir, ignore_errors=True)
            jax.profiler.start_trace(str(tdir))
        try:
            calls = run_window(program, cell, seed, seconds,
                               max_calls=TRACED_CALLS if trace else None)
        finally:
            if trace:
                jax.profiler.stop_trace()
    compiles = sum(1 for e, _, _ in window_spans
                   if e.endswith("backend_compile_duration"))
    say(f"window: {len(calls)} calls, compiles inside the window: "
        f"{compiles} ({len(window_spans)} trace/lower/compile events)")
    wall = calls[-1].end - calls[0].start
    lane_ticks = int(sum(c.horizons.sum() for c in calls))
    scenarios = sum(len(c.results) for c in calls)
    say(f"window: {wall:.6f} s wall, {lane_ticks} lane-ticks, "
        f"{scenarios} scenarios, executed ticks per call "
        f"{[int(c.horizons.max()) for c in calls]}")
    mem = memory_peak(used)

    if trace:
        from bench import trace_reduce

        path = sorted(glob.glob(str(tdir / "**" / "*.xplane.pb"),
                                recursive=True))[-1]
        t0 = time.perf_counter()
        red = trace_reduce.reduce(path)
        red["devices"] = red["devices"][:len(used)]
        say(f"trace: {os.path.getsize(path)} bytes, reduced in "
            f"{time.perf_counter() - t0:.2f} s")
        metrics = read_per_layer(cell, calls, red, used,
                                 peaks_all.get(kind))
    else:
        values = {"lane_ticks_per_s": lane_ticks / wall,
                  "scenarios_per_s": scenarios / wall, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    counts = check_calls(cell, calls, seed)
    counts["compiles_in_window"] = compiles
    correct = all(counts[k] <= check.LIMITS[k] for k in check.LIMITS)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem}
    line = {"correct": correct,
            "attempted": len(calls) * int(cell.traffic["batch"]),
            "failed": min(scenarios, counts["missing_results"]
                          + counts["unfinished_lanes"]
                          + counts["wrong_payload_lanes"]
                          + counts["reference_mismatch_lanes"]),
            "metrics": metrics, "device": device,
            "setup_compile_s": span_seconds(setup_spans)}
    if red is not None and red["devices"]:
        device["busy_s"] = float(np.mean([busy_ns(d) for d in
                                          red["devices"]])) / 1e9
        device["window_s"] = window_ns(red) / 1e9
        line["breakdown"] = breakdown(red)
    line["checks"] = {k: {"value": counts[k], "limit": check.LIMITS[k]}
                      for k in check.LIMITS}
    for k in check.LIMITS:
        say(f"check {k} = {counts[k]} (limit {check.LIMITS[k]})")
    return line
