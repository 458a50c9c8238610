"""The benchmark's traffic generator: one sweep of fabric scenarios.

A configuration file (``bench/configs/<name>.json``) names a fabric, a
transport and a collective; a traffic file (``bench/traffic/<name>.json``)
names the batch, the fault schedules and their lane order, and the
``scenario_seed`` from which each lane's seed follows. Each lane's seed
drives both its spraying draws and its gray-link loss draws. Every call
of every run makes the same B scenarios: the collective's flow table
(the same for every lane) under each lane's fault lanes and seed. A
run's ``--seed`` only orders the lanes inside each schedule's block, a
new order for every call: a scenario's seed changes how long it runs (a
flapped lane's horizon by a fifth), so scenarios drawn from ``--seed``
would make it change the work. The same seed gives the same lanes.

A collective's flow table is ``bench/collectives/<algo>.py`` and a fault
kind's lanes ``bench/faults/<kind>.py``, found by name: they follow the
program's ``repro.network.collectives`` and fault lanes, written out
again here so that the yardstick does not move with the program.
"""
from __future__ import annotations

import importlib
import json
import random
from pathlib import Path

import numpy as np

from bench.reference import FatTree

ROOT = Path(__file__).resolve().parent
NEVER = 2 ** 31 - 1          # a tick no run reaches: the queue never fails
MASK64 = (1 << 64) - 1


def load_json(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``; names hold no path separators."""
    if "/" in name or name.startswith("."):
        raise ValueError(f"bad {kind} name {name!r}")
    return json.loads((ROOT / kind / f"{name}.json").read_text())


def tree_of(cfg: dict) -> FatTree:
    t = cfg["topology"]
    return FatTree(t["k"], t["pods"])


def flow_table(coll: dict) -> dict:
    """Flows of one collective, as numpy: src, dst (host ids), size
    (packets), dep (flow index that must source-complete first, or -1).
    The schedule is ``bench/collectives/<algo>.py``; rank i runs on
    ``hosts[i]`` (host i where the configuration names no hosts)."""
    n, s = coll["ranks"], coll["size_pkts"]
    if "/" in coll["algo"] or coll["algo"].startswith("."):
        raise ValueError(f"bad algorithm name {coll['algo']!r}")
    algo = importlib.import_module(f"bench.collectives.{coll['algo']}")
    ft = {k: np.asarray(v, np.int32)
          for k, v in algo.flows(coll["kind"], n, s).items()}
    hosts = np.asarray(rank_hosts(coll), np.int32)
    ft["src"], ft["dst"] = hosts[ft["src"]], hosts[ft["dst"]]
    return ft


def rank_hosts(coll: dict) -> "list[int]":
    """The host of each rank."""
    hosts = list(coll.get("hosts", range(coll["ranks"])))
    if len(hosts) != coll["ranks"] or len(set(hosts)) != len(hosts):
        raise ValueError(f"{coll['ranks']} ranks need as many distinct "
                         f"hosts, got {hosts}")
    return hosts


def expected_host_rx(cfg: dict) -> np.ndarray:
    """Packets each host must receive, exactly: the guarantee of reliable
    delivery."""
    ft = flow_table(cfg["collective"])
    rx = np.zeros(tree_of(cfg).hosts, np.int64)
    np.add.at(rx, ft["dst"], ft["size"].astype(np.int64))
    return rx


def _splitmix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & MASK64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & MASK64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & MASK64
    return x ^ (x >> 31)


def lane_seed(seed: int, lane: int) -> int:
    """A lane's uint32 seed from a seed of any size and the lane's
    index."""
    x = _splitmix64(int(seed) & MASK64)
    return _splitmix64(x ^ int(lane)) & 0xFFFFFFFF


def schedule_lanes(cfg: dict, schedule: dict) -> dict:
    """One fault schedule's per-queue lanes: fail_at, heal_at, loss_p."""
    tree = tree_of(cfg)
    Q = tree.num_queues
    out = {"fail_at": np.full(Q, NEVER, np.int32),
           "heal_at": np.full(Q, NEVER, np.int32),
           "loss_p": np.zeros(Q, np.float32)}
    for f in schedule["faults"]:
        if "/" in f["kind"] or f["kind"].startswith("."):
            raise ValueError(f"bad fault kind {f['kind']!r}")
        kind = importlib.import_module(f"bench.faults.{f['kind']}")
        kind.apply(out, tree.uplink(f["leaf"], f["uplink"]), f)
    return out


def lane_schedules(cfg: dict, traffic: dict) -> "list[str]":
    """Each lane's schedule name: the schedules in file order, each over
    an equal contiguous block of lanes (schedule-major)."""
    B, names = traffic["batch"], [s["name"] for s in traffic["schedules"]]
    if B % len(names):
        raise ValueError(f"batch {B} does not split over {len(names)} "
                         f"schedules")
    return [names[i * len(names) // B] for i in range(B)]


def call_lanes(cfg: dict, traffic: dict, seed: int, call: int
               ) -> "list[dict]":
    """The lanes of window call `call`: flow table, fault lanes, seed."""
    ft = flow_table(cfg["collective"])
    by_name = {s["name"]: schedule_lanes(cfg, s)
               for s in traffic["schedules"]}
    names = lane_schedules(cfg, traffic)
    lanes = [{**ft, **by_name[name], "schedule": name,
              "seed": lane_seed(traffic["scenario_seed"], i)}
             for i, name in enumerate(names)]
    # the run's seed orders each schedule's block of lanes: another
    # order, the same work
    rng = random.Random(f"lanes:{int(seed)}:{int(call)}")
    out = list(lanes)
    for name in dict.fromkeys(names):
        block = [i for i, n in enumerate(names) if n == name]
        for i, j in zip(block, rng.sample(block, len(block))):
            out[i] = lanes[j]
    return out
