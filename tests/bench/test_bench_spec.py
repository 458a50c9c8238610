"""``BENCHMARK.json`` and the files it names: the entries resolve to
configuration, traffic and metric files by name, the configurations
build at full size on the CPU as stated, and a new cell, mix or metric
is new files plus an entry. No chip."""
import copy
import importlib
import json
import os
import re
import shutil
import subprocess
import sys
import uuid
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, sweep  # noqa: E402
from bench.reference import FatTree  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_spec_has_the_contract_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert e2e == {"lane_ticks_per_s", "scenarios_per_s", "setup_s"}
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] == "lane_ticks_per_s" and "bound" not in m
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for w in SPEC["workloads"]:
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= 1
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for m in SPEC["per_layer"]:
        assert set(m.get("workloads", [])) <= {w["name"]
                                               for w in SPEC["workloads"]}


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_by_name(cell):
    c = harness.load_cell(cell)
    assert c.cfg["name"] == next(w["config"] for w in SPEC["workloads"]
                                 if w["name"] == cell)
    assert c.traffic["batch"] % c.chips == 0
    assert len(sweep.lane_schedules(c.cfg, c.traffic)) == c.traffic["batch"]
    for m in c.per_layer:
        assert hasattr(importlib.import_module(f"bench.metrics.{m['name']}"),
                       "read")
    assert {m["name"] for m in c.end_to_end} == {
        "lane_ticks_per_s", "scenarios_per_s", "setup_s"}


@pytest.mark.parametrize("config,flows,want_rx", [
    # 16-rank ring all-reduce, rank i on host 4i: 30 phases x 64 packets
    # into each rank's host, none into the others
    ("fig2_allreduce_ring", 480, lambda h: 30 * 64 if h % 4 == 0 else 0),
    # 32-rank all-to-all: 31 peers x 8 packets to each rank, none beyond
    ("fig2_moe_a2a_ep32", 992, lambda h: 31 * 8 if h < 32 else 0),
])
def test_configuration_builds_at_full_size(config, flows, want_rx):
    """The program's graph and workload, at full size on the CPU, match
    the benchmark's own flow table, queue numbering and payloads."""
    from repro.network import topology
    from repro.network.collectives import CollectiveSpec, build_workload

    cfg = sweep.load_json("configs", config)
    topo, coll = cfg["topology"], cfg["collective"]
    g = getattr(topology, topo["family"])(topo["k"], topo["pods"])
    tree = FatTree(topo["k"], topo["pods"])
    assert g.num_queues == tree.num_queues == 320
    assert g.num_hosts == tree.hosts == 64
    for leaf in range(tree.leaves):
        for j in range(tree.half):
            assert int(g.up1_table[leaf, j]) == tree.uplink(leaf, j)
    wl = build_workload(CollectiveSpec(coll["kind"],
                                       tuple(sweep.rank_hosts(coll)),
                                       coll["size_pkts"]), coll["algo"])
    ft = sweep.flow_table(coll)
    assert len(ft["src"]) == flows
    for k in ("src", "dst", "size", "dep"):
        assert np.array_equal(np.asarray(getattr(wl, k)), ft[k]), k
    rx = sweep.expected_host_rx(cfg)
    assert list(rx) == [want_rx(h) for h in range(64)]


def test_lane_seeds_are_stable_and_take_large_seeds():
    a = sweep.lane_seed(2 ** 31 + 12345, 5)
    assert a == sweep.lane_seed(2 ** 31 + 12345, 5)
    assert 0 <= a < 2 ** 32
    seeds = {sweep.lane_seed(s, i) for s in (1, 2 ** 40, 2 ** 31 + 12345)
             for i in range(8)}
    assert len(seeds) == 24
    # the run's seed orders the lanes inside each schedule's block only
    cell = harness.load_cell("allreduce_healthy")
    cell.traffic = dict(cell.traffic, schedules=[
        {"name": "a", "faults": []},
        {"name": "b", "faults": [{"kind": "flap", "leaf": 0, "uplink": 0,
                                  "fail_at": 10, "heal_at": 20}]}])
    runs = [sweep.call_lanes(cell.cfg, cell.traffic, s, c)
            for s, c in ((2 ** 33 + 1, 0), (2 ** 33 + 1, 1), (7, 0))]
    for lanes in runs:
        assert [ln["schedule"] for ln in lanes] == [
            ln["schedule"] for ln in runs[0]]
        assert sorted(ln["seed"] for ln in lanes) == sorted(
            ln["seed"] for ln in runs[0])
    assert [ln["seed"] for ln in runs[0]] == [
        ln["seed"] for ln in sweep.call_lanes(cell.cfg, cell.traffic,
                                              2 ** 33 + 1, 0)]


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _has_result(stdout: str) -> bool:
    return any(ln.startswith("{") for ln in stdout.splitlines())


def test_run_refuses_a_cpu_backend():
    p = _run(["--workload", "allreduce_healthy", "--seed", "1",
              "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode != 0 and not _has_result(p.stdout)
    assert "not a TPU" in p.stderr


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    """Only BENCHMARK.json and the benchmark's paths: no program, no
    result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["--workload", "allreduce_healthy", "--seed", "1",
              "--seconds", "1", "--trace", "0"], tmp_path)
    assert p.returncode != 0 and not _has_result(p.stdout)


def test_a_new_cell_is_new_files_and_an_entry():
    """A throwaway configuration, collective, fault kind, traffic mix and
    per-layer metric, added as new files plus entries (no existing file
    edited), load and run."""
    tag = "tmp_" + uuid.uuid4().hex[:8]
    cfg = copy.deepcopy(sweep.load_json("configs", "fig2_allreduce_ring"))
    cfg.update(name=tag)
    cfg["topology"].update(k=4, pods=2)
    cfg["collective"] = {"kind": tag, "algo": tag, "ranks": 8,
                         "size_pkts": 8}
    cfg["params"]["ticks"] = 2048
    traffic = {"why": "throwaway", "batch": 2, "scenario_seed": 5,
               "checked_lanes": 1,
               "schedules": [{"name": "lossy", "faults": [
                   {"kind": tag, "leaf": 1, "uplink": 0, "p": 0.25}]}]}
    files = {"configs": (ROOT / "bench" / "configs" / f"{tag}.json",
                         json.dumps(cfg)),
             "traffic": (ROOT / "bench" / "traffic" / f"{tag}.json",
                         json.dumps(traffic)),
             "metrics": (ROOT / "bench" / "metrics" / f"{tag}.py",
                         "def read(ctx):\n"
                         "    return len(ctx['calls'][0].horizons)\n"),
             # one phase: each rank sends its vector to the next one
             "collectives": (ROOT / "bench" / "collectives" / f"{tag}.py",
                             "def flows(kind, n, s):\n"
                             "    return {'src': list(range(n)),\n"
                             "            'dst': [(i + 1) % n for i in "
                             "range(n)],\n"
                             "            'size': [s] * n, 'dep': [-1] * n}\n"),
             "faults": (ROOT / "bench" / "faults" / f"{tag}.py",
                        "def apply(out, q, fault):\n"
                        "    out['loss_p'][q] = fault['p']\n")}
    spec = copy.deepcopy(SPEC)
    spec["configs"].append({"name": tag, "source": "https://example.org",
                            "file": f"bench/configs/{tag}.json",
                            "reduced": [], "why": "throwaway"})
    spec["workloads"].append({"name": tag, "config": tag, "traffic": tag,
                              "chips": 1, "why": "throwaway"})
    spec["per_layer"].append({"name": tag, "unit": "1", "better": "lower",
                              "source": "program_counter", "layer": "driver",
                              "moves": "lane_ticks_per_s",
                              "workloads": [tag]})
    try:
        for path, text in files.values():
            path.write_text(text)
        cell = harness.load_cell(tag, spec)
        assert [m["name"] for m in cell.per_layer][-1] == tag
        assert cell.cfg["topology"]["k"] == 4
        line = harness.run_cell(cell, 7, 0.1, True, 0.0, require_chip=False)
        assert line["correct"], line["checks"]
        assert line["metrics"][tag] == {"value": 2.0, "unit": "1"}
        assert list(line)[-1] == "checks"
        lanes = sweep.call_lanes(cell.cfg, cell.traffic, 7, 0)
        assert lanes[0]["loss_p"].max() == np.float32(0.25)
        assert len(lanes[0]["src"]) == 8
    finally:
        for path, _ in files.values():
            path.unlink(missing_ok=True)
        shutil.rmtree(harness.CHECKOUT / "bench_out" / "trace" / f"{tag}.7",
                      ignore_errors=True)
