"""The benchmark's plain reference against the program, and its control,
at a size a CPU test run holds: a k=4 fat tree (2 pods, 8 hosts), the
configurations' transport and parameters, the four fault schedules.

The program's lanes must equal the reference's in every compared field;
the control (the reference with its NSCC window state and arithmetic in
bfloat16, the precision below the configuration's float32) must not."""
import copy
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import check, harness, sweep  # noqa: E402
from bench.reference import Model, run_reference  # noqa: E402

FAULTS = {"batch": 4, "scenario_seed": 99, "checked_lanes": 4,
          "schedules": [
    {"name": "healthy", "faults": []},
    {"name": "flap", "faults": [{"kind": "flap", "leaf": 0, "uplink": 0,
                                 "fail_at": 100, "heal_at": 400}]},
    {"name": "gray", "faults": [{"kind": "gray", "leaf": 0, "uplink": 1,
                                 "loss_p": 0.05}]},
    {"name": "flap+gray", "faults": [
        {"kind": "flap", "leaf": 0, "uplink": 0, "fail_at": 100,
         "heal_at": 400},
        {"kind": "gray", "leaf": 0, "uplink": 1, "loss_p": 0.05}]}]}


def small_cell(config: str, algo: str, ranks: int, size: int
               ) -> harness.Cell:
    cfg = copy.deepcopy(sweep.load_json("configs", config))
    cfg["topology"].update(k=4, pods=2)
    cfg["collective"].update(algo=algo, ranks=ranks,
                             hosts=list(range(ranks)), size_pkts=size)
    cfg["params"]["ticks"] = 8192
    return harness.Cell("small", 1, cfg, copy.deepcopy(FAULTS), [], [])


CASES = {"ring": ("fig2_allreduce_ring", "ring", 8, 64),
         "tree": ("fig2_allreduce_ring", "tree", 8, 64),
         "a2a": ("fig2_moe_a2a_ep32", "round_robin", 8, 256)}


@pytest.fixture(scope="module", params=sorted(CASES))
def lanes_and_outcomes(request):
    cell = small_cell(*CASES[request.param])
    lanes = sweep.call_lanes(cell.cfg, cell.traffic, 2 ** 31 + 99, 0)
    results = harness.Program(cell).call(lanes, cell.budget)
    model = Model.from_config(cell.cfg)
    ref = run_reference(model, lanes, cell.budget)
    ctl = run_reference(model, lanes, cell.budget, fdtype=jnp.bfloat16)
    return request.param, results, ref, ctl


def test_program_equals_the_reference(lanes_and_outcomes):
    name, results, ref, _ = lanes_and_outcomes
    for r, want in zip(results, ref):
        assert check.differences(check.program_outcome(r), want) == []
        assert want["clash_ticks"] == 0
    # the lanes did exercise the transport: losses and their recovery
    assert sum(w["drops"] for w in ref) > 0
    assert sum(w["timeouts"] for w in ref) > 0
    if name == "tree":
        assert sum(w["trims"] for w in ref) > 0


def test_control_in_bfloat16_is_caught(lanes_and_outcomes):
    _, _, ref, ctl = lanes_and_outcomes
    differing = [check.differences(c, w) for c, w in zip(ctl, ref)]
    assert sum(bool(d) for d in differing) >= 2
    assert any("cwnd" in d for d in differing)
