"""A whole benchmark run, past its look for a chip, with the timed path
broken underneath: ``correct`` must come out false for each fault a cell
can have, and true for the sound path. Small cells on the CPU (a k=4 fat
tree, 8 hosts); the four-chip cell runs on four virtual CPU devices."""
import copy
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, sweep  # noqa: E402
from repro.network import fabric  # noqa: E402

SEED = 2 ** 31 + 4242
#: the chip smoke's four fault schedules, schedule-major, two lanes each
FAULTS = {"batch": 8, "scenario_seed": 1208, "checked_lanes": 4,
          "schedules": [
    {"name": "healthy", "faults": []},
    {"name": "flap", "faults": [{"kind": "flap", "leaf": 0, "uplink": 0,
                                 "fail_at": 100, "heal_at": 300}]},
    {"name": "gray", "faults": [{"kind": "gray", "leaf": 0, "uplink": 1,
                                 "loss_p": 0.01}]},
    {"name": "flap+gray", "faults": [
        {"kind": "flap", "leaf": 0, "uplink": 0, "fail_at": 100,
         "heal_at": 300},
        {"kind": "gray", "leaf": 0, "uplink": 1, "loss_p": 0.01}]}]}


def small_cell(chips: int = 1) -> harness.Cell:
    """The ring all-reduce configuration under the fault sweep, cut to a
    k=4 fat tree, one rank per host, and a batch of 8."""
    cfg = copy.deepcopy(sweep.load_json("configs", "fig2_allreduce_ring"))
    cfg["topology"].update(k=4, pods=2)
    cfg["collective"].update(ranks=8, hosts=list(range(8)), size_pkts=64)
    cfg["params"]["ticks"] = 16384
    return harness.Cell("small", chips, cfg, copy.deepcopy(FAULTS), [], [])


def run(cell, program_factory=harness.Program) -> dict:
    return harness.run_cell(cell, SEED, 0.5, False, 0.0,
                            require_chip=False,
                            program_factory=program_factory)


@pytest.fixture
def fresh_executables(monkeypatch):
    """A fault patched into the tick must not be served a clean
    executable compiled earlier in the process."""
    monkeypatch.setattr(fabric, "_RUN_CACHE", {})
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_path_is_correct():
    line = run(small_cell())
    assert line["correct"]
    assert all(v["value"] == 0 for v in line["checks"].values())


def test_a_step_that_returns_its_state_unchanged(monkeypatch,
                                                 fresh_executables):
    real = fabric.make_step

    def stuck(*a, **k):
        step = real(*a, **k)

        def broken(s, tick, wl, fault):
            _, out = step(s, tick, wl, fault)
            return s, out
        return broken

    monkeypatch.setattr(fabric, "make_step", stuck)
    line = run(small_cell())
    assert not line["correct"]
    assert line["checks"]["unfinished_lanes"]["value"] > 0


def test_half_of_the_batch_left_out():
    class HalfBatch(harness.Program):
        """Runs the first half of the lanes and hands their results out
        for the whole batch."""

        def call(self, lanes, budget):
            half = super().call(lanes[:len(lanes) // 2] * 2, budget)
            return half[:len(lanes) // 2] * 2

    line = run(small_cell(), program_factory=HalfBatch)
    assert not line["correct"]
    assert line["checks"]["reference_mismatch_lanes"]["value"] > 0


def test_the_exchange_between_chips_left_out():
    class FirstChipOnly(harness.Program):
        """A sharded call whose gather keeps only the first chip's block:
        every chip's slice of the results is the first chip's."""

        def call(self, lanes, budget):
            res = super().call(lanes, budget)
            per = len(res) // len(self.devices)
            return res[:per] * len(self.devices)

    line = run(small_cell(chips=4), program_factory=FirstChipOnly)
    assert not line["correct"]
    assert line["checks"]["reference_mismatch_lanes"]["value"] > 0


def test_an_answer_altered_where_it_is_produced(monkeypatch,
                                                fresh_executables):
    real = fabric._stats_update

    def late(st, prev, s, wl, tick, w0, w1):
        nst = real(st, prev, s, wl, tick, w0, w1)
        newly = (st["comp"] < 0) & (nst["comp"] >= 0)
        return dict(nst, comp=jax.numpy.where(newly, nst["comp"] + 1,
                                              nst["comp"]))

    monkeypatch.setattr(fabric, "_stats_update", late)
    line = run(small_cell())
    assert not line["correct"]
    assert line["checks"]["reference_mismatch_lanes"]["value"] > 0
    assert line["checks"]["wrong_payload_lanes"]["value"] == 0


def test_sharded_sound_path_is_correct():
    cell = small_cell(chips=4)
    line = run(cell)
    assert line["correct"], line["checks"]
    assert sweep.lane_schedules(cell.cfg, cell.traffic)[::2] == [
        "healthy", "flap", "gray", "flap+gray"]
