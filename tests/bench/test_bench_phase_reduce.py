"""The reduction of the program's own spans and counters
(``bench.phase_reduce``) and the per-layer readers built on it, on
synthetic planes in the style of ``test_bench_trace_reduce``; and, on the
CPU, the fallback that scopes ops from the compiled module's text and a
traced run of a small cell."""
import copy
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, phase_reduce as pr, sweep  # noqa: E402
from bench.metrics import (control_ms_per_batch_tick,  # noqa: E402
                           enqueue_ms_per_batch_tick,
                           forwarding_ms_per_batch_tick,
                           freeze_ms_per_batch_tick, host_prep_ms_per_call,
                           masked_chunk_share, unscoped_share)

LOOP = "jit(run)/while/body/cond/branch_1_fun/while/body"
PHASE_READERS = (control_ms_per_batch_tick, forwarding_ms_per_batch_tick,
                 enqueue_ms_per_batch_tick, freeze_ms_per_batch_tick,
                 unscoped_share)
NEW_READERS = PHASE_READERS + (masked_chunk_share, host_prep_ms_per_call)


@pytest.mark.parametrize("op_name,scope", [
    (f"{LOOP}/vmap(tick.enqueue)/tick.enqueue.loss/mul", "tick.enqueue.loss"),
    (f"{LOOP}/vmap(tick.control)/jit(nack_mark)/pallas_call",
     "tick.control"),
    (f"{LOOP}/vmap(tick.control_tc)/scatter", "tick.control_tc"),
    (f"{LOOP}/closed_call/driver.freeze/jit(_where)/select_n",
     "driver.freeze"),
    ("jit(run)/while/body/driver.quiescent/vmap(reduce_and)",
     "driver.quiescent"),
    ("jit(run)/while/body/add", None),
    ("jit(run)/while/body/tick_count/add", None),
])
def test_scope_of_finds_the_innermost_phase(op_name, scope):
    assert pr.scope_of(op_name) == scope


HLO = """HloModule jit_run, is_scheduled=true

%fused_computation.3 (param_0: u32[8]) -> u32[8] {
  %param_0 = u32[8]{0} parameter(0)
  %add.1 = u32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(run)/vmap(tick.grants)/add"}
  ROOT %select.2 = u32[8]{0} select(%p, %add.1, %param_0), metadata={op_name="jit(run)/closed_call/driver.freeze/select_n"}
}

%fused_computation.4 (param_0.1: u32[8]) -> u32[8] {
  %param_0.1 = u32[8]{0} parameter(0)
  %scatter.3 = u32[8]{0} scatter(%param_0.1, %param_0.1), to_apply=%region_0, metadata={op_name="jit(run)/vmap(tick.enqueue)/scatter"}
  ROOT %bitcast.9 = u32[8]{0:T(128)} bitcast(%scatter.3)
}

ENTRY %main.9 (p: u32[8]) -> u32[8] {
  %p = u32[8]{0} parameter(0)
  %fusion.3 = u32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(run)/vmap(tick.grants)/add"}
  %copy.4 = u32[8]{0:T(128)} copy(%fusion.3)
  %copy.6 = u32[8]{0} copy(%p)
  %fusion.4 = u32[8]{0} fusion(%copy.6), kind=kCustom, calls=%fused_computation.4
  %copy-start.7 = (u32[8]{0}, u32[8]{0}, u32[]) copy-start(%fusion.4)
  %copy-done.7 = u32[8]{0} copy-done(%copy-start.7)
  ROOT %custom-call.5 = u32[8]{0} custom-call(%copy.4, %copy-done.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/vmap(tick.control)/pallas_call"}
}
"""


def test_hlo_scopes_give_fusions_and_copies_a_scope():
    """A fusion takes its root's scope, else the one its body carries; a
    copy the compiler added takes its operand's, else its user's."""
    got = pr.hlo_scopes(HLO)
    assert got["fusion.3"] == "driver.freeze"
    assert got["add.1"] == "tick.grants"
    assert got["fusion.4"] == "tick.enqueue"
    assert got["copy.4"] == "driver.freeze"
    assert got["copy.6"] == "tick.enqueue"      # a parameter's: its user's
    assert got["copy-done.7"] == "tick.enqueue"
    assert got["p"] is None
    assert got["custom-call.5"] == "tick.control"


def _ev(name, start, dur, tf_op=None):
    stats = [("tf_op", tf_op)] if tf_op is not None else []
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                           stats=stats)


def _plane(named=True):
    """One chip: an init module (one unscoped op) and a run module of
    1000 ns holding a container, four scoped ops and one copy."""
    def op(name, s, d, path):
        return _ev(name, s, d, f"{LOOP}/{path}" if named else None)

    ops = [_ev("%fusion.1 = s32[8] fusion(...), kind=kLoop", 150, 30,
               "jit(init_one)/add" if named else None),
           _ev("%while.2 = (...) while(...)", 250, 700),
           op("%fusion.7 = u32[8,480] fusion(...), kind=kCustom", 250, 200,
              "vmap(tick.enqueue)/scatter"),
           op("%fusion.8 = u32[8,800] fusion(...), kind=kLoop", 450, 50,
              "vmap(tick.enqueue)/tick.enqueue.loss/mul"),
           op("%vmap_jit_nack_mark__.3 = u32[8,480,16] custom-call(...), "
              'custom_call_target="tpu_custom_call"', 500, 100,
              "vmap(tick.control)/jit(nack_mark)/pallas_call"),
           op("%fusion.9 = u32[8,320] fusion(...), kind=kLoop", 650, 150,
              "closed_call/driver.freeze/select_n"),
           _ev("%copy.5 = s32[8,320] copy(...)", 800, 80)]
    return SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Modules", events=[
            _ev("jit_init_one(1)", 140, 50), _ev("jit_run(2)", 240, 660)]),
        SimpleNamespace(name="XLA Ops", events=ops)])


FALLBACK = {"fusion.7": "tick.enqueue", "fusion.8": "tick.enqueue.loss",
            "vmap_jit_nack_mark__.3": "tick.control", "fusion.9": "driver.freeze",
            "copy.5": None}


@pytest.mark.parametrize("named", [True, False])
def test_device_ops_take_their_scope_from_the_event_or_the_text(named):
    p = pr._device_plane(_plane(named))
    assert [m[0] for m in p["modules"]] == ["jit_init_one(1)", "jit_run(2)"]
    assert len(p["ops"]) == 6                   # the while is left out
    if not named:
        assert pr.scoped_modules(p) == set()
        assert pr.rescope(p, FALLBACK, "jit_run") == 4
    assert pr.scoped_modules(p) == {"jit_run(2)"}
    # the init module's op is outside the run: not in the phase table
    assert dict(pr.phase_ns(p)) == {
        "tick.enqueue": 200, "tick.enqueue.loss": 50, "tick.control": 100,
        "driver.freeze": 150, None: 80}
    kernel = [(s, c) for s, c, *_ in p["ops"] if c.startswith("pallas")]
    assert kernel == [("tick.control", "pallas nack_mark")]


def test_the_text_scopes_only_the_runs_unscoped_ops():
    """A copy the events leave unscoped takes the text's scope; the init
    module's op and the ops the events scoped keep theirs."""
    p = pr._device_plane(_plane())
    pr.phase_ns(p)
    assert pr.rescope(p, {"copy.5": "driver.freeze", "fusion.1": "tick.x",
                          "fusion.7": "tick.grants"}, "jit_run") == 1
    assert dict(pr.phase_ns(p)) == {
        "tick.enqueue": 200, "tick.enqueue.loss": 50, "tick.control": 100,
        "driver.freeze": 230}
    assert p["ops"][0][0] is None               # init module: untouched


def _red():
    """One traced call of 1000 ns on the aligned clock, its host spans,
    and the synthetic plane."""
    spans = [("bench.call.0", 0, 1000), ("fabric.simulate_batch", 10, 990),
             ("fabric.prepare", 10, 100), ("fabric.prepare", 100, 120),
             ("fabric.init", 120, 200), ("fabric.run", 200, 260),
             ("fabric.fetch", 260, 900), ("fabric.split", 900, 980)]
    return {"devices": [pr._device_plane(_plane())], "spans": spans}


def test_idle_gaps_go_to_the_innermost_host_span():
    red = _red()
    got = pr.gaps(red, red["devices"][0])
    assert got == [
        (0, 150, {"bench.call (no fabric span)": 10, "fabric.prepare": 110,
                  "fabric.init": 30}),
        (180, 70, {"fabric.init": 20, "fabric.run": 50}),
        (600, 50, {"fabric.fetch": 50}),
        (880, 120, {"fabric.fetch": 20, "fabric.split": 80,
                    "fabric.simulate_batch": 10,
                    "bench.call (no fabric span)": 10})]
    assert [g[:2] for g in pr.gaps(red, red["devices"][0], 100)] == [
        (0, 150), (880, 120)]


def _results(chunks, n=2):
    return [SimpleNamespace(driver_chunks=chunks) for _ in range(n)]


def _ctx(red, calls, monkeypatch, trace=True):
    path = "synthetic.xplane.pb"
    monkeypatch.setattr(pr, "latest", lambda cell: path)
    monkeypatch.setitem(pr._CACHE, path, red)
    cell = SimpleNamespace(name="synthetic",
                           cfg={"params": {"chunk_ticks": 2}})
    return {"cell": cell, "calls": calls, "trace": {} if trace else None,
            "devices": 1, "peaks": None}


def test_readers_on_a_synthetic_trace(monkeypatch):
    """Two lanes, 3 fast and 2 masked chunks of 2 ticks: 10 batch ticks."""
    calls = [SimpleNamespace(results=_results((3, 2)),
                             horizons=np.asarray([6, 10]))]
    ctx = _ctx(_red(), calls, monkeypatch)
    assert pr.executed_ticks(ctx) == [10]
    assert control_ms_per_batch_tick.read(ctx) == pytest.approx(100 / 10
                                                                / 1e6)
    assert enqueue_ms_per_batch_tick.read(ctx) == pytest.approx(250 / 10
                                                                / 1e6)
    assert forwarding_ms_per_batch_tick.read(ctx) == 0
    assert freeze_ms_per_batch_tick.read(ctx) == pytest.approx(150 / 10
                                                               / 1e6)
    assert unscoped_share.read(ctx) == pytest.approx(80 / 580)
    # the counter's share equals the horizons' (10 - 6) / 10 here
    assert masked_chunk_share.read(ctx) == pytest.approx(2 / 5)
    # prepare 90 + 20, init 80, run 60, split 80; fetch left out
    assert host_prep_ms_per_call.read(ctx) == pytest.approx(330 / 1e6)


def test_masked_chunk_share_pools_calls_and_lanes(monkeypatch):
    calls = [SimpleNamespace(results=_results((34, 0), 8),
                             horizons=np.full(8, 2176)),
             SimpleNamespace(results=_results((34, 84), 8),
                             horizons=np.asarray([2176] * 4 + [7552] * 4))]
    ctx = _ctx(_red(), calls, monkeypatch)
    assert masked_chunk_share.read(ctx) == pytest.approx(84 / (34 + 118))


def test_readers_find_nothing_where_the_program_names_nothing(monkeypatch):
    """Untraced; a program without scopes, spans or counters (as before
    they existed): every new reader returns None and none raises."""
    calls = [SimpleNamespace(results=_results((3, 2)),
                             horizons=np.asarray([6, 10]))]
    for reader in PHASE_READERS + (host_prep_ms_per_call,):
        assert reader.read(_ctx(_red(), calls, monkeypatch,
                                trace=False)) is None
    bare = _red()
    plane = bare["devices"][0]
    plane["ops"] = [(None,) + op[1:] for op in plane["ops"]]
    plane.pop("phases", None)
    bare["spans"] = [s for s in bare["spans"] if s[0].startswith("bench.")]
    old = [SimpleNamespace(results=[SimpleNamespace(), SimpleNamespace()],
                           horizons=np.asarray([6, 10]))]
    ctx = _ctx(bare, old, monkeypatch)
    for reader in NEW_READERS:
        assert reader.read(ctx) is None, reader.__name__
    monkeypatch.setattr(pr, "latest", lambda cell: None)
    assert host_prep_ms_per_call.read(ctx) is None


@pytest.mark.parametrize("named", [True, False])
def test_unscoped_ops_take_the_compiled_text(monkeypatch, named):
    """Ops whose events hold no scope (every op, where the events hold
    no op_name) take it from the text of the compiled run, here a
    synthetic stand-in; the text is made once per trace."""
    monkeypatch.setattr(pr, "latest", lambda cell: "bare.xplane.pb")
    monkeypatch.setattr(pr, "_CACHE", {})
    monkeypatch.setattr(pr, "reduce", lambda path: {
        "devices": [pr._device_plane(_plane(named))], "spans": []})
    texts = []
    monkeypatch.setattr(pr, "program_hlo",
                        lambda ctx: texts.append(1) or HLO)
    monkeypatch.setattr(pr, "hlo_scopes", lambda text: dict(
        FALLBACK, **{"copy.5": "driver.freeze"}))
    calls = [SimpleNamespace(results=_results((3, 2)),
                             horizons=np.asarray([6, 10]))]
    ctx = {"cell": SimpleNamespace(name="bare",
                                   cfg={"params": {"chunk_ticks": 2}}),
           "calls": calls, "trace": {}, "devices": 1, "peaks": None}
    assert control_ms_per_batch_tick.read(ctx) == pytest.approx(1e-5)
    assert freeze_ms_per_batch_tick.read(ctx) == pytest.approx(2.3e-5)
    assert unscoped_share.read(ctx) == 0
    assert texts == [1]


def _small_cell(per_layer=()) -> harness.Cell:
    """The ring all-reduce under one gray schedule, cut to a k=4 fat tree
    and a batch of 2."""
    cfg = copy.deepcopy(sweep.load_json("configs", "fig2_allreduce_ring"))
    cfg["topology"].update(k=4, pods=2)
    cfg["collective"].update(ranks=8, hosts=list(range(8)), size_pkts=64)
    cfg["params"]["ticks"] = 16384
    traffic = {"batch": 2, "scenario_seed": 1208, "checked_lanes": 1,
               "schedules": [{"name": "gray", "faults": [
                   {"kind": "gray", "leaf": 0, "uplink": 1,
                    "loss_p": 0.01}]}]}
    return harness.Cell("small_phases", 1, cfg, traffic, [],
                        [{"name": m.__name__.rsplit(".", 1)[1], "unit": "1"}
                         for m in per_layer])


def test_program_hlo_names_the_phases_of_the_cells_run():
    """The fallback's compiled text is the cell's own run: every phase of
    the lossy program is in it, the gray-loss draw nested in enqueue."""
    scopes = set(pr.hlo_scopes(pr.program_hlo({"cell": _small_cell()}))
                 .values())
    assert {"tick.control", "tick.forwarding", "tick.enqueue",
            "tick.enqueue.loss", "driver.freeze", "driver.quiescent",
            "driver.stats"} <= scopes


def test_a_traced_cpu_run_reports_the_host_spans(monkeypatch, tmp_path):
    """A whole traced run on the CPU: no device plane, so the device
    readers find nothing, while the host spans and the chunk counter
    read."""
    monkeypatch.setattr(pr, "TRACES", tmp_path)
    monkeypatch.setattr(harness, "trace_dir",
                        lambda cell, seed: tmp_path / f"{cell.name}.{seed}")
    line = harness.run_cell(_small_cell(NEW_READERS), 2 ** 31 + 77, 0.5,
                            True, 0.0, require_chip=False)
    assert line["correct"]
    assert set(line["metrics"]) == {"host_prep_ms_per_call",
                                    "masked_chunk_share"}
    assert line["metrics"]["host_prep_ms_per_call"]["value"] > 0
    assert 0 <= line["metrics"]["masked_chunk_share"]["value"] < 1
