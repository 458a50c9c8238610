"""The reader of the enqueue's write (``enqueue_write_ms_per_batch_tick``)
on synthetic reduced planes: it reads the ops under ``tick.enqueue.write``,
which ``enqueue_ms_per_batch_tick`` counts too, and finds nothing in a
program that does not name the write."""
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import phase_reduce as pr  # noqa: E402
from bench.metrics import (enqueue_ms_per_batch_tick,  # noqa: E402
                           enqueue_write_ms_per_batch_tick)

MODULE = "jit_run(2)"


def _red(write_scope):
    """One chip running one module: enqueue ops of 120 (the write, under
    `write_scope`), 80 and 50 ns (the loss draw), a control op of 100 ns
    and an unscoped copy of 40 ns."""
    ops = [(write_scope, "scatter/gather", MODULE, 100, 220, "fusion.7"),
           ("tick.enqueue", "loop/other", MODULE, 220, 300, "fusion.8"),
           ("tick.enqueue.loss", "loop/other", MODULE, 300, 350, "fusion.9"),
           ("tick.control", "pallas nack_mark", MODULE, 350, 450, "cc.3"),
           (None, "copy", MODULE, 450, 490, "copy.5")]
    plane = {"name": "/device:TPU:0", "modules": [(MODULE, 90, 500)],
             "ops": ops, "names": {}, "stat_keys": []}
    return {"devices": [plane], "spans": []}


def _ctx(red, monkeypatch, trace=True):
    """Two lanes, 3 fast and 2 masked chunks of 2 ticks: 10 batch ticks."""
    path = "synthetic.xplane.pb"
    monkeypatch.setattr(pr, "latest", lambda cell: path)
    monkeypatch.setattr(pr, "_CACHE", {path: red})
    calls = [SimpleNamespace(
        results=[SimpleNamespace(driver_chunks=(3, 2)) for _ in range(2)],
        horizons=np.asarray([6, 10]))]
    cell = SimpleNamespace(name="synthetic",
                           cfg={"params": {"chunk_ticks": 2}})
    return {"cell": cell, "calls": calls, "trace": {} if trace else None,
            "devices": 1, "peaks": None}


@pytest.mark.parametrize("write_scope,write_ns", [
    ("tick.enqueue.write", 120),
    ("tick.enqueue", None),         # a program that does not name the write
])
def test_the_write_reader_reads_its_own_scope(monkeypatch, write_scope,
                                              write_ns):
    ctx = _ctx(_red(write_scope), monkeypatch)
    got = enqueue_write_ms_per_batch_tick.read(ctx)
    if write_ns is None:
        assert got is None
    else:
        assert got == pytest.approx(write_ns / 10 / 1e6)
    # the phase counts its nested write either way
    assert enqueue_ms_per_batch_tick.read(ctx) == pytest.approx(
        (120 + 80 + 50) / 10 / 1e6)


def test_the_write_reader_finds_nothing_without_a_trace_or_scopes(
        monkeypatch):
    """Untraced, or traced from a program with no scopes at all (as
    before they existed): None, and no raise."""
    assert enqueue_write_ms_per_batch_tick.read(
        _ctx(_red("tick.enqueue.write"), monkeypatch, trace=False)) is None
    bare = _red("tick.enqueue.write")
    bare["devices"][0]["ops"] = [(None,) + op[1:]
                                 for op in bare["devices"][0]["ops"]]
    assert enqueue_write_ms_per_batch_tick.read(
        _ctx(bare, monkeypatch)) is None
    ctx = _ctx(_red("tick.enqueue.write"), monkeypatch)
    monkeypatch.setattr(pr, "latest", lambda cell: None)   # no trace file
    assert enqueue_write_ms_per_batch_tick.read(ctx) is None
