"""The comparison that decides a run's ``correct``.

Two parts, both on the results of the window's own calls:

* every lane of every call: it quiesced inside the tick budget with
  every flow complete, and each host received exactly the payload the
  collective owes it (reliable delivery), and every call returned one
  result per lane;
* a sample of lanes drawn from the run's seed, one from each contiguous
  block of the batch (so each fault schedule, and in a sharded cell each
  device's block, is covered) with the longest lane of the window in it:
  the plain reference (``bench.reference``) runs each sampled lane's
  scenario from scratch, and every observable field must equal the
  program's exactly: horizon, per-flow completion ticks, counters, and
  the source and destination tracker, retransmit and NSCC window state.

Besides, the window may compile nothing: a compile there would time the
compiler, not the simulator. Each count below has the limit 0.
"""
from __future__ import annotations

import random

import numpy as np

#: fields compared between the program's and the reference's outcome
FIELDS = (
    "horizon", "completion", "src_completion", "win_delivered",
    "qlen_peak", "delivered", "next_psn", "inflight", "last_progress",
    "src_base", "src_ring", "src_rx_ok", "src_dup", "src_oor", "rtx",
    "dst_base", "dst_ring", "dst_rx_ok", "dst_dup", "dst_oor", "cwnd",
    "epoch_acked", "epoch_lost", "epoch_tick", "trims", "drops", "dups",
    "retransmits", "timeouts", "ticks_degraded", "q_len", "clash_ticks")

LIMITS = {"missing_results": 0, "unfinished_lanes": 0,
          "wrong_payload_lanes": 0, "reference_mismatch_lanes": 0,
          "compiles_in_window": 0}


def program_outcome(r) -> dict:
    """The compared fields of one program ``SimResult`` (stats tier)."""
    s = r.state
    return {
        "horizon": int(r.horizon), "completion": r.stat_completion,
        "src_completion": r.stat_src_completion,
        "win_delivered": r.stat_win_delivered,
        "qlen_peak": int(r.qlen_peak), "delivered": s.delivered,
        "next_psn": s.next_psn, "inflight": s.inflight,
        "last_progress": s.last_progress,
        "src_base": s.src_track.base, "src_ring": s.src_track.ring,
        "src_rx_ok": s.src_track.rx_ok, "src_dup": s.src_track.dup,
        "src_oor": s.src_track.oor, "rtx": s.rtx,
        "dst_base": s.dst_track.base, "dst_ring": s.dst_track.ring,
        "dst_rx_ok": s.dst_track.rx_ok, "dst_dup": s.dst_track.dup,
        "dst_oor": s.dst_track.oor,
        "cwnd": np.asarray(s.cc.cwnd, np.float32),
        "epoch_acked": s.cc.epoch_acked, "epoch_lost": s.cc.epoch_lost,
        "epoch_tick": s.cc.epoch_tick,
        "trims": int(s.trims), "drops": int(s.drops), "dups": int(s.dups),
        "retransmits": int(s.retransmits), "timeouts": int(s.timeouts),
        "ticks_degraded": int(s.ticks_degraded), "q_len": s.q_len,
        "clash_ticks": 0,
    }


def differences(got: dict, want: dict) -> "list[str]":
    """Names of the fields in which `got` differs from `want`, bit for
    bit (the float window is compared as its bits)."""
    out = []
    for f in FIELDS:
        a, b = np.asarray(got[f]), np.asarray(want[f])
        if a.dtype == np.float32 or b.dtype == np.float32:
            a = a.astype(np.float32).view(np.uint32)
            b = b.astype(np.float32).view(np.uint32)
        if a.shape != b.shape or not np.array_equal(a, b):
            out.append(f)
    return out


def sample(horizons: "list[np.ndarray]", blocks: int, seed: int
           ) -> "list[tuple[int, int]]":
    """(call, lane) pairs to check: one lane from each of `blocks`
    contiguous lane blocks, from a call drawn from `seed`; the window's
    longest lane replaces its block's draw."""
    rng = random.Random(f"check:{seed}")
    B = len(horizons[0])
    picks = []
    for k in range(blocks):
        lo, hi = k * B // blocks, (k + 1) * B // blocks
        picks.append((rng.randrange(len(horizons)), rng.randrange(lo, hi)))
    call, lane = max(((c, i) for c in range(len(horizons))
                      for i in range(B)),
                     key=lambda ci: (horizons[ci[0]][ci[1]], -ci[0], -ci[1]))
    picks[lane * blocks // B] = (call, lane)
    return picks


def guarantee_counts(calls: "list[list]", batch: int, budget: int,
                     dst: np.ndarray, want_rx: np.ndarray) -> dict:
    """Counts over every lane of every call: missing results, lanes not
    finished inside the budget, lanes with a wrong per-host payload."""
    out = {"missing_results": 0, "unfinished_lanes": 0,
           "wrong_payload_lanes": 0}
    for results in calls:
        out["missing_results"] += abs(batch - len(results))
        for r in results:
            if (r.horizon >= budget or (r.stat_completion < 0).any()
                    or (r.stat_src_completion < 0).any()):
                out["unfinished_lanes"] += 1
            rx = np.zeros_like(want_rx)
            np.add.at(rx, dst, np.asarray(r.state.delivered, np.int64))
            if not np.array_equal(rx, want_rx):
                out["wrong_payload_lanes"] += 1
    return out
