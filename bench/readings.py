#!/usr/bin/env python3
"""The readings the check's limits are set from, on the chip.

    python3 bench/readings.py --workload <cell> --seeds 1 2 3 ... \
        --control-seeds 1 2 3

For each seed: one call of the cell's batch through the timed path (the
window's first call for that seed), the check's sample of its lanes, and
the plain reference on them. Printed per seed: the program's counts
(every lane: unfinished, wrong payload; sampled: differing from the
reference) and, for the control seeds, the control's count: the same
sampled lanes run by the reference with its NSCC window state and
arithmetic in bfloat16, compared with the float32 reference as if it
were the program. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
# JAX's persistent compile cache at a fixed path inside the checkout,
# which the program's own cache set-up takes from this variable
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from bench import check, harness, sweep
    from bench.reference import Model, run_reference
    from repro.compile_cache import enable_persistent_cache

    if jax.devices()[0].platform != "tpu":
        print("readings: needs a TPU", file=sys.stderr)
        return 2
    enable_persistent_cache()
    cpu = jax.devices("cpu")[0]
    cell = harness.load_cell(args.workload)
    used = jax.devices()[:cell.chips]
    program = harness.Program(cell, list(used) if cell.chips > 1 else None)
    model = Model.from_config(cell.cfg)
    dst = sweep.flow_table(cell.cfg["collective"])["dst"]
    want_rx = sweep.expected_host_rx(cell.cfg)
    rows = []
    for seed in args.seeds:
        lanes = sweep.call_lanes(cell.cfg, cell.traffic, seed, 0)
        t0 = time.perf_counter()
        results = program.call(lanes, cell.budget)
        call_s = time.perf_counter() - t0
        counts = check.guarantee_counts([results], len(lanes), cell.budget,
                                        dst, want_rx)
        picks = [i for _, i in check.sample(
            [harness.Call(0, 0, lanes, results).horizons],
            int(cell.traffic["checked_lanes"]), seed)]
        t0 = time.perf_counter()
        with jax.default_device(cpu):       # where the benchmark runs it
            ref = run_reference(model, [lanes[i] for i in picks],
                                cell.budget)
        ref_s = time.perf_counter() - t0
        row = {"seed": seed, "call_s": call_s, "reference_s": ref_s,
               "horizons": [int(r.horizon) for r in results], **counts,
               "reference_mismatch_lanes": sum(
                   bool(check.differences(check.program_outcome(results[i]),
                                          w)) for i, w in zip(picks, ref))}
        if seed in args.control_seeds:
            with jax.default_device(cpu):
                ctl = run_reference(model, [lanes[i] for i in picks],
                                    cell.budget, fdtype=jnp.bfloat16)
            diffs = [check.differences(c, w) for c, w in zip(ctl, ref)]
            row["control_mismatch_lanes"] = sum(bool(d) for d in diffs)
            row["control_fields"] = sorted({f for d in diffs for f in d})
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": cell.name, "device": used[0].device_kind,
                      "lower_reading": max(r["reference_mismatch_lanes"]
                                           for r in rows),
                      "upper_reading": min(
                          (r["control_mismatch_lanes"] for r in rows
                           if "control_mismatch_lanes" in r),
                          default=None)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
