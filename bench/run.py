#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Prints progress and the check on standard error, and as the last line
of standard output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, last, ``checks``
(each number compared, with its limit). Exits non-zero with no result
line when JAX finds no TPU of a kind in ``bench/peaks.json``, fewer
chips than the cell needs, or no program to run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
# JAX's persistent compile cache at a fixed path inside the checkout,
# which the program's own cache set-up takes from this variable
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
# the TPU runtime's logs would otherwise go to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        from bench import harness

        cell = harness.load_cell(args.workload)
        line = harness.run_cell(cell, args.seed, args.seconds,
                                bool(args.trace), T_START)
    except Exception as e:                      # no result line on failure
        traceback.print_exc()
        print(f"bench: no result: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
