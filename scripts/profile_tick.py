#!/usr/bin/env python3
"""Per-tick device time of the chip smoke's batched tick, and where it goes.

Runs the scenario batch of ``chip_smoke.py`` phase (b) (64-rank tree
all-reduce on ``paper_fig2()``, 4 fault schedules x seeds) through
``simulate_batch`` for a fixed window of ticks, warm, at each batch size
given; then traces one more call at the last batch size with the JAX
profiler and reduces the device plane of the trace to a table:

* leaf XLA op time per tick (``while``/``cond`` containers left out,
  since they only enclose the leaves), by op class: each Pallas kernel,
  XLA custom fusions (scatters and gathers), loop fusions,
  dynamic-update-slice, and the rest by HLO opcode;
* the device's idle share: 1 - (union of leaf-op spans) over the XLA
  module time (idle inside the device program), and over the traced
  call's host wall time (idle including set-up and result fetch).

    python3 scripts/profile_tick.py                     # on a TPU
    python3 scripts/profile_tick.py --batches 8 128 --ticks 512 \
        --out chiprun_out/prof
    python3 scripts/profile_tick.py --reduce PATH.xplane.pb --ticks 512

``--reduce`` only reduces a trace written earlier. The window is too
short for any lane to quiesce, so every lane runs all ``--ticks`` ticks.
Times on the host clock include dispatch and the result fetch.
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

_CONTAINER = re.compile(r"(while|cond|conditional)\.")


def _op_class(head: str, body: str) -> str:
    if "tpu_custom_call" in body:
        return "pallas " + re.sub(r"^(vmap_)*jit_", "", head.split("__")[0])
    if "kind=kCustom" in body:
        return "custom fusion (scatter/gather)"
    if head.startswith("dynamic-update-slice"):
        return "dynamic-update-slice"
    if "fusion" in head:
        return "loop/other fusion"
    return re.sub(r"(\.\d+|\.clone)+$", "", head)


def _union_ns(spans) -> int:
    busy, reach = 0, -1
    for s, t in sorted(spans):
        busy += max(0, t - max(s, reach))
        reach = max(reach, t)
    return busy


def reduce_trace(path: str, ticks: int,
                 wall_s: "float | None" = None) -> dict:
    """Leaf-op time per tick by op class, and the idle shares, from the
    first TPU plane of an ``.xplane.pb``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    plane = next(p for p in pd.planes if p.name.startswith("/device:TPU:"))
    lines = {ln.name: list(ln.events) for ln in plane.lines}
    module_ns = sum(e.duration_ns for e in lines.get("XLA Modules", ()))
    by_class, spans = collections.Counter(), []
    for e in lines.get("XLA Ops", ()):
        head, _, body = e.name.partition(" = ")
        head = head.lstrip("%")
        if _CONTAINER.match(head):
            continue
        by_class[_op_class(head, body)] += e.duration_ns
        spans.append((e.start_ns, e.start_ns + e.duration_ns))
    leaf_ns = sum(by_class.values())
    busy_ns = _union_ns(spans)
    out = {
        "trace": path, "ticks": ticks,
        "leaf_op_ms_per_tick": leaf_ns / ticks / 1e6,
        "module_ms": module_ns / 1e6, "leaf_busy_ms": busy_ns / 1e6,
        "idle_share_in_modules": (1 - busy_ns / module_ns
                                  if module_ns else None),
        "share_by_class": {k: v / leaf_ns
                           for k, v in by_class.most_common()},
    }
    if wall_s:
        out["traced_call_s"] = wall_s
        out["idle_share_of_call"] = 1 - busy_ns / 1e9 / wall_s
    return out


def _print_reduction(r: dict) -> None:
    print(f"trace {r['trace']}: {r['leaf_op_ms_per_tick']:.3f} ms of leaf "
          f"op time per tick; leaf-op union {r['leaf_busy_ms']:.1f} ms of "
          f"{r['module_ms']:.1f} ms in XLA modules (idle share there "
          f"{r['idle_share_in_modules']:.4f})"
          + (f"; idle share of the {r['traced_call_s']:.3f} s traced call "
             f"{r['idle_share_of_call']:.4f}" if "traced_call_s" in r
             else ""))
    rest = {k: v for k, v in r["share_by_class"].items() if v < 1e-3}
    for k, v in r["share_by_class"].items():
        if k not in rest:
            ms = v * r["leaf_op_ms_per_tick"]
            print(f"  {100 * v:6.2f}%  {ms:8.4f} ms/tick  {k}")
    if rest:
        print(f"  {100 * sum(rest.values()):6.2f}%  {len(rest)} classes "
              f"under 0.1% each")


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batches", type=int, nargs="+", default=[8, 128],
                    help="scenario batch sizes, multiples of 4 (the last "
                         "one is traced)")
    ap.add_argument("--ticks", type=int, default=512,
                    help="ticks per call (default 512)")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "prof"),
                    help="trace directory; profile.json is written here")
    ap.add_argument("--reduce", metavar="XPLANE",
                    help="only reduce this trace, run nothing")
    args = ap.parse_args(argv)

    if args.reduce:
        _print_reduction(reduce_trace(args.reduce, args.ticks))
        return 0

    import jax

    import chip_smoke as cs
    from repro.compile_cache import enable_persistent_cache
    from repro.network.topology import paper_fig2

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"profile_tick: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    enable_persistent_cache()
    g = paper_fig2()
    report = {"device": dev.device_kind, "ticks": args.ticks, "batches": {}}
    for B in args.batches:
        b = cs.make_batch(g, cs.RANKS, cs.SIZE_PKTS, B // 4, args.ticks,
                          cs.FLAP, cs.GRAY_P)
        b.run()                                      # compile
        t0 = time.perf_counter()
        rs = b.run()
        warm_s = time.perf_counter() - t0
        ticks = max(r.horizon for r in rs)
        report["batches"][B] = {"warm_s": warm_s, "ticks": ticks,
                                "ms_per_tick": warm_s / ticks * 1e3}
        print(f"B={B}: {ticks} ticks warm {warm_s:.6f} s = "
              f"{warm_s / ticks * 1e3:.6f} ms per batch tick", flush=True)

    trace_dir = os.path.join(args.out, f"B{b.size}")
    with jax.profiler.trace(trace_dir):
        t0 = time.perf_counter()
        b.run()
        traced_s = time.perf_counter() - t0
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    r = reduce_trace(path, ticks, traced_s)
    _print_reduction(r)
    report["trace"] = r
    with open(os.path.join(args.out, "profile.json"), "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
