"""Reduce a traced run's ``.xplane.pb`` to the program's own spans: the
device time under each tick phase and driver piece, and the device's
idle gaps put down to the host span that covers them.

The program names its work (DESIGN.md, "Spans and counters"):

* on the device, ``jax.named_scope`` on each phase of the tick
  (``tick.control``, ``tick.enqueue``, ``tick.enqueue.loss``, ...) and on
  the driver's pieces (``driver.freeze``, ``driver.quiescent``,
  ``driver.stats``), which lands in each HLO op's ``op_name`` metadata:
  ``jit(run)/while/body/.../vmap(tick.enqueue)/tick.enqueue.loss/mul``;
* on the host, ``jax.profiler.TraceAnnotation`` spans ``fabric.*`` inside
  each ``simulate_batch`` call.

Each leaf device op (``bench.trace_reduce``'s leaves: containers left
out) takes the innermost scope of the ``op_name`` its trace event
carries. An op whose event carries none (every op, on a v5e: its events
hold only times) takes it from the compiled run's text, by instruction
name (`hlo_scopes`). A trace is read once per path and shared by every
reader of the run.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import sys
import time
from pathlib import Path

from bench import trace_reduce as tr

#: where the harness writes each traced run (``harness.trace_dir``)
TRACES = Path(__file__).resolve().parent.parent / "bench_out" / "trace"
#: innermost ``tick.*`` / ``driver.*`` scope of an op_name path; a
#: transform wraps it as ``vmap(tick.enqueue)``
_SCOPE = re.compile(r"(?:^|[/(])((?:tick|driver)\.[a-z_]+(?:\.[a-z_]+)*)"
                    r"(?=$|[/)\"])")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
#: stats of a device op event that can hold its op_name
_NAME_STATS = ("tf_op", "long_name", "op_name")
#: host spans the gaps are put down to
_HOST = ("fabric.", "bench.call.")
#: the host spans that ``host_prep_ms_per_call`` adds up
PREP_SPANS = ("fabric.prepare", "fabric.init", "fabric.run", "fabric.split")
#: device idle gaps reported on stderr: at least this long
GAP_NS = 100_000

_CACHE: dict = {}


def scope_of(op_name: str) -> "str | None":
    """The innermost tick or driver scope in an ``op_name`` path."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def hlo_scopes(text: str) -> dict:
    """``{instruction name: scope or None}`` of a compiled module's
    ``as_text()``. An op that calls a computation (a fusion) takes the
    scope of the callee's root, else its own, else the one most of the
    callee's instructions carry. A copy or bitcast the compiler added
    without an op_name takes its operand's scope, else its first
    scoped user's."""
    comp, roots, own, calls, copies = None, {}, {}, {}, set()
    members = collections.defaultdict(list)
    operands, users = {}, collections.defaultdict(list)
    for line in text.splitlines():
        m = re.match(r"\s*(?:ENTRY\s+)?%([\w.\-]+)\s*\(.*\{\s*$", line)
        if m:
            comp = m.group(1)
            continue
        m = re.match(r"\s*(ROOT\s+)?%([\w.\-]+)\s*=(.*)$", line)
        if not m:
            continue
        root, name, rest = m.groups()
        op = _OP_NAME.search(rest)
        own[name] = scope_of(op.group(1)) if op else None
        called = re.search(r"calls=%([\w.\-]+)", rest)
        if called:
            calls[name] = called.group(1)
        if comp is not None:
            members[comp].append(name)
            if root:
                roots[comp] = name
        opcode = re.search(r"\s([a-z][a-z0-9\-]*)\(", rest)
        if opcode and opcode.group(1) in ("copy", "copy-start",
                                          "copy-done", "bitcast"):
            copies.add(name)
        operands[name] = re.findall(r"%([\w.\-]+)",
                                    rest.split(", metadata=")[0])
        for o in operands[name]:
            users[o].append(name)
    out = {}
    for name, scope in own.items():
        callee = calls.get(name)
        inside = collections.Counter(own[n] for n in members.get(callee, ())
                                     if own.get(n))
        out[name] = (own.get(roots.get(callee)) or scope
                     or next(iter(inside.most_common(1)), (None,))[0])

    def along(name, step, seen):
        while name in copies and out.get(name) is None and name not in seen:
            seen.add(name)
            nxt = [n for n in step(name) if n in out]
            if not nxt:
                return None
            name = nxt[0]
        return out.get(name)

    for name in copies:
        if out[name] is None:
            out[name] = (along(name, operands.get, set())
                         or next(filter(None, (along(u, users.get, set())
                                               for u in users[name])), None))
    return out


def _module_of(modules, starts, t) -> str:
    i = bisect.bisect_right(starts, t) - 1
    return modules[i][0] if i >= 0 and t < modules[i][2] else ""


def _device_plane(plane) -> dict:
    """Leaf ops of one device plane as ``(scope, class, module, start,
    end, instruction)``; `names` keeps one event name per instruction."""
    lines = {ln.name: list(ln.events) for ln in plane.lines}
    modules = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in lines.get("XLA Modules", ())),
                     key=lambda m: m[1])
    starts = [m[1] for m in modules]
    known: dict = {}
    ops, names, keys = [], {}, None
    for e in lines.get("XLA Ops", ()):
        name, start = e.name, e.start_ns
        module = _module_of(modules, starts, start)
        key = (module, name)
        if key not in known:
            head, _, body = name.partition(" = ")
            head = head.lstrip("%")
            if tr._CONTAINER.match(head):
                known[key] = None
                continue
            stats = dict(e.stats)
            if keys is None:
                keys = sorted(stats)
            texts = [name] + [str(stats[k]) for k in _NAME_STATS
                              if k in stats]
            scope = next(filter(None, map(scope_of, texts)), None)
            known[key] = (scope, tr.op_class(head, body), head)
            names[head] = name
        got = known[key]
        if got is not None:
            ops.append((got[0], got[1], module, start,
                        start + e.duration_ns, got[2]))
    return {"name": plane.name, "modules": modules, "ops": ops,
            "names": names, "stat_keys": keys or []}


def reduce(path: str) -> dict:
    """``{"devices": [plane...], "spans": [(name, start, end)]}`` of one
    trace: each device plane's leaf ops with their scopes, and the
    host's ``fabric.*`` and ``bench.call.*`` spans, every time in the
    trace's nanoseconds."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            devices.append(_device_plane(plane))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(_HOST):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    devices.sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def latest(cell: str) -> "str | None":
    """The newest trace the harness wrote for `cell`."""
    found = glob.glob(str(TRACES / f"{cell}.*" / "**" / "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def scoped_modules(plane) -> set:
    """The modules that hold the program's scoped ops: the run."""
    return {op[2] for op in plane["ops"] if op[0] is not None}


def rescope(plane, scopes: dict, module: "str | None") -> int:
    """Give each unscoped op of the run (the scoped modules, or where
    none is scoped the modules named `module`) the scope `scopes` maps
    its instruction to; the number of instructions it scoped."""
    mods = scoped_modules(plane) or {
        m[0] for m in plane["modules"] if m[0].split("(")[0] == module}
    done, ops = set(), []
    for op in plane["ops"]:
        if op[0] is None and op[2] in mods and scopes.get(op[5]):
            op = (scopes[op[5]],) + op[1:]
            done.add(op[5])
        ops.append(op)
    plane["ops"] = ops
    plane.pop("phases", None)
    return len(done)


def phase_ns(plane) -> "collections.Counter":
    """Leaf-op nanoseconds per scope (None: unscoped) in the run's
    modules, worked out once per plane."""
    if "phases" not in plane:
        mods = scoped_modules(plane)
        c = collections.Counter()
        for scope, _, m, s, t, _ in plane["ops"]:
            if m in mods:
                c[scope] += t - s
        plane["phases"] = c
    return plane["phases"]


def gaps(red: dict, plane, min_ns: int = 0) -> list:
    """The plane's idle stretches of at least `min_ns` inside each traced
    call, as ``(start offset in the call, length, {host span: ns})``:
    each stretch cut at the host spans' edges and each piece put down to
    the innermost span over it (the shortest)."""
    calls = [s for s in red["spans"] if s[0].startswith("bench.call.")]
    host = [s for s in red["spans"] if s[0].startswith("fabric.")]
    busy = tr.union([op[3:5] for op in plane["ops"]])
    out = []
    for _, c0, c1 in calls:
        inside = [(max(s, c0), min(t, c1)) for s, t in busy
                  if t > c0 and s < c1]
        edges = [c0] + [x for st in inside for x in st] + [c1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b - a < max(min_ns, 1):
                continue
            cuts = sorted({a, b} | {x for _, s, t in host for x in (s, t)
                                    if a < x < b})
            by = collections.Counter()
            for u, v in zip(cuts, cuts[1:]):
                over = [h for h in host if h[1] <= u and v <= h[2]]
                name = (min(over, key=lambda h: h[2] - h[1])[0] if over
                        else "bench.call (no fabric span)")
                by[name] += v - u
            out.append((a - c0, b - a, dict(by)))
    return out


def _say(red: dict, planes) -> None:
    """What a reader of this trace should see once, on stderr: where the
    op names came from, the phase table, the largest unscoped ops, the
    op classes (each kernel one) by scope, the clock check and the idle
    gaps over ``GAP_NS``."""
    def say(msg):
        print(f"phases: {msg}", file=sys.stderr, flush=True)

    for p in planes:
        say(f"{p['name']}: op event stats {p['stat_keys']}; instructions "
            f"scoped from the compiled text: {p.get('rescoped', 0)}")
        ph = phase_ns(p)
        total = sum(ph.values())
        every = sum(op[4] - op[3] for op in p["ops"])
        say(f"  the run's leaf-op time {total / 1e9:.6f} s of "
            f"{every / 1e9:.6f} s in every module")
        for scope, ns in sorted(ph.items(), key=lambda kv: -kv[1]):
            say(f"  {scope or '(unscoped)'}: {ns / 1e9:.6f} s, "
                f"{ns / max(total, 1):.4f} of the run's leaf-op time")
        mods = scoped_modules(p)
        loose, pairs = collections.Counter(), collections.Counter()
        for scope, cls, m, s, t, head in p["ops"]:
            if scope is None and m in mods:
                loose[head] += t - s
            k = tr.kernel_of(cls)
            pairs[("pallas " + k if k else cls, scope)] += t - s
        for head, ns in loose.most_common(8):
            say(f"  unscoped {ns / 1e9:.6f} s: {p['names'][head][:240]}")
        for (cls, scope), ns in pairs.most_common(16):
            say(f"  {cls} under {scope}: {ns / 1e9:.6f} s")
        run = [op[3:5] for op in p["ops"] if op[2] in mods]
        host = {n: (s, t) for n, s, t in red["spans"]}
        if run and "fabric.run" in host and "fabric.fetch" in host:
            first = (run[0][0] - host["fabric.run"][0]) / 1e6
            last = (host["fabric.fetch"][1] - max(t for _, t in run)) / 1e6
            say(f"  clock: the run's first op starts {first:.3f} ms after "
                f"fabric.run starts; its last op ends {last:.3f} ms before "
                f"fabric.fetch ends")
        for off, ln, by in sorted(gaps(red, p, GAP_NS), key=lambda g: -g[1]):
            say(f"  idle {ln / 1e6:.3f} ms at +{off / 1e6:.3f} ms: "
                + ", ".join(f"{n} {v / 1e6:.3f} ms" for n, v in
                            sorted(by.items(), key=lambda kv: -kv[1])))


def program_hlo(ctx) -> str:
    """The compiled text of the run that ``simulate_batch`` dispatches
    for the cell (one chip)."""
    import jax
    import numpy as np

    from bench import harness, sweep

    cell = ctx["cell"]
    prog = harness.Program(cell)
    lanes = sweep.call_lanes(cell.cfg, cell.traffic, 0, 0)
    fault = prog.faults(lanes)
    seeds = np.asarray([ln["seed"] for ln in lanes], np.uint32)
    init, run = prog.fabric.driver_fns(
        prog.g, prog.profile, prog.params, prog.wls.src.shape[1], fault,
        "stats", batched=True)
    s0 = jax.eval_shape(init, prog.wls, seeds)
    i32 = np.int32
    return run.lower(s0, prog.wls, fault, i32(cell.budget), i32(0),
                     i32(cell.budget)).compile().as_text()


def load(ctx) -> "dict | None":
    """The reduced trace of a reader's traced run (read once per path),
    or None: an untraced run, or no trace file."""
    path = None if ctx["trace"] is None else latest(ctx["cell"].name)
    if path is None:
        return None
    if path not in _CACHE:
        red = reduce(path)
        red["devices"] = red["devices"][:ctx["devices"]]
        # a v5e trace's op events carry no op_name: the scopes come from
        # the compiled run (program_hlo: one chip's)
        if ctx["devices"] > 1:
            print("phases: a sharded run's ops keep the events' scopes",
                  file=sys.stderr)
        elif red["devices"]:
            t0 = time.perf_counter()
            try:
                text = program_hlo(ctx)
            except Exception as e:             # the trace's scopes stand
                print(f"phases: no compiled text to scope the rest: "
                      f"{type(e).__name__}: {e}", file=sys.stderr)
            else:
                print(f"phases: the compiled text took "
                      f"{time.perf_counter() - t0:.2f} s", file=sys.stderr)
                scopes = hlo_scopes(text)
                module = re.match(r"HloModule ([\w.\-]+)", text)
                for p in red["devices"]:
                    p["rescoped"] = rescope(p, scopes, module and
                                            module.group(1))
        _say(red, red["devices"])
        _CACHE[path] = red
    return _CACHE[path]


def for_ctx(ctx) -> "dict | None":
    """`load`, or None where the program names none of its phases."""
    red = load(ctx)
    if red is None or not red["devices"] or not all(
            phase_ns(p).keys() - {None} for p in red["devices"]):
        return None
    return red


def executed_ticks(ctx) -> "list[int] | None":
    """[devices] executed batch ticks of each chip over the window's
    calls, from the driver's chunk counter: (fast + masked) x chunk.
    None where the program does not count its chunks."""
    import numpy as np

    n, chunk = ctx["devices"], int(ctx["cell"].cfg["params"]["chunk_ticks"])
    out = [0] * n
    for c in ctx["calls"]:
        for d, block in enumerate(np.array_split(np.arange(len(c.results)),
                                                 n)):
            got = getattr(c.results[block[0]], "driver_chunks", None)
            if got is None:
                return None
            out[d] += sum(got) * chunk
    return out


def ms_per_batch_tick(ctx, prefix: str) -> "float | None":
    """Device time under the scopes named `prefix` (and those nested
    under the name, ``tick.enqueue`` with ``tick.enqueue.loss``) per
    executed batch tick, in ms, averaged over the cell's chips."""
    red = for_ctx(ctx)
    ticks = executed_ticks(ctx) if red is not None else None
    if ticks is None:
        return None
    per = []
    for p, t in zip(red["devices"], ticks):
        ns = sum(v for s, v in phase_ns(p).items()
                 if s == prefix or (s or "").startswith(prefix + "."))
        if t > 0:
            per.append(ns / t)
    return sum(per) / len(per) / 1e6 if per else None
