"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

The op classes and the span union follow the program's
``scripts/profile_tick.py`` (copied here, so that later changes to the
program cannot move the yardstick): leaf XLA ops only, with the
``while``/``cond`` containers left out since they only enclose the
leaves; each Pallas kernel (``tpu_custom_call``) is its own class, XLA
custom fusions (scatters and gathers, ``kind=kCustom``) one class,
dynamic-update-slice one, other fusions one, the rest by HLO opcode.

Per device plane it keeps the XLA module spans, the leaf-op spans and
their classes; from the host planes it keeps the benchmark's own
``TraceAnnotation`` spans (names starting with ``bench.``).
"""
from __future__ import annotations

import collections
import re

_CONTAINER = re.compile(r"(while|cond|conditional)\.")
KERNELS = ("sack_fused", "nack_mark", "sack_advance")


def op_class(head: str, body: str) -> str:
    if "tpu_custom_call" in body:
        return "pallas " + re.sub(r"^(vmap_)*jit_", "", head.split("__")[0])
    if "kind=kCustom" in body:
        return "custom fusion (scatter/gather)"
    if head.startswith("dynamic-update-slice"):
        return "dynamic-update-slice"
    if "fusion" in head:
        return "loop/other fusion"
    return re.sub(r"(\.\d+|\.clone)+$", "", head)


def kernel_of(cls: str) -> "str | None":
    """The hot-path kernel a Pallas op class belongs to, if any."""
    if not cls.startswith("pallas "):
        return None
    return next((k for k in KERNELS if k in cls), None)


def union(spans) -> "list[tuple[int, int]]":
    """Disjoint spans covering the union of `spans`, in order."""
    out: "list[list[int]]" = []
    for s, t in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def union_ns(spans) -> int:
    return sum(t - s for s, t in union(spans))


def device_plane(plane) -> dict:
    """Module spans, leaf-op spans by class, of one device plane."""
    lines = {ln.name: list(ln.events) for ln in plane.lines}
    modules = [(e.start_ns, e.start_ns + e.duration_ns)
               for e in lines.get("XLA Modules", ())]
    ops = []
    for e in lines.get("XLA Ops", ()):
        head, _, body = e.name.partition(" = ")
        head = head.lstrip("%")
        if _CONTAINER.match(head):
            continue
        ops.append((op_class(head, body), e.start_ns,
                    e.start_ns + e.duration_ns))
    return {"name": plane.name, "modules": modules, "ops": ops}


def reduce(path: str, prefix: str = "bench.") -> dict:
    """``{"devices": [device_plane...], "spans": [(name, start, end)]}``
    of one trace file, every time in the trace's nanoseconds."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            devices.append(device_plane(plane))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(prefix):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    devices.sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def by_class(ops) -> "collections.Counter":
    """Leaf-op nanoseconds per op class."""
    c = collections.Counter()
    for cls, s, t in ops:
        c[cls] += t - s
    return c
