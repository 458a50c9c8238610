"""Compile the fabric tick's Pallas kernels for a TPU v5e without one.

JAX's TPU compiler compiles for a described chip with none attached: it
refuses what interpret mode accepts (unsigned reductions, value-level
scatters, lane-splitting reshapes, too much fast memory). So the three
hot-path kernels, and the batched stats driver with them switched on,
are compiled at the widths `chip_smoke.py` runs on the chip: the
64-rank tree all-reduce on ``paper_fig2()`` (126 flows, 16 ring words,
572 NACK lanes), vmapped over its 128 scenarios; `nack_mark` at the
ring all-reduce's widths (16 and 32 ranks), vmapped over 8; and the
batched driver at the benchmark ring cells' widths, whose enqueue write
must be the dense contraction. Nothing runs, so these say nothing of
results or times.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and
every test worker imports every test file.

The file also runs `chip_smoke.py`'s phases at a tiny size on the CPU
and checks that its ``main`` refuses a host without a TPU.
"""
import importlib.util
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.nack_mark import nack_mark
from repro.kernels.sack_bitmap import sack_advance
from repro.kernels.sack_fused import sack_fused
from repro.network import fabric
from repro.network.collectives import CollectiveSpec, build_workload
from repro.network.faults import FaultSchedule
from repro.network.topology import leaf_spine, paper_fig2

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # bench

TPU_CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def chip_smoke():
    """The repo-root script, imported as a module."""
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod            # its dataclass resolves here
    spec.loader.exec_module(mod)
    yield mod
    del sys.modules[spec.name]


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def smoke_batch(chip_smoke):
    """The chip smoke's phase-(b) batch (host-side description only)."""
    cs = chip_smoke
    return cs.make_batch(paper_fig2(), cs.RANKS, cs.SIZE_PKTS, cs.SEEDS,
                         cs.BUDGET, cs.FLAP, cs.GRAY_P)


@pytest.fixture(scope="module")
def smoke_widths(chip_smoke, smoke_batch):
    """(B, F, ring words, NACK lanes) the smoke runs the kernels at."""
    F = int(smoke_batch.workload().src.shape[0])
    return (smoke_batch.size, F, smoke_batch.params.mp_range // 32,
            chip_smoke.nack_lanes(smoke_batch.g, F))


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kernel", ["sack_fused", "nack_mark",
                                    "sack_advance"])
def test_kernel_compiles_for_v5e(kernel, one_chip, smoke_widths):
    """Each hot-path kernel compiles for one v5e chip at the smoke's
    widths, alone and vmapped over its scenario batch."""
    B, F, W, L = smoke_widths
    u32, i32 = jnp.uint32, jnp.int32
    fn, shapes = {
        "sack_fused": (sack_fused, [((F, W), u32), ((F,), u32),
                                    ((F, W), u32), ((F, W), u32)]),
        "nack_mark": (nack_mark, [((F, W), u32), ((L,), i32),
                                  ((L,), i32), ((L,), jnp.bool_)]),
        "sack_advance": (sack_advance, [((F, W), u32), ((F,), u32)]),
    }[kernel]
    for batch in ((), (B,)):
        args = [_shape(one_chip, batch + s, d) for s, d in shapes]
        f = jax.vmap(fn) if batch else fn
        text = jax.jit(f).lower(*args).compile().as_text()
        assert TPU_CUSTOM_CALL in text, (kernel, batch)


@pytest.mark.parametrize("F,W,L", [
    (480, 16, 1280),       # the benchmark's 16-rank ring all-reduce
    (1984, 16, 4288),      # the same ring over 32 ranks
])
def test_nack_mark_compiles_for_v5e_at_ring_widths(F, W, L, one_chip):
    """`nack_mark` vmapped over a batch of 8 at the ring all-reduce's
    widths fits the chip's default 16 MB of scoped VMEM."""
    B = 8
    args = [_shape(one_chip, (B,) + s, d) for s, d in
            [((F, W), jnp.uint32), ((L,), jnp.int32), ((L,), jnp.int32),
             ((L,), jnp.bool_)]]
    text = jax.jit(jax.vmap(nack_mark)).lower(*args).compile().as_text()
    assert TPU_CUSTOM_CALL in text


def _compile_driver(one_chip, b, wls, fault):
    """The batched stats driver of `b` over `wls`, gray-link draw
    compiled in, with the ops steered as on a TPU backend (the caller's
    monkeypatch), compiled for one v5e; it must fit the chip's HBM."""
    init_fn, run = fabric._build_fns(
        b.g, b.profile, b.params, int(wls.src.shape[1]), batched=True,
        trace="stats", lossy=True)
    s0 = jax.eval_shape(init_fn, wls, jnp.asarray(b.seeds))
    args = jax.tree_util.tree_map(
        lambda x: _shape(one_chip, x.shape, x.dtype),
        (s0, wls, fault, jnp.int32(0), jnp.int32(0), jnp.int32(0)))
    compiled = jax.jit(run, donate_argnums=(0,)).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < V5E_HBM_BYTES
    return compiled.as_text()


def test_batched_stats_driver_compiles_with_kernels(one_chip, smoke_batch,
                                                    monkeypatch):
    """The chip smoke's executable: the batched stats driver over its 128
    paper_fig2 all-reduce scenarios, gray-link draw compiled in, with the
    ops steered to the compiled kernels as on a TPU backend."""
    monkeypatch.setattr(ops, "_compiled_kernels", lambda: True)
    text = _compile_driver(one_chip, smoke_batch, *smoke_batch.stacked())
    # each kernel in both branches of the fast/masked chunk cond
    assert text.count(TPU_CUSTOM_CALL) == 6


def test_ring_cell_driver_writes_its_queues_without_a_scatter(
        one_chip, chip_smoke, monkeypatch):
    """The benchmark ring cells' executable (NCCL's ring all-reduce over
    16 ranks on paper_fig2: F=480, Q=320, C=64, a batch of 8), ops
    steered as on a TPU backend: the enqueue write is the dense one-hot
    contraction, no scatter under ``tick.enqueue.write``."""
    cs = chip_smoke
    b = cs.make_batch(paper_fig2(), 16, 64, 2, cs.BUDGET, cs.FLAP, cs.GRAY_P)
    spec = CollectiveSpec("all_reduce", tuple(range(0, 64, 4)), 1024)
    wls = fabric.Workload.stack([build_workload(spec, "ring")] * b.size)
    assert (wls.src.shape, b.g.num_queues) == ((8, 480), 320)
    monkeypatch.setattr(ops, "_compiled_kernels", lambda: True)
    text = _compile_driver(one_chip, b, wls, FaultSchedule.stack(b.faults))
    write = [ln for ln in text.splitlines() if "tick.enqueue.write" in ln]
    assert any(" convolution(" in ln or " dot(" in ln for ln in write)
    assert not [s for s in _scatter_scopes(text)
                if (s or "").startswith("tick.enqueue.write")]


def _scatter_scopes(text: str) -> list:
    """The scope the benchmark's trace reader gives each scatter of a
    compiled module: its own, or that of the fusion that calls it (a
    TPU scatter sits in a fusion whose op_name may be the only one)."""
    from bench import phase_reduce

    scopes = phase_reduce.hlo_scopes(text)
    comp, held, out = None, set(), []
    for line in text.splitlines():
        head = re.match(r"\s*(?:ENTRY\s+)?%([\w.\-]+)\s*\(.*\{\s*$", line)
        if head:
            comp = head.group(1)
        elif re.match(r"\s*(?:ROOT\s+)?%[\w.\-]+\s*=.*\sscatter\(", line):
            held.add(comp)
            out.append(scopes.get(line.split("=")[0].split("%")[1].strip()))
    for line in text.splitlines():
        call = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+)\s*=.*calls=%([\w.\-]+)",
                        line)
        if call and call.group(2) in held:
            out.append(scopes.get(call.group(1)))
    return out


def test_chip_smoke_phases_tiny_on_cpu(chip_smoke):
    """chip_smoke.py's three phases at a tiny size: control flow, checks
    and the sharded device placement (4 virtual CPU devices, conftest)."""
    cs = chip_smoke
    g = leaf_spine(leaves=2, spines=2, hosts_per_leaf=2)
    out = cs.kernel_phase(6, 4, cs.nack_lanes(g, 6), 3,
                          expect_kernels=False)
    assert all(v["bitwise_equal"] for v in out.values())
    b = cs.make_batch(g, ranks=4, size_pkts=32, n_seeds=2, budget=4096,
                      flap=(20, 120), gray_p=0.05)
    assert b.size == 8 and len(set(b.names)) == 4
    res = cs.main_path_phase(b, expect_kernels=False)
    assert 0 < res["ticks"] < 4096
    res = cs.sharded_phase(b, 4)
    assert res["devices"] == [d.id for d in jax.devices()[:4]]


def test_chip_smoke_refuses_a_host_without_tpu(chip_smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "'cpu'" in err and '"ok"' not in out
