"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode on CPU; `chip_smoke.py` runs the compiled kernels of the
fabric tick against the same oracles on the chip)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cms.nscc import NSCCParams
from repro.kernels import ops, ref
from repro.kernels.ecmp_hash import ecmp_select
from repro.kernels.nack_mark import nack_mark
from repro.kernels.nscc_update import nscc_update
from repro.kernels.queue_write import queue_write
from repro.kernels.sack_bitmap import sack_advance

RNG = np.random.default_rng(7)


@pytest.mark.parametrize("n", [1, 7, 128, 129, 1000, 4096])
def test_nscc_update_matches_ref(n):
    cwnd = jnp.asarray(RNG.uniform(1, 48, n), jnp.float32)
    ecn = jnp.asarray(RNG.integers(0, 2, n), jnp.int32)
    rtt = jnp.asarray(RNG.uniform(0.5, 60, n), jnp.float32)
    cnt = jnp.asarray(RNG.integers(0, 5, n), jnp.int32)
    a = nscc_update(cwnd, ecn, rtt, cnt, interpret=True)
    b = ref.nscc_update_ref(cwnd, ecn, rtt, cnt, NSCCParams())
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


@pytest.mark.parametrize("params", [
    NSCCParams(), NSCCParams(base_rtt=20.0, md=0.3),
    NSCCParams(max_cwnd=128.0, quick_gain=1.5),
])
def test_nscc_update_param_sweep(params):
    n = 512
    cwnd = jnp.asarray(RNG.uniform(params.min_cwnd, params.max_cwnd, n),
                       jnp.float32)
    ecn = jnp.asarray(RNG.integers(0, 2, n), jnp.int32)
    rtt = jnp.asarray(RNG.uniform(0.5, 80, n), jnp.float32)
    cnt = jnp.asarray(RNG.integers(0, 3, n), jnp.int32)
    a = nscc_update(cwnd, ecn, rtt, cnt, params, interpret=True)
    b = ref.nscc_update_ref(cwnd, ecn, rtt, cnt, params)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    assert (np.asarray(a) >= params.min_cwnd - 1e-6).all()
    assert (np.asarray(a) <= params.max_cwnd + 1e-6).all()


@pytest.mark.parametrize("n,w", [(1, 2), (5, 4), (64, 16), (300, 32),
                                 (1000, 8)])
def test_sack_advance_matches_ref(n, w):
    ring = jnp.asarray(
        RNG.integers(0, 2 ** 32, (n, w), dtype=np.uint32))
    base = jnp.asarray(RNG.integers(0, 10000, n, dtype=np.uint32))
    r1, b1, a1 = sack_advance(ring, base, interpret=True)
    r2, b2, a2 = ref.sack_advance_ref(ring, base)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))


def test_sack_advance_edge_cases():
    # all-ones rows advance the full window; all-zero rows advance 0
    ring = jnp.stack([jnp.full((8,), 0xFFFFFFFF, jnp.uint32),
                      jnp.zeros((8,), jnp.uint32),
                      jnp.asarray([1, 0, 0, 0, 0, 0, 0, 0], jnp.uint32)])
    base = jnp.zeros((3,), jnp.uint32)
    r, b, a = sack_advance(ring, base, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), [256, 0, 1])
    np.testing.assert_array_equal(np.asarray(b), [256, 0, 1])
    assert int(np.asarray(r)[0].sum()) == 0


@pytest.mark.parametrize("n", [3, 500, 4096])
@pytest.mark.parametrize("fanout", [2, 4, 7, 8, 13, 16])
def test_ecmp_select_matches_ref(n, fanout):
    src = jnp.asarray(RNG.integers(0, 1 << 20, n), jnp.int32)
    dst = jnp.asarray(RNG.integers(0, 1 << 20, n), jnp.int32)
    ev = jnp.asarray(RNG.integers(0, 65536, n), jnp.int32)
    salt = jnp.asarray(RNG.integers(0, 256, n), jnp.int32)
    a = ecmp_select(src, dst, ev, salt, fanout, interpret=True)
    b = ref.ecmp_hash_ref(src, dst, ev, salt, fanout)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(a) >= 0).all() and (np.asarray(a) < fanout).all()


def test_ecmp_determinism_and_spread():
    """Same EV => same port; the port histogram over EVs is well mixed."""
    n = 1 << 14
    ev = jnp.arange(n, dtype=jnp.int32)
    src = jnp.zeros((n,), jnp.int32)
    dst = jnp.ones((n,), jnp.int32)
    salt = jnp.full((n,), 3, jnp.int32)
    p1 = ecmp_select(src, dst, ev, salt, 4, interpret=True)
    p2 = ecmp_select(src, dst, ev, salt, 4, interpret=True)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    hist = np.bincount(np.asarray(p1), minlength=4) / n
    np.testing.assert_allclose(hist, 0.25, atol=0.02)


@pytest.mark.parametrize("f,w,lanes,same", [
    pytest.param(1, 2, 5, False, id="1-2-5"),
    pytest.param(9, 16, 64, False, id="9-16-64"),
    pytest.param(130, 4, 300, False, id="130-4-300"),
    # the benchmark ring's widths: 480 flows, 16 ring words, Q + 2F lanes
    pytest.param(480, 16, 1280, False, id="480-16-1280"),
    # partial row and lane blocks; two row blocks
    pytest.param(993, 16, 2306, False, id="993-16-2306"),
    pytest.param(1100, 8, 300, False, id="1100-8-300"),
    # every lane sets one bit: a hit count of L still thresholds to 1
    pytest.param(480, 16, 1280, True, id="480-16-1280-same"),
])
def test_nack_mark_matches_ref(f, w, lanes, same):
    rtx = jnp.asarray(RNG.integers(0, 2 ** 32, (f, w), dtype=np.uint32))
    flow = jnp.asarray(RNG.integers(-2, f + 2, lanes), jnp.int32)
    off = jnp.asarray(RNG.integers(-4, w * 32 + 8, lanes), jnp.int32)
    valid = jnp.asarray(RNG.integers(0, 2, lanes).astype(bool))
    if same:
        flow, off = (jnp.full((lanes,), v, jnp.int32)
                     for v in RNG.integers(0, [f, w * 32]))
        valid = jnp.ones((lanes,), bool)
    # the fabric always hands the kernel in-range rows/offsets; clip the
    # sweep the same way so both paths see the contract inputs
    valid = valid & (flow >= 0) & (flow < f) & (off >= 0) & (off < w * 32)
    a = nack_mark(rtx, flow, off, valid, interpret=True)
    b = ref.nack_mark_ref(rtx, flow, off, valid)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_nack_mark_or_semantics_with_duplicates():
    """Two lanes carrying the SAME (flow, offset) must set the bit once
    (OR, not add) — the packet + its retransmission trimmed in one tick."""
    rtx = jnp.zeros((3, 2), jnp.uint32)
    flow = jnp.asarray([1, 1, 1, 2, 0], jnp.int32)
    off = jnp.asarray([5, 5, 37, 0, 63], jnp.int32)
    valid = jnp.asarray([True, True, True, True, False])
    for mark in (ops.nack_mark, ref.nack_mark_ref,
                 lambda *a: nack_mark(*a, interpret=True)):
        out = np.asarray(mark(rtx, flow, off, valid))
        assert out[1, 0] == 1 << 5
        assert out[1, 1] == 1 << (37 - 32)
        assert out[2, 0] == 1
        assert out[0].sum() == 0, "invalid lane must mark nothing"


def test_nack_mark_preserves_existing_bits():
    rtx = jnp.full((2, 2), 0x80000001, jnp.uint32)
    out = np.asarray(nack_mark(
        rtx, jnp.asarray([0], jnp.int32), jnp.asarray([1], jnp.int32),
        jnp.asarray([True]), interpret=True))
    assert out[0, 0] == 0x80000003 and out[1, 0] == 0x80000001


def _enqueue(rng, kind, q=12, n=40, c=8):
    """One tick's enqueue, as the fabric's enqueue phase builds it: each
    candidate's target queue (or none), its arrival rank there, and the
    slot it writes where it fits; `kind` picks the hard case."""
    cq = np.where(rng.random(n) < 0.3, -1, rng.integers(0, q, n))
    q_len = rng.integers(0, c + 1, q)
    q_head = rng.integers(0, c, q)
    cand = rng.integers(-2 ** 31, 2 ** 31, (n, 5), dtype=np.int64)
    if kind == "overflow":            # queue 0 empty to full, 1 nearly full
        cq[:c + 3] = 0
        cq[c + 3:c + 6] = 1
        q_len[:2] = 0, c - 1
    elif kind == "wrap":              # every ring's next slot wraps past C
        q_head[:] = c - 1
        q_len[:] = rng.integers(0, 3, q)
    elif kind == "extremes":
        cand = rng.choice([-1, 0, 2 ** 31 - 1, -(2 ** 31 - 1), -2 ** 31],
                          (n, 5))
    elif kind == "none":
        cq[:] = -1
    elif kind == "one_queue":         # every candidate into queue 3
        cq[:] = 3
        q_len[3] = 0
    rank = np.array([(cq[:i] == cq[i]).sum() for i in range(n)])
    safe = np.where(cq >= 0, cq, 0)
    pos = q_len[safe] + rank
    fits = (cq >= 0) & (pos < c)
    wslot = (q_head[safe] + pos) % c
    tq = np.where(fits, cq, q)
    q_pkt = rng.integers(-2 ** 31, 2 ** 31, (q, c, 5), dtype=np.int64)
    return q_pkt, tq, wslot, cand


@pytest.mark.parametrize("kind", ["random", "overflow", "wrap", "extremes",
                                  "none", "one_queue"])
def test_queue_write_dense_matches_the_scatter(kind):
    """The enqueue write's dense path (one-hot MXU contraction over byte
    limbs) against the scatter oracle, bit for bit, vmapped over 4."""
    rng = np.random.default_rng(sum(map(ord, kind)))
    args = [jnp.asarray(np.stack(a), jnp.int32) for a in
            zip(*(_enqueue(rng, kind) for _ in range(4)))]
    want = jax.vmap(ref.queue_write_ref)(*args)
    got = jax.jit(jax.vmap(queue_write))(*args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if kind == "none":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(args[0]))


@pytest.mark.parametrize("dense,bound,scatters", [
    (False, ops.DENSE_WRITE_MAX, True),
    (True, ops.DENSE_WRITE_MAX, False),
    (True, 12 * 40 * 8 - 1, True),
])
def test_queue_write_path_follows_backend_and_shape(dense, bound, scatters,
                                                    monkeypatch):
    """The scatter off the TPU, and on it past the shape bound; the
    contraction on it under the bound."""
    monkeypatch.setattr(ops, "_dense_queue_write", lambda: dense)
    monkeypatch.setattr(ops, "DENSE_WRITE_MAX", bound)
    args = [jnp.asarray(a, jnp.int32) for a in
            _enqueue(np.random.default_rng(0), "random")]
    # a fresh function each time: JAX caches a function's traces
    text = str(jax.make_jaxpr(lambda *a: ops.queue_write(*a))(*args))
    assert ("scatter" in text) == scatters
    assert ("dot_general" in text) != scatters
