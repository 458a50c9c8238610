"""Unit + property tests for the UET core: headers, addressing, matching,
messaging cost model, PDC state machine, PSN/SACK tracking, TSS rules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import addressing, headers, matching, messaging, pdc, pds
from repro.core.types import (MsgProtocol, Profile, TransportMode,
                              UET_UDP_PORT)


# ---------------------------------------------------------------- headers
def test_uet_udp_port_is_rocev2_plus_two_and_prime():
    assert UET_UDP_PORT == 4793 == 4791 + 2
    assert all(UET_UDP_PORT % k for k in range(2, int(4793 ** 0.5) + 1))


def test_header_byte_model_matches_spec_table():
    """Sec. 3.2.2: PDS 12B RUD/ROD (16 w/ RCCC), 8B RUDI, 4B UUD; SES
    44/32/20B; TSS 12B (+16B ICV); Ethernet 14+4."""
    h = headers.HeaderConfig()  # RUD / UDP / IPv4 / SES std
    assert h.overhead_bytes() == 14 + 4 + 20 + 8 + 12 + 44
    rcc = headers.HeaderConfig(rccc=True)
    assert rcc.overhead_bytes() - h.overhead_bytes() == 4
    uud = headers.HeaderConfig(mode=TransportMode.UUD,
                               ses=headers.SES_HEADER_MIN)
    assert uud.overhead_bytes() == 14 + 4 + 20 + 8 + 4 + 20
    rudi = headers.HeaderConfig(mode=TransportMode.RUDI,
                                ses=headers.SES_HEADER_MIN)
    assert rudi.overhead_bytes() - uud.overhead_bytes() == 4
    tss = headers.HeaderConfig(tss=True)
    assert tss.overhead_bytes() - h.overhead_bytes() == 12 + 16
    native = headers.HeaderConfig(native_ip=True)
    assert h.overhead_bytes() - native.overhead_bytes() == 4  # 8B UDP -> 4B EV
    crc = headers.HeaderConfig(e2e_crc=True)
    assert crc.overhead_bytes() - h.overhead_bytes() == 4


@given(payload=st.integers(min_value=64, max_value=9000))
def test_header_efficiency_monotone(payload):
    h = headers.HeaderConfig()
    assert 0 < h.efficiency(payload) < 1
    assert h.efficiency(payload + 64) > h.efficiency(payload)


# ------------------------------------------------------------- addressing
def test_relative_addressing_resolves_and_authorizes():
    t = addressing.FEPTables.create(num_jobs=4, procs_per_job=8,
                                    ris_per_proc=4)
    ris = jnp.arange(8 * 4, dtype=jnp.int32).reshape(8, 4) + 100
    t = addressing.register_job(t, 1, jobid=0xABCDE,
                                proc_ids=jnp.arange(8), ri_contexts=ris)
    ctx, ok = addressing.resolve(
        t,
        jobid=jnp.array([0xABCDE, 0xABCDE, 0xDEAD]),
        pid_on_fep=jnp.array([3, 99, 0]),
        ri=jnp.array([2, 0, 0]),
        rel=jnp.array([1, 1, 1]))
    assert bool(ok[0]) and int(ctx[0]) == 100 + 3 * 4 + 2
    assert not bool(ok[1])   # PIDonFEP out of range
    assert not bool(ok[2])   # unknown JobID => unauthorized
    assert int(ctx[1]) == -1 and int(ctx[2]) == -1


def test_absolute_addressing_service_table():
    t = addressing.FEPTables.create(2, 2, 2, num_services=16)
    t = addressing.FEPTables(
        t.jobid_keys, t.jobid_to_pid, t.pid_table, t.ri_table,
        t.service_table.at[5].set(777))
    ctx, ok = addressing.resolve(
        t, jobid=jnp.array([0]), pid_on_fep=jnp.array([5]),
        ri=jnp.array([0]), rel=jnp.array([0]))
    assert bool(ok[0]) and int(ctx[0]) == 777


def test_directory_scaling_claim():
    """Sec. 3.1.1: relative addressing stores N entries, not N*P."""
    assert addressing.directory_entries(10_000, 1000, relative=True) == 10_000
    assert addressing.directory_entries(10_000, 1000,
                                        relative=False) == 10_000_000


# ---------------------------------------------------------------- matching
def test_exact_match_and_consume():
    q = matching.RecvQueue.create(8)
    hi, lo = matching.encode_match_key(3, 42, 7)
    q = matching.post_receive(q, 0, hi, lo, 0, 0, matching.ANY_INITIATOR,
                              seq=0, buffer_id=11)
    slot, ok = matching.match(q, jnp.array([hi]), jnp.array([lo]),
                              jnp.array([1], jnp.uint32), Profile.AI_FULL)
    assert bool(ok[0]) and int(slot[0]) == 0
    q = matching.consume(q, slot[0], ok[0])
    slot2, ok2 = matching.match(q, jnp.array([hi]), jnp.array([lo]),
                                jnp.array([1], jnp.uint32), Profile.AI_FULL)
    assert not bool(ok2[0])  # consumed => unexpected now


def test_hpc_wildcard_in_order():
    """HPC: lowest posting order wins among wildcard matches."""
    q = matching.RecvQueue.create(8)
    mh, ml = matching.wildcard_mask(match_tag=False, match_seq=False)
    bh, bl = matching.encode_match_key(1, 0, 0)
    q = matching.post_receive(q, 3, bh, bl, mh, ml, matching.ANY_INITIATOR,
                              seq=5, buffer_id=1)
    q = matching.post_receive(q, 1, bh, bl, mh, ml, matching.ANY_INITIATOR,
                              seq=2, buffer_id=2)
    th, tl = matching.encode_match_key(1, 77, 9)
    slot, ok = matching.match(q, jnp.array([th]), jnp.array([tl]),
                              jnp.array([0], jnp.uint32), Profile.HPC)
    assert bool(ok[0]) and int(slot[0]) == 1  # seq=2 posted earlier


def test_ai_full_rejects_wildcards():
    q = matching.RecvQueue.create(4)
    mh, ml = matching.wildcard_mask(match_tag=False)
    bh, bl = matching.encode_match_key(1, 0, 0)
    q = matching.post_receive(q, 0, bh, bl, mh, ml, matching.ANY_INITIATOR,
                              0, 1)
    th, tl = matching.encode_match_key(1, 5, 0)
    _, ok = matching.match(q, jnp.array([th]), jnp.array([tl]),
                           jnp.array([0], jnp.uint32), Profile.AI_FULL)
    assert not bool(ok[0])


@given(comm=st.integers(0, 0xFFFF), tag=st.integers(0, 0xFFFFFF),
       seq=st.integers(0, 0xFFFFFF))
@settings(max_examples=50)
def test_match_key_roundtrip_distinct(comm, tag, seq):
    """The in-order-over-unordered trick (Sec. 3.2.1): distinct message
    seqs produce distinct keys, so unordered RUD still fills in order."""
    hi1, lo1 = matching.encode_match_key(comm, tag, seq)
    hi2, lo2 = matching.encode_match_key(comm, tag, (seq + 1) & 0xFFFFFF)
    assert (int(hi1), int(lo1)) != (int(hi2), int(lo2))


# -------------------------------------------------------------- messaging
@pytest.mark.parametrize("proto", list(MsgProtocol))
@pytest.mark.parametrize("expected", [True, False])
def test_completion_time_table(proto, expected):
    """Sec. 3.1.3 table: playout equals the alpha/beta model for all six
    (protocol x expectedness) cells."""
    link = messaging.LinkModel(alpha=2.0, beta=0.05)
    size = 400.0
    ts, tr = (10.0, 4.0) if expected else (4.0, 30.0)
    model = messaging.model_completion(proto, expected, size, ts, tr, link)
    sim = messaging.simulate_protocol(proto, size, ts, tr, link,
                                      eager_limit=1000.0)
    assert abs(model - sim.receiver_complete) < 1e-9


def test_receiver_initiated_extra_rtt():
    """AI Base pays +2 alpha in the expected case vs rendezvous."""
    link = messaging.LinkModel(alpha=3.0, beta=0.01)
    r = messaging.model_completion(MsgProtocol.RENDEZVOUS, True, 100, 0, 0,
                                   link)
    ri = messaging.model_completion(MsgProtocol.RECEIVER_INITIATED, True,
                                    100, 0, 0, link)
    assert ri - r == pytest.approx(2 * link.alpha)


def test_deferrable_tracks_window():
    out = messaging.deferrable_vs_rendezvous_bandwidth(
        size=1e6, link=messaging.LinkModel(alpha=1e-6, beta=2.5e-12),
        eager_limit=16384.0, true_window=1e6)
    assert out["deferrable"] > out["rendezvous"]
    # the window-paced stall branches live in tests/test_messaging_window.py
    # (this module is hypothesis-gated and skips without dev deps)


# ------------------------------------------------------------------- PDC
def test_pdc_fig6_scenario():
    """Fig. 6: open -> full-rate during SYN -> establish on first ACK ->
    quiesce -> drain -> close."""
    pool = pdc.PDCPool.create(4)
    pool = pdc.open_pdc(pool, jnp.int32(0), jnp.int32(7), jnp.uint32(4))
    assert int(pool.state[0]) == pdc.PDCState.SYN
    assert bool(pdc.may_send_data(pool.state)[0])  # full rate during SYN!
    pool = pdc.on_ack(pool, jnp.int32(0), jnp.int32(19), jnp.int32(1))
    assert int(pool.state[0]) == pdc.PDCState.ESTABLISHED
    assert int(pool.remote_id[0]) == 19
    st = pdc.step_initiator(pool.state[:1],
                            jnp.array([int(pdc.InitEvent.CLOSE_REQ)]))
    assert int(st[0]) == pdc.PDCState.QUIESCE
    assert not bool(pdc.may_accept_new_message(st)[0])
    st = pdc.step_initiator(st, jnp.array([int(pdc.InitEvent.DRAINED)]))
    assert int(st[0]) == pdc.PDCState.ACK_WAIT
    st = pdc.step_initiator(st, jnp.array([int(pdc.InitEvent.CLOSE_ACK)]))
    assert int(st[0]) == pdc.PDCState.CLOSED


def test_pdc_target_machine():
    st = jnp.array([int(pdc.PDCState.CLOSED)])
    st = pdc.step_target(st, jnp.array([int(pdc.TgtEvent.RX_SYN)]))
    assert int(st[0]) == pdc.PDCState.SYN
    st = pdc.step_target(st, jnp.array([int(pdc.TgtEvent.RX_NOSYN)]))
    assert int(st[0]) == pdc.PDCState.ESTABLISHED
    st = pdc.step_target(st, jnp.array([int(pdc.TgtEvent.RX_CLOSE)]))
    assert int(st[0]) == pdc.PDCState.CLOSED


def test_pdc_secure_pending_path():
    """Sec. 3.4.2: secure PSN establishment goes through PENDING."""
    st = jnp.array([int(pdc.PDCState.CLOSED)])
    st = pdc.step_target(st, jnp.array([int(pdc.TgtEvent.SECURE_PENDING)]))
    assert int(st[0]) == pdc.PDCState.PENDING
    st = pdc.step_target(st, jnp.array([int(pdc.TgtEvent.SECURE_OK)]))
    assert int(st[0]) == pdc.PDCState.SYN


# ---------------------------------------------------------------- PSN/SACK
@given(st.lists(st.integers(0, 255), min_size=1, max_size=64, unique=True))
@settings(max_examples=40, deadline=None)
def test_psn_tracker_property(psns):
    """Property: after receiving an arbitrary PSN set, CACK advances to
    exactly the first gap, and every received PSN is marked."""
    t = pds.PSNTracker.create(1, 256)
    arr = jnp.asarray(psns, jnp.uint32)
    t, fresh = pds.record_rx(t, jnp.zeros(len(psns), jnp.int32), arr,
                             jnp.ones(len(psns), bool))
    assert bool(fresh.all())
    t, adv = pds.advance_cack(t)
    expect = 0
    s = set(psns)
    while expect in s:
        expect += 1
    assert int(t.base[0]) == expect
    assert int(adv[0]) == expect


def test_mp_range_rejection():
    """Sec. 3.2.5: PSNs beyond MP_RANGE are not accepted — receiver
    resource protection."""
    t = pds.PSNTracker.create(1, 64)
    t, fresh = pds.record_rx(t, jnp.array([0, 0], jnp.int32),
                             jnp.array([63, 64], jnp.uint32),
                             jnp.ones(2, bool))
    assert bool(fresh[0]) and not bool(fresh[1])
    assert int(t.oor[0]) == 1


def test_duplicate_detection():
    t = pds.PSNTracker.create(1, 64)
    one = jnp.array([5], jnp.uint32)
    t, f1 = pds.record_rx(t, jnp.array([0], jnp.int32), one,
                          jnp.ones(1, bool))
    t, f2 = pds.record_rx(t, jnp.array([0], jnp.int32), one,
                          jnp.ones(1, bool))
    assert bool(f1[0]) and not bool(f2[0])
    assert int(t.dup[0]) == 1


def test_sack_view_and_ooo():
    t = pds.PSNTracker.create(1, 128)
    for p in (0, 1, 5, 9):
        t, _ = pds.record_rx(t, jnp.array([0], jnp.int32),
                             jnp.array([p], jnp.uint32), jnp.ones(1, bool))
    t, adv = pds.advance_cack(t)
    assert int(adv[0]) == 2
    cack, lo, hi = pds.sack_view(t)
    assert int(cack[0]) == 2
    # bits now at offsets 3 (psn 5) and 7 (psn 9)
    assert int(lo[0]) == (1 << 3) | (1 << 7)
    assert int(pds.ooo_distance(t)[0]) == 8


# ------------------------------------------------------------------- DFC
def test_dfc_scales_rccc_credit_rate():
    """Sec. 3.3.4: Destination Flow Control throttles senders below what
    the network could deliver — RCCC grants scale by the destination's
    absorption rate."""
    from repro.core.cms import rccc as R
    st = R.RCCCState.create(4, initial_credit=0.0)
    st = R.mark_seen(st, jnp.arange(4), jnp.ones(4, bool))
    dst = jnp.array([0, 0, 1, 1], jnp.int32)
    active = jnp.ones(4, bool)
    # destination 1 can only absorb half rate (memory pressure)
    dfc = jnp.array([1.0, 0.5], jnp.float32)
    st = R.grant_credits(st, dst, active, num_hosts=2, rate=1.0, dfc=dfc)
    np.testing.assert_allclose(np.asarray(st.balance),
                               [0.5, 0.5, 0.25, 0.25], atol=1e-6)


def test_dfc_nscc_window_penalty():
    """Sec. 3.3.4 NSCC path: the receiver's window penalty scales the
    sender's congestion window."""
    from repro.core.cms import nscc as N
    params = N.NSCCParams()
    st = N.NSCCState.create(4, params)
    st2 = N.apply_dfc_penalty(st, params, jnp.array([1, 2]),
                              jnp.array([0.5, 0.25], jnp.float32),
                              jnp.ones(2, bool))
    np.testing.assert_allclose(np.asarray(st2.cwnd),
                               [64.0, 32.0, 48.0, 64.0], atol=1e-4)


def test_nscc_division_is_ieee_rounded():
    """NSCC's window quotients come from integer long division, so they
    are the IEEE 754 float32 quotient, bit for bit, on any backend."""
    from repro.core.cms.nscc import div
    rng = np.random.default_rng(7)
    n = 200_000
    a = np.concatenate([
        rng.uniform(-1e4, 1e4, n),
        np.exp(rng.uniform(-20, 20, n)) * rng.choice([-1, 1], n),
        np.arange(40000) - 12.5,                   # overload, gap numerators
        np.ones(n),                                # gentle: ai / cwnd
        rng.integers(0, 5000, n),                  # Quick Adapt's delivered
        [0.0, -0.0, 1.0, 3.0]]).astype(np.float32)
    b = np.concatenate([
        rng.uniform(1e-6, 1e4, n), np.exp(rng.uniform(-13, 20, n)),
        np.maximum(np.arange(40000), 1e-6), rng.uniform(1, 48, n),
        rng.integers(1, 10000, n), [2.0, 2.0, 3.0, 1.0]]).astype(np.float32)
    want = (a / b).astype(np.float32)
    got = np.asarray(jax.jit(div)(a, b))
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
