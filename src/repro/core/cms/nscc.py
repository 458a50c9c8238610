"""Network Signal-based Congestion Control (Sec. 3.3.1).

NSCC runs a control loop at the *source*, combining two signals per ACK:

* ECN — a fast statistical 1-bit signal, marked at switch **egress** (the
  spec's departure from RFC 3168) so it skips the queue it describes;
* RTT — a lagging multi-bit signal measured request->response, excluding
  receiver service time.

Four cases on each arriving ACK (paper's enumeration):

  1. ECN && low RTT   -> congestion *building*: do not react.
  2. ECN && high RTT  -> congested/overloaded: aggressive multiplicative
                         decrease per incoming ACK.
  3. !ECN && low RTT  -> underloaded: quick increase, sized by the gap
                         between measured and expected RTT.
  4. !ECN && high RTT -> congestion draining: gentle additive increase.

Plus **Quick Adapt (QA)**: on packet-loss evidence (e.g. trimming NACKs),
once per RTT-epoch rescale the window to the fraction of traffic actually
delivered — the incast fast path.

All state is SoA over congestion-control contexts (CCCs) and the update is
a pure function over a batch of ACKs, so one call services every CCC in
one fused op — mirroring a hardware NIC pipeline. The Pallas kernel in
repro/kernels/nscc_update.py implements `nscc_update` blockwise; this
module is the reference semantics (its `ref.py` re-exports from here).

Windows are measured in MTU packet units (float32); the fabric simulator
works in packet-time ticks.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp


def div(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a / b`` in float32, rounded to nearest even as IEEE 754 says, on
    every backend. A TPU's float32 divide is not correctly rounded (an
    ulp off on about a third of inputs), so a window updated with ``/``
    there drifts from the same scenario's window on a CPU. The backend's
    divide only estimates the quotient of the significands; the exact
    remainder (integer products, wrapped to 32 bits: the remainder is
    small) corrects it and decides the rounding. No integer divide: a
    TPU emulates those at length. Domain: `a` zero or normal, `b`
    positive and normal, the quotient normal (every window quotient
    below is).
    """
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    u32, i32, f32 = jnp.uint32, jnp.int32, jnp.float32
    ab = jax.lax.bitcast_convert_type(a, u32)
    bb = jax.lax.bitcast_convert_type(b, u32)
    ea = ((ab >> 23) & 0xFF).astype(i32)
    eb = ((bb >> 23) & 0xFF).astype(i32)
    ma = (ab & 0x7FFFFF) | 0x800000
    mb = (bb & 0x7FFFFF) | 0x800000
    # scale ma into [mb, 2 mb): the quotient's leading bit is then 1
    low = ma < mb
    ma = jnp.where(low, ma << 1, ma)
    e = ea - eb + 127 - low.astype(i32)
    # c ~ floor(ma 2^23 / mb) within a few units; r = ma 2^23 - c mb is
    # exact mod 2^32 and |r| < 2^31, so exact
    c = jnp.floor(ma.astype(f32) / mb.astype(f32) * 2.0 ** 23).astype(u32)
    r = ((ma << 23) - c * mb).astype(i32)
    k = jnp.floor(r.astype(f32) / mb.astype(f32)).astype(i32)
    c, r = c + k.astype(u32), r - k * mb.astype(i32)
    for _ in range(2):                     # k may be one off either way
        neg = r < 0
        c, r = jnp.where(neg, c - 1, c), jnp.where(neg, r + mb.astype(i32), r)
        big = r >= mb.astype(i32)
        c, r = jnp.where(big, c + 1, c), jnp.where(big, r - mb.astype(i32), r)
    r2 = r << 1
    mbi = mb.astype(i32)
    q = c + ((r2 > mbi) | ((r2 == mbi) & ((c & 1) == 1))).astype(u32)
    carry = q >> 24                        # rounded up to 2^24
    q, e = q >> carry, e + carry.astype(i32)
    bits = (ab & u32(0x80000000)) | (e.astype(u32) << 23) | (q & 0x7FFFFF)
    return jnp.where(a == 0, a, jax.lax.bitcast_convert_type(bits, f32))


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class NSCCParams:
    """Control-loop gains. Class-level defaults; tune via replace()."""

    base_rtt: float = 8.0        # unloaded RTT estimate, ticks
    target_factor: float = 1.25  # high/low RTT threshold = base_rtt * this
    md: float = 0.65             # case-2 multiplicative decrease per ACK
    quick_gain: float = 0.60     # case-3 increase gain (packets per ACK max)
    ai: float = 1.0              # case-4 additive increase (pkts per cwnd ACKs)
    min_cwnd: float = 1.0
    max_cwnd: float = 64.0       # slightly above BDP; optimistic start value
    qa_min_frac: float = 0.125   # QA floor as a fraction of max_cwnd


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class NSCCState:
    """Per-CCC state (SoA over N contexts).

    cwnd:        [N] float32 congestion window, packets
    epoch_acked: [N] int32 packets delivered in current QA epoch
    epoch_lost:  [N] int32 packets reported lost in current QA epoch
    epoch_tick:  [N] int32 tick when the current QA epoch started
    """

    cwnd: jax.Array
    epoch_acked: jax.Array
    epoch_lost: jax.Array
    epoch_tick: jax.Array

    @staticmethod
    def create(n: int, params: NSCCParams) -> "NSCCState":
        # Optimistic start: window at/near BDP, i.e. start at full rate
        # (Sec. 3.3.3 "Both RCCC and NSCC ... start at full rate").
        return NSCCState(
            cwnd=jnp.full((n,), params.max_cwnd, jnp.float32),
            epoch_acked=jnp.zeros((n,), jnp.int32),
            epoch_lost=jnp.zeros((n,), jnp.int32),
            epoch_tick=jnp.zeros((n,), jnp.int32),
        )


def classify(ecn: jax.Array, rtt: jax.Array, params: NSCCParams) -> jax.Array:
    """Return the paper's case number (1..4) per ACK."""
    high = rtt > params.base_rtt * params.target_factor
    return jnp.where(ecn, jnp.where(high, 2, 1), jnp.where(high, 4, 3))


def window_delta(cwnd: jax.Array, ecn: jax.Array, rtt: jax.Array,
                 params: NSCCParams) -> jax.Array:
    """Per-ACK window adjustment (packets); the four-case core.

    Vectorized over ACKs; `cwnd` is the current window of the ACK's CCC.
    """
    target = params.base_rtt * params.target_factor
    high = rtt > target
    # case 2: aggressive MD proportional to RTT excess, per incoming ACK
    overload = jnp.clip(div(rtt - target, jnp.maximum(rtt, 1e-6)), 0.0,
                        1.0)
    dec = -params.md * overload  # packets per ACK
    # case 3: quick increase guessing from measured vs expected RTT
    gap = jnp.clip(div(target - rtt, target), 0.0, 1.0)
    quick = params.quick_gain * gap
    # case 4: gentle additive increase (+ai per full window of ACKs)
    gentle = div(params.ai, jnp.maximum(cwnd, 1.0))
    return jnp.where(ecn, jnp.where(high, dec, 0.0),
                     jnp.where(high, gentle, quick))


def on_acks(state: NSCCState, params: NSCCParams, ccc: jax.Array,
            ecn: jax.Array, rtt: jax.Array,
            valid: jax.Array) -> NSCCState:
    """Apply a batch of ACKs: ccc [B] int32, ecn [B] bool, rtt [B] float32.

    Multiple ACKs may target the same CCC in one batch; deltas accumulate
    via scatter-add (order-independent by construction).
    """
    cw = state.cwnd[ccc]
    delta = window_delta(cw, ecn, rtt.astype(jnp.float32), params)
    delta = jnp.where(valid, delta, 0.0)
    n = state.cwnd.shape[0]
    drop = jnp.where(valid, ccc, n)  # OOB -> dropped
    cwnd = state.cwnd.at[drop].add(delta, mode="drop")
    cwnd = jnp.clip(cwnd, params.min_cwnd, params.max_cwnd)
    acked = state.epoch_acked.at[drop].add(
        jnp.where(valid, 1, 0), mode="drop")
    return replace(state, cwnd=cwnd, epoch_acked=acked)


def on_ack_per_flow(state: NSCCState, params: NSCCParams, ecn: jax.Array,
                    rtt: jax.Array, active: jax.Array) -> NSCCState:
    """Dense variant of `on_acks` for the one-ACK-per-CCC-per-round case
    (the fabric tick: one host downlink per destination): ecn/rtt/active
    are [N] per-CCC lanes, so the update is pure elementwise — no
    scatter. Matches `on_acks` exactly when each CCC has <= 1 valid lane.
    """
    delta = window_delta(state.cwnd, ecn, rtt.astype(jnp.float32), params)
    cwnd = jnp.where(active, state.cwnd + delta, state.cwnd)
    return replace(
        state,
        cwnd=jnp.clip(cwnd, params.min_cwnd, params.max_cwnd),
        epoch_acked=state.epoch_acked + active.astype(jnp.int32),
    )


def on_loss_per_flow(state: NSCCState, count: jax.Array) -> NSCCState:
    """Dense variant of `on_loss`: count [N] losses per CCC, elementwise."""
    return replace(state, epoch_lost=state.epoch_lost + count)


def on_loss(state: NSCCState, ccc: jax.Array, count: jax.Array,
            valid: jax.Array) -> NSCCState:
    """Record loss evidence (trim NACK / EV-inference / timeout) for QA."""
    n = state.cwnd.shape[0]
    drop = jnp.where(valid, ccc, n)
    return replace(state, epoch_lost=state.epoch_lost.at[drop].add(
        jnp.where(valid, count, 0), mode="drop"))


def quick_adapt(state: NSCCState, params: NSCCParams,
                now: jax.Array) -> NSCCState:
    """Once per RTT-epoch: if losses were seen, rescale cwnd to the
    delivered fraction (Sec. 3.3.1 QA / SMaRTT)."""
    epoch_len = jnp.int32(params.base_rtt * params.target_factor)
    due = (now - state.epoch_tick) >= epoch_len
    delivered = state.epoch_acked.astype(jnp.float32)
    lost = state.epoch_lost.astype(jnp.float32)
    frac = div(delivered, jnp.maximum(delivered + lost, 1.0))
    lossy = due & (state.epoch_lost > 0)
    new_cwnd = jnp.where(
        lossy,
        jnp.clip(state.cwnd * frac, params.qa_min_frac * params.max_cwnd,
                 params.max_cwnd),
        state.cwnd)
    reset = due
    return NSCCState(
        cwnd=jnp.maximum(new_cwnd, params.min_cwnd),
        epoch_acked=jnp.where(reset, 0, state.epoch_acked),
        epoch_lost=jnp.where(reset, 0, state.epoch_lost),
        epoch_tick=jnp.where(reset, now, state.epoch_tick),
    )


@jax.tree_util.register_static
@dataclass(frozen=True)
class NSCCPolicy:
    """NSCC as a pluggable CC policy for the fabric engine.

    Implements the policy protocol documented in
    `repro.network.profile`: per-tick hooks over densified [F] lanes.
    State is one `NSCCState` pytree carried in the simulator's scan
    carry. The hook bodies are exactly the calls the engine used to
    inline — the composition point moved, the ops did not (the profile
    refactor is bitwise-parity-tested against the pre-refactor engine).
    """

    params: NSCCParams

    def create(self, f: int) -> NSCCState:
        return NSCCState.create(f, self.params)

    def on_ack(self, st: NSCCState, has_ack: jax.Array, ecn: jax.Array,
               rtt: jax.Array) -> NSCCState:
        return on_ack_per_flow(st, self.params, ecn, rtt, has_ack)

    def on_nack(self, st: NSCCState, count: jax.Array) -> NSCCState:
        return on_loss_per_flow(st, count)

    def on_grant_tick(self, st, flow_dst, active, num_hosts):
        return st  # sender-based: no receiver scheduling round

    def on_send_gate(self, st: NSCCState, inflight: jax.Array) -> jax.Array:
        return inflight < jnp.floor(st.cwnd).astype(jnp.int32)

    def on_inject(self, st, injected):
        return st  # window-based: nothing to spend per packet

    def on_rx_seen(self, st, seen):
        return st

    def on_timeout(self, st: NSCCState, stalled: jax.Array) -> NSCCState:
        return on_loss_per_flow(st, stalled.astype(jnp.int32))

    def end_of_tick(self, st: NSCCState, tick: jax.Array) -> NSCCState:
        return quick_adapt(st, self.params, tick)

    def cwnd_view(self, st: NSCCState, f: int) -> jax.Array:
        return st.cwnd


def apply_dfc_penalty(state: NSCCState, params: NSCCParams, ccc: jax.Array,
                      penalty: jax.Array, valid: jax.Array) -> NSCCState:
    """Destination Flow Control for NSCC (Sec. 3.3.4): the receiver sends a
    window *penalty* that scales the sender's congestion window."""
    n = state.cwnd.shape[0]
    drop = jnp.where(valid, ccc, n)
    scale = jnp.clip(1.0 - penalty, 0.05, 1.0)
    cwnd = state.cwnd.at[drop].mul(jnp.where(valid, scale, 1.0), mode="drop")
    return replace(state, cwnd=jnp.clip(cwnd, params.min_cwnd, params.max_cwnd))
