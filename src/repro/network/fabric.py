"""Vectorized packet-level fabric simulator — the UET reproduction engine.

One simulator tick == the serialization time of one MTU packet on one link.
Every link is a FIFO queue; each tick every queue dequeues at most one
packet (line rate) and forwards it one hop. All protocol state — PSN
bitmaps, congestion windows, credit balances, EV recycle rings — is
structure-of-arrays, and a tick is a pure function stepped by
``jax.lax.scan`` under ``jit``. This is the TPU-native re-architecture of
the paper's protocol: what a hardware UET NIC does per packet, the
simulator does per *vector of flows* per tick.

The public API is declarative: a :class:`~repro.network.profile.
TransportProfile` says WHAT transport composition to run (CC algorithm,
LB scheme, per-flow delivery modes — the paper's profile table), and
``SimParams`` holds the numeric knobs (tick budget, queue depths,
thresholds). ``make_step`` composes the tick from pluggable CC and LB
policy objects; new policies implement the small protocol documented in
`repro.network.profile` and land without touching this engine.

The engine runs in two modes:

* ``simulate(g, wl, profile, p)`` — one scenario per call;
* ``simulate_batch(g, wls, profile, p)`` — a whole scenario sweep
  (different workloads, LB seeds, failure sets) ``vmap``-ed over a
  leading scenario axis, so an entire failure or incast sweep is ONE
  compiled ``scan``. Workloads, seeds and failure masks are traced
  inputs: sweeping them never recompiles. Profiles are *static* (they
  pick the compiled composition); passing a list of per-scenario
  profiles groups the batch by profile — one executable per distinct
  profile, e.g. a 3-profile x N-scenario ablation is 3 compiles and 3
  device launches for the whole grid. Per-lane results are bitwise
  identical to serial ``simulate`` calls. ``shard=True`` (or
  ``devices=``) additionally shards the scenario axis across devices
  with ``shard_map`` — see `repro.network.shard` — still bitwise
  identical, with each device exiting at its own lanes' quiescence.

Execution model (the adaptive-horizon driver): the tick budget is NOT a
fixed scan length. The driver runs a ``lax.while_loop`` over fixed-size
scan chunks (``SimParams.chunk_ticks``) and exits as soon as a scenario
is *quiescent* — every source CACK-complete, nothing inflight, queues
and control-event buffers drained — so a 1600-tick budget costs only as
many chunks as the scenario actually needs. The budget (``max_ticks`` /
``SimParams.ticks``) is a traced bound: one compiled executable serves
every horizon for a given (topology, profile, flow count, chunk) shape.
Results come in two trace tiers (see :class:`SimResult`): the default
``trace="stats"`` streams completion ticks / windowed goodput inside the
scan (no per-tick lanes, memory independent of the horizon);
``trace="full"`` buffers the dense per-tick lanes chunk by chunk and
concatenates them on the host. The state trajectory on the ticks that
run is bitwise identical across tiers, batching, and horizons.

Modeled faithfully (paper sections in parens):

* ECMP spraying with per-packet EVs through a real Clos topology (2.1)
* egress ECN marking above a queue threshold (3.3.1)
* packet trimming on overflow -> fast NACK to the source (3.2.4)
* RUD selective-repeat with a source retransmit bitmap; ROD go-back-N on a
  single static path with an in-order-only receiver (3.2.1)
* receiver PSN tracking with SACK rings + MP_RANGE rejection (3.2.5)
* NSCC 4-case window control + Quick Adapt; RCCC receiver credits; both
  composable, as the spec prescribes (3.3)
* LB schemes: static / oblivious / RR-slots / REPS / EV-bitmap (3.3.5)
* OOO-count and EV-based loss inference, timeout fallback (3.2.4)
* control traffic (ACKs, NACKs, credits) rides the second traffic class,
  modeled as a fixed-latency uncongested return path (3.1.4)
* dependency-scheduled flows (``Workload.dep``): multi-phase collectives
  (repro.network.collectives) gate each phase on its parent's source
  completion inside the scan — a whole ring/recursive-doubling/tree
  collective is one compiled run
* in-network reduction (``TransportProfile.inc`` + ``Workload.red``):
  switch-resident accumulator contexts absorb all but one child packet
  per PSN at the destination ToR and ACK the absorbed sources
  (repro.core.inc; the UE roadmap's in-network-collectives frontier)

Simplifications recorded in DESIGN.md: RCCC credit grants apply without
path delay (the grant *rate* is what the algorithm controls); trimmed
headers travel on the control TC (elevated priority per the spec).
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import inc, pds
from repro.core import pdc as pdc_fsm
from repro.core.cms.nscc import NSCCParams
from repro.core.link import CTR_MOD, LinkConfig
from repro.core.lb.schemes import LBPolicy, LBScheme, LBState, _mix32
from repro.core.lb.schemes import _pick_lane as _pick
from repro.kernels import ops as kops
from repro.network.ecmp import DELIVERED, RoutingTables
from repro.network import telemetry as telem
from repro.network.faults import FaultSchedule, as_schedule, loss_threshold
from repro.network.profile import (DeliveryMode, TransportProfile,
                                   make_cc_policy)
from repro.network.telemetry import TelemetrySpec
from repro.network.topology import QueueGraph, Stage

# packet meta bits
META_TRIMMED = 1
META_ECN = 2

# event types
EV_NONE, EV_ACK, EV_NACK, EV_OOO = 0, 1, 2, 3

# packed packet-field lanes of SimState.q_pkt (one scatter/gather moves a
# whole packet record instead of five scalar planes)
PKT_FLOW, PKT_PSN, PKT_EV, PKT_META, PKT_TSENT, PKT_FIELDS = 0, 1, 2, 3, 4, 5
# packed control-event lanes of SimState.ev_buf
EVF_TYPE, EVF_FLOW, EVF_PSN, EVF_VAL, EVF_ECN, EVF_TSENT, EVF_FIELDS = \
    0, 1, 2, 3, 4, 5, 6

DEFAULT_SEED = 0x5EED


@dataclass(frozen=True)
class SimParams:
    """Numeric simulation knobs (hashable; closed over by jit).

    Transport *composition* — CC algorithm, LB scheme, delivery modes —
    lives in :class:`TransportProfile`, not here. (The pre-profile
    transport fields — ``mode``/``lb``/``nscc``/``rccc``/
    ``failed_queues`` — are gone; constructing with them is a TypeError.
    The positional-SimParams call form still warns for one release, see
    ``_normalize_call``.)
    """

    ticks: int = 2000
    #: while-scan chunk size: quiescence is checked (and the dense trace
    #: is flushed) every `chunk_ticks` ticks. Static — it shapes the
    #: compiled chunk body — but the horizon itself is traced, so
    #: executables are shared across every tick budget.
    chunk_ticks: int = 128
    queue_capacity: int = 64
    ecn_threshold: int = 12
    trimming: bool = True
    ack_return_ticks: int = 4
    mp_range: int = 512           # receiver tracking window (PSNs)
    ev_slots: int = 16            # K for RR/REPS/EVBITMAP
    timeout_ticks: int = 256
    ooo_threshold: int = 0        # 0 = disabled
    max_cwnd: float = 48.0        # ~BDP in packets (optimistic start)
    base_rtt: float = 10.0        # unloaded RTT in ticks, for NSCC
    inc_slots: int = 64           # INC accumulator slots per reduction group


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class Workload:
    """Flow set: src/dst host ids, message size (packets), start tick,
    plus two scheduling lanes:

    * ``dep`` — flow dependency: flow f becomes eligible to inject only
      after flow ``dep[f]`` *completes at its source* (CACK reaches its
      message size); -1 = no dependency. Gated in-scan exactly like
      ``start``, so a whole multi-phase collective (repro.network.
      collectives) compiles to ONE ``lax.scan``. Dependencies must be
      acyclic (builders emit phase-ordered chains; a cycle never becomes
      eligible).
    * ``red`` — in-network-reduction group id (-1 = none): flows sharing
      a ``red`` id and destination form one switch-resident reduction
      group when the profile has ``inc=True`` (repro.core.inc).

    All fields are traced arrays — a Workload can carry a leading scenario
    axis ([B, F]) for ``simulate_batch``; build one with ``Workload.stack``.
    """

    src: jax.Array   # [F] int32
    dst: jax.Array   # [F] int32
    size: jax.Array  # [F] int32
    start: jax.Array  # [F] int32
    dep: jax.Array   # [F] int32 flow index this flow waits on (-1 = none)
    red: jax.Array   # [F] int32 INC reduction-group id (-1 = none)

    @staticmethod
    def of(src, dst, size, start=None, dep=None, red=None) -> "Workload":
        src = jnp.asarray(src, jnp.int32)
        f = src.shape[0]
        neg1 = jnp.full((f,), -1, jnp.int32)
        return Workload(
            src=src, dst=jnp.asarray(dst, jnp.int32),
            size=jnp.asarray(size, jnp.int32) * jnp.ones((f,), jnp.int32),
            start=(jnp.zeros((f,), jnp.int32) if start is None
                   else jnp.asarray(start, jnp.int32)),
            dep=(neg1 if dep is None else jnp.asarray(dep, jnp.int32)),
            red=(neg1 if red is None else jnp.asarray(red, jnp.int32)),
        )

    @staticmethod
    def stack(wls: "list[Workload] | tuple[Workload, ...]") -> "Workload":
        """Stack same-F workloads along a leading scenario axis ([B, F])."""
        f = {int(w.src.shape[-1]) for w in wls}
        if len(f) != 1:
            raise ValueError(f"scenario batch needs a uniform flow count, "
                             f"got {sorted(f)}")
        return Workload(
            src=jnp.stack([w.src for w in wls]),
            dst=jnp.stack([w.dst for w in wls]),
            size=jnp.stack([w.size for w in wls]),
            start=jnp.stack([w.start for w in wls]),
            dep=jnp.stack([w.dep for w in wls]),
            red=jnp.stack([w.red for w in wls]),
        )


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class SimState:
    """The lax.scan carry: the entire fabric + protocol state."""

    # queues (ring buffers; packet records packed along the last axis so
    # one enqueue scatter / dequeue gather moves whole packets)
    q_pkt: jax.Array    # [Q, C, PKT_FIELDS] int32 (flow = -1 => empty)
    q_head: jax.Array   # [Q] int32
    q_len: jax.Array    # [Q] int32
    # sender state
    next_psn: jax.Array     # [F] int32
    inflight: jax.Array     # [F] int32
    src_track: pds.PSNTracker  # ACK tracking at the source (base = CACK)
    rtx: jax.Array          # [F, W] uint32 retransmit bitmap (rel. to base)
    last_progress: jax.Array  # [F] int32
    slot_last_ack: jax.Array  # [F, K] int32, EV-based loss detection
    # receiver state
    dst_track: pds.PSNTracker
    last_ooo_nack: jax.Array  # [F] int32
    # congestion control (policy-owned pytree) + LB
    cc: object
    lb: LBState
    # control-TC delay ring (packed: type/flow/psn/ev/ecn/tsent lanes)
    ev_buf: jax.Array   # [D, E, EVF_FIELDS] int32
    # in-network reduction contexts (repro.core.inc; zero-size when the
    # profile has INC off)
    inc: object
    # stats
    delivered: jax.Array  # [F] int32 packets delivered (first copies)
    trims: jax.Array      # [] int32
    drops: jax.Array      # [] int32
    dups: jax.Array       # [] int32
    #: packets absorbed by switch-resident reduction (each one a packet
    #: the parent downlink never carried) / aggregates emitted
    inc_reduced: jax.Array  # [] int32
    inc_emits: jax.Array    # [] int32
    #: in-range arrivals a ROD receiver discarded for being out of order
    #: (go-back-N rejects; NOT duplicates — counted separately from dups)
    rod_rejects: jax.Array  # [] int32
    retransmits: jax.Array  # [] int32
    #: per-flow retransmission timeout, in ticks. Constant at
    #: ``SimParams.timeout_ticks`` unless the profile sets
    #: ``rto_backoff > 1``: then each timeout multiplies it (capped at
    #: ``rto_max_scale`` x base) and any ACK resets it.
    rto: jax.Array          # [F] int32
    #: recovery-loop counters (streamed: O(1) carry, present in both
    #: trace tiers via SimResult.timeouts / .ev_evictions / ...)
    timeouts: jax.Array       # [] int32 RTO expiries (incl. ROD rewinds)
    ev_evictions: jax.Array   # [] int32 EVs blacklisted by the LB policy
    ticks_degraded: jax.Array  # [] int32 ticks with >= 1 link/host dead
    #: PDC liveness lanes (value-inert unless the profile sets
    #: ``pdc_dead_after > 0`` — the updates are statically elided)
    rto_strikes: jax.Array    # [F] int32 consecutive zero-progress RTOs
    quarantined: jax.Array    # [F] bool PDC torn down, flow abandoned
    flows_abandoned: jax.Array    # [] int32 PDCs declared unreachable
    ticks_unreachable: jax.Array  # [] int32 ticks with >= 1 quarantined flow
    #: link-layer reliability lanes (repro.core.link.LinkConfig; the
    #: per-queue arrays are zero-size unless the dispatching `link=`
    #: spec arms them — the scalars stream 0 on unarmed runs)
    llr_busy_until: jax.Array  # [Q] int32 LLR go-back-N replay deadline
    llr_replays: jax.Array     # [] int32 frames corrupted + replayed at hop
    cbfc_consumed: jax.Array   # [Q] uint32 20-bit cyclic credits consumed
    cbfc_freed: jax.Array      # [Q] uint32 20-bit cyclic credits freed
    cbfc_ret: jax.Array        # [Rd, Q] int32 credit-return delay ring
    credit_stall_ticks: jax.Array  # [] int32 ticks with >= 1 credit stall


def _first_set_bit(ring: jax.Array) -> jax.Array:
    """Per-row index of the lowest set bit of a [N, W] uint32 ring, or -1."""
    nz = ring != 0
    has = nz.any(axis=1)
    W = ring.shape[1]
    first_w = jnp.argmax(nz, axis=1)
    w = ring[jnp.arange(ring.shape[0]), first_w]
    lsb = w & (jnp.uint32(0) - w)
    ctz = pds._popcount32(lsb - jnp.uint32(1))
    return jnp.where(has, first_w * 32 + ctz, -1).astype(jnp.int32)


def _bit_plane(off: jax.Array, valid: jax.Array, w: int) -> jax.Array:
    """[F, W] uint32 plane with row i's bit `off[i]` set (elementwise —
    the dense replacement for a one-lane-per-row bit scatter)."""
    o = jnp.clip(off, 0, w * 32 - 1)
    wordsel = jnp.arange(w)[None, :] == (o // 32)[:, None]
    bit = (jnp.uint32(1) << (o % 32).astype(jnp.uint32))[:, None]
    ok = valid & (off >= 0) & (off < w * 32)
    return jnp.where(ok[:, None] & wordsel, bit, jnp.uint32(0))


def _set_own_bit(ring: jax.Array, off: jax.Array,
                 valid: jax.Array) -> jax.Array:
    """Row i sets bit off[i] — elementwise, no scatter."""
    return ring | _bit_plane(off, valid, ring.shape[1])


def _clear_own_bit(ring: jax.Array, off: jax.Array,
                   valid: jax.Array) -> jax.Array:
    """Row i clears bit off[i] — elementwise, no scatter."""
    return ring & ~_bit_plane(off, valid, ring.shape[1])


def _own_word(ring: jax.Array, off: jax.Array) -> jax.Array:
    """Row i's ring word containing bit offset off[i] (clipped)."""
    w = ring.shape[1]
    word = jnp.clip(off, 0, w * 32 - 1) // 32
    return jnp.take_along_axis(ring, word[:, None], axis=1)[:, 0]


def init_state(g: QueueGraph, wl: Workload, profile: TransportProfile,
               p: SimParams, seed: "int | jax.Array" = DEFAULT_SEED,
               link: "LinkConfig | None" = None) -> SimState:
    Q, C = g.num_queues, p.queue_capacity
    F = wl.src.shape[0]
    D = p.ack_return_ticks + 1
    E = 2 * Q + 2 * F
    W = p.mp_range // 32
    nparams = NSCCParams(base_rtt=p.base_rtt, max_cwnd=p.max_cwnd)
    cc_pol = make_cc_policy(profile.cc, nparams, p.max_cwnd)
    q_pkt = jnp.zeros((Q, C, PKT_FIELDS), jnp.int32).at[:, :, PKT_FLOW].set(-1)
    return SimState(
        q_pkt=q_pkt,
        q_head=jnp.zeros((Q,), jnp.int32),
        q_len=jnp.zeros((Q,), jnp.int32),
        next_psn=jnp.zeros((F,), jnp.int32),
        inflight=jnp.zeros((F,), jnp.int32),
        src_track=pds.PSNTracker.create(F, p.mp_range),
        rtx=jnp.zeros((F, W), jnp.uint32),
        last_progress=jnp.zeros((F,), jnp.int32),
        slot_last_ack=jnp.full((F, p.ev_slots), -1, jnp.int32),
        dst_track=pds.PSNTracker.create(F, p.mp_range),
        last_ooo_nack=jnp.full((F,), -10**6, jnp.int32),
        cc=cc_pol.create(F),
        lb=LBState.create(F, p.ev_slots, seed),
        ev_buf=jnp.zeros((D, E, EVF_FIELDS), jnp.int32),
        inc=(inc.INCState.create(F, p.inc_slots) if profile.inc
             else inc.INCState.empty()),
        delivered=jnp.zeros((F,), jnp.int32),
        trims=jnp.int32(0), drops=jnp.int32(0), dups=jnp.int32(0),
        inc_reduced=jnp.int32(0), inc_emits=jnp.int32(0),
        rod_rejects=jnp.int32(0), retransmits=jnp.int32(0),
        rto=jnp.full((F,), p.timeout_ticks, jnp.int32),
        timeouts=jnp.int32(0), ev_evictions=jnp.int32(0),
        ticks_degraded=jnp.int32(0),
        rto_strikes=jnp.zeros((F,), jnp.int32),
        quarantined=jnp.zeros((F,), jnp.bool_),
        flows_abandoned=jnp.int32(0), ticks_unreachable=jnp.int32(0),
        llr_busy_until=jnp.zeros(
            (Q if link is not None and link.llr else 0,), jnp.int32),
        llr_replays=jnp.int32(0),
        cbfc_consumed=jnp.zeros(
            (Q if link is not None and link.cbfc else 0,), jnp.uint32),
        cbfc_freed=jnp.zeros(
            (Q if link is not None and link.cbfc else 0,), jnp.uint32),
        cbfc_ret=jnp.zeros(
            ((link.credit_return_ticks, Q)
             if link is not None and link.cbfc else (0, 0)), jnp.int32),
        credit_stall_ticks=jnp.int32(0),
    )


def _rank_within(target: jax.Array, valid: jax.Array,
                 base: jax.Array) -> tuple[jax.Array, jax.Array]:
    """For candidate lanes with target queue ids, compute each lane's
    arrival rank within its target and the resulting queue position.

    Segment-count scheme: rank[i] = #{j < i : target[j] == target[i] and
    valid[j]} via a masked pairwise count — a few fused vector passes
    instead of the per-tick stable argsort the seed used (XLA sorts are
    slow on CPU and batch poorly under vmap).

    Returns (pos, rank) where pos[i] = base[target[i]] + rank.
    """
    n = target.shape[0]
    t = jnp.where(valid, target, -1)
    lane = jnp.arange(n)
    same = (t[None, :] == t[:, None]) & valid[None, :] \
        & (lane[None, :] < lane[:, None])
    rank = same.sum(axis=1, dtype=jnp.int32)
    pos = base[jnp.where(valid, target, 0)] + rank
    return pos, rank


class _Spans:
    """Consecutive named spans without a nested block each: ``span(name)``
    closes the open span and opens `name`; ``close()`` or the end of the
    ``with`` block closes the last. `kind` makes one span:
    ``jax.named_scope`` (the ``op_name`` metadata of the ops traced
    inside it; the compiled arithmetic is unchanged) or
    ``jax.profiler.TraceAnnotation`` (a host span on the profiler's
    clock). DESIGN.md lists the names."""

    def __init__(self, kind=jax.named_scope):
        self._kind = kind
        self._open = None

    def __call__(self, name: str) -> None:
        self.close()
        self._open = self._kind(name)
        self._open.__enter__()

    def close(self) -> None:
        if self._open is not None:
            opened, self._open = self._open, None
            opened.__exit__(None, None, None)

    def __enter__(self) -> "_Spans":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def make_step(g: QueueGraph, profile: TransportProfile, p: SimParams, F: int,
              lossy: bool = False, tel: "TelemetrySpec | None" = None,
              hosty: bool = False, corrupty: bool = False,
              link: "LinkConfig | None" = None):
    """Build the per-tick transition function for one transport profile.

    The tick is composed from the profile's pluggable policy objects: a
    CC policy (``make_cc_policy``) hooked at the ACK/NACK/grant/gate/
    inject/timeout points, and an ``LBPolicy`` hooked at the feedback and
    EV-selection points. Delivery modes are per-flow static masks: ROD
    flows run go-back-N on one static path, gate injection on in-order
    CACK advance, and their receiver accepts only the next expected PSN;
    RUD/RUDI flows keep spray + selective-retransmit semantics.

    The returned ``step(s, tick, wl, fault)`` takes the workload and the
    per-queue fault schedule as *traced* arguments so one compiled step
    serves every scenario of a sweep (and vmaps over a scenario axis).
    ``lossy`` is the one schedule-derived STATIC: the gray-link loss
    draw (two hash rounds per enqueue lane per tick) is only compiled
    in when the dispatching schedule has a nonzero ``loss_p`` lane, so
    loss-free runs — every pre-fault-engine call site — pay nothing
    for it.

    ``tel`` (a :class:`~repro.network.telemetry.TelemetrySpec`) is the
    same kind of static: when enabled, the step additionally emits a
    ``probe`` dict in its out lanes — per-queue egress-mark / trim /
    silent-drop increments and per-flow RTT-sample and cwnd views, all
    signals the tick already computed — for the telemetry lanes riding
    the stats carry. Disabled (the default), no probe is built and the
    compiled step is bitwise the pre-telemetry one.

    ``hosty`` is the endpoint analogue of ``lossy``: the per-host
    outage/NIC-stall semantics (dead hosts stop injecting, processing
    ACKs, and absorbing deliveries; stalled hosts only stop injecting)
    are compiled in only when the dispatching schedule actually carries
    host faults, so all-healthy runs pay nothing and stay bitwise the
    pre-endpoint-fault program.

    ``corrupty`` gates the PHY-corruption draw the same way ``lossy``
    gates the gray-link draw: compiled in only when the dispatching
    schedule has a nonzero ``corrupt_p`` lane. Corruption is drawn per
    TRANSMISSION (at dequeue, one frame per queue per tick — so
    retransmitted frames re-draw) from an independent hash stream.

    ``link`` (a :class:`~repro.core.link.LinkConfig`, static like
    ``tel``) arms the link-layer reliability lanes: ``llr`` confines a
    corrupted transmission to the hop — the queue holds its head frame
    for ``llr_rtt`` ticks (link NACK turnaround + go-back-N replay) and
    then retransmits it, so delivery is delayed, never dropped; without
    it a corrupted frame is a silent end-to-end loss. ``cbfc`` puts a
    20-bit cyclic credit gate at enqueue: a candidate whose target queue
    has no credited space left is back-pressured in place (the upstream
    hop keeps its head frame, an injector waits at the NIC) instead of
    overflowing, with dequeue credits returning after
    ``credit_return_ticks``. ``link=None`` (or an off spec) compiles
    the exact pre-feature program.
    """
    tel_on = tel is not None and tel.enabled
    llr = link is not None and link.llr
    cbfc = link is not None and link.cbfc
    llr_rtt = int(link.llr_rtt) if llr else 0
    Rd = int(link.credit_return_ticks) if cbfc else 1
    MASK20 = jnp.uint32(CTR_MOD - 1)
    rt = RoutingTables(g)
    Q = g.num_queues
    C = p.queue_capacity
    D = p.ack_return_ticks + 1
    E = 2 * Q + 2 * F
    H = g.num_hosts
    K = p.ev_slots
    mp = p.mp_range
    W = mp // 32
    flow_ids = jnp.arange(F)
    nparams = NSCCParams(base_rtt=p.base_rtt, max_cwnd=p.max_cwnd)
    cc_pol = make_cc_policy(profile.cc, nparams, p.max_cwnd)
    # per-flow delivery modes are static: compiled straight into the step
    dm = profile.delivery_modes(F)
    rod_np = dm == int(DeliveryMode.ROD)
    all_rod = bool(rod_np.all())
    any_rod = bool(rod_np.any())
    mixed_rod = any_rod and not all_rod
    rod_mask = jnp.asarray(rod_np)
    # an all-ROD profile is single-path by definition (spec: ordered
    # delivery forbids spraying); mixed profiles spray the RUD lanes and
    # pin the ROD lanes to their static EV below
    lb_pol = LBPolicy(LBScheme.STATIC if all_rod else profile.lb,
                      evict_enabled=profile.ev_eviction)
    # recovery-loop statics: with the defaults (rto_backoff=1.0,
    # ev_eviction=False) every gated lane below is elided and the
    # compiled tick is exactly the pre-fault-engine one
    backoff_on = profile.rto_backoff != 1.0
    evict_on = profile.ev_eviction
    rto_cap = int(p.timeout_ticks) * int(profile.rto_max_scale)
    lane_ids = jnp.arange(Q + F, dtype=jnp.uint32)
    # PDC liveness teardown static (mirrors repro.core.pdc.unreachable):
    # off (the default) elides every quarantine lane update below.
    pdc_on = profile.pdc_dead_after > 0
    dead_after = int(profile.pdc_dead_after)
    if hosty:
        # static queue -> host map for the dead-host downlink mask (only
        # each host's final downlink is host-owned; fabric queues carry
        # -1 and never inherit a host outage)
        qh_np = np.full((Q,), -1, np.int64)
        qh_np[np.asarray(g.host_queue, np.int64)] = np.arange(H)
        q_is_host = jnp.asarray(qh_np >= 0)
        q_host = jnp.asarray(np.where(qh_np >= 0, qh_np, 0), jnp.int32)

    def step(s: SimState, tick: jax.Array, wl: Workload,
             fault: FaultSchedule):
        with _Spans() as phase:
            return phases(s, tick, wl, fault, phase)

    def phases(s: SimState, tick: jax.Array, wl: Workload,
               fault: FaultSchedule, phase: _Spans):
        phase("tick.faults")
        flow_src = wl.src
        flow_dst = wl.dst
        slot = tick % D
        # fault lanes -> this tick's dead-queue mask. The static failed=
        # mask degenerates to fail_at=0, heal_at=NEVER_TICK, making this
        # window test bitwise the old constant mask.
        dead = (fault.fail_at <= tick) & (tick < fault.heal_at)
        if hosty:
            # endpoint fault lanes: hd = dead hosts (no inject / no ACK
            # / no absorb), nic = stalled NICs (no inject only). A dead
            # host's downlink eats enqueues like a dead link — silent
            # drops, counted below — and the host's flows are frozen via
            # the per-flow masks.
            hd = (fault.host_fail_at <= tick) & (tick < fault.host_heal_at)
            nic = (fault.nic_stall_at <= tick) & (tick < fault.nic_heal_at)
            dead = dead | (q_is_host & hd[q_host])
            src_dead = hd[flow_src]            # [F] source host is dead
            dst_dead = hd[flow_dst]            # [F] destination host is dead
            # a dead destination does NOT freeze the source: it keeps
            # retransmitting into the dead downlink (silent drops) until
            # the PDC liveness teardown quarantines the flow
            inj_frozen = src_dead | nic[flow_src]

        # ------------------------------------------------ 1. control events
        phase("tick.control")
        evs = s.ev_buf[slot]                                  # [E, 6]
        et = evs[:, EVF_TYPE]
        ef = evs[:, EVF_FLOW]
        ep = evs[:, EVF_PSN]
        ee = evs[:, EVF_VAL]
        ec = evs[:, EVF_ECN]
        ets = evs[:, EVF_TSENT]
        is_ack = et == EV_ACK
        is_nack = (et == EV_NACK) | (et == EV_OOO)
        if hosty:
            # a dead SOURCE host processes no returning control traffic:
            # its lanes' ACKs/NACKs are lost on arrival (the events were
            # consumed from the ring, so nothing replays after heal)
            lane_src_dead = src_dead[jnp.clip(ef, 0, F - 1)]
            is_ack = is_ack & ~lane_src_dead
            is_nack = is_nack & ~lane_src_dead

        # Per-flow densification of the ACK lanes: a flow's ACKs all come
        # from its destination's single host downlink, so at most ONE ACK
        # lane per flow is active per tick. That turns every ACK-driven
        # update (SACK record, CC, LB, progress) into elementwise [F] or
        # [F, W] work — one [F, E] one-hot is the only lane-wide pass.
        # (NACK lanes stay lane-wise: several trims can hit one flow.)
        hot_ack = (ef[None, :] == flow_ids[:, None]) & is_ack[None, :]
        hot_nack = (ef[None, :] == flow_ids[:, None]) & is_nack[None, :]
        has_ack = hot_ack.any(axis=1)
        nack_count = hot_nack.sum(axis=1, dtype=jnp.int32)
        ack_psn = _pick(hot_ack, ep)

        # ACKs: record at source, advance CACK, shift the rtx ring in
        # lockstep — the fused SACK hot path (kernels/sack_fused.py).
        ack_off0 = (ack_psn.astype(jnp.uint32)
                    - s.src_track.base).astype(jnp.int32)
        ack_in_range = has_ack & (ack_off0 >= 0) & (ack_off0 < mp)
        ack_bit = jnp.uint32(1) << (ack_off0 % 32).astype(jnp.uint32)
        ack_already = ack_in_range & (
            (_own_word(s.src_track.ring, ack_off0) & ack_bit) != 0)
        ack_mask = _bit_plane(ack_off0, ack_in_range, W)
        src_ring, src_base, rtx, adv = kops.sack_fused(
            s.src_track.ring, s.src_track.base, s.rtx, ack_mask)
        one = jnp.uint32(1)
        src_track = pds.PSNTracker(
            base=src_base, ring=src_ring,
            rx_ok=s.src_track.rx_ok + jnp.where(
                ack_in_range & ~ack_already, one, 0),
            dup=s.src_track.dup + jnp.where(ack_already, one, 0),
            oor=s.src_track.oor + jnp.where(
                has_ack & ~ack_in_range, one, 0),
        )

        # retire inflight, CC + LB feedback (policy hooks over [F] lanes)
        retire = has_ack.astype(jnp.int32) + nack_count
        inflight = jnp.maximum(s.inflight - retire, 0)
        ack_ecn = _pick(hot_ack, ec).astype(jnp.bool_)
        rtt = (tick - _pick(hot_ack, ets)).astype(jnp.float32)
        cc_st = cc_pol.on_ack(s.cc, has_ack, ack_ecn, rtt)
        cc_st = cc_pol.on_nack(cc_st, nack_count)
        lbs = lb_pol.on_ack(s.lb, hot_ack, ef, ee, ec, is_ack, is_nack,
                            flow_ok=(~rod_mask) if mixed_rod else None)

        # progress clock: any ACK freshens the flow
        last_progress = jnp.where(has_ack, tick, s.last_progress)
        # per-flow RTO lane: an ACK resets backed-off timeouts to base.
        # With rto_backoff == 1.0 the lane is never mutated (constant ==
        # timeout_ticks), so every predicate on it compiles to the old
        # fixed-constant comparison.
        rto = (jnp.where(has_ack, jnp.int32(p.timeout_ticks), s.rto)
               if backoff_on else s.rto)
        if evict_on:
            # trim NACKs implicate the path EV they carry: collect one
            # per flow for the eviction hook in section 9. OOO NACKs are
            # receiver gap reports, not path evidence — excluded. ROD
            # lanes are excluded too (an ordered flow's static path must
            # not churn on congestion; it evicts on timeout instead).
            hot_tnack = hot_nack & (et == EV_NACK)[None, :]
            nack_ev = jnp.max(jnp.where(hot_tnack, ee[None, :], -1), axis=1)
            nack_evict = hot_tnack.any(axis=1)
            if any_rod:
                nack_evict = nack_evict & ~rod_mask

        # ACK'd PSNs can't be pending retransmit anymore (rtx was already
        # shifted by the fused op, so offsets are relative to the new base)
        ack_off = ack_psn - src_track.base.astype(jnp.int32)
        rtx = _clear_own_bit(rtx, ack_off, has_ack)

        # NACKs (trim / OOO): mark PSN for selective retransmit (RUD);
        # ROD does go-back-N instead (handled at injection via next_psn).
        # Several NACKs may hit one flow, so this stays lane-wise — but
        # as a dense bitwise-OR fold over the NACK-capable lanes (ACK
        # lanes [0, Q) carry NACKs only for ROD flows, which never take
        # the selective-retransmit path), not a scatter: OR is naturally
        # duplicate-safe, so no dedup or already-set pass is needed.
        nf, nep = ef[Q:], ep[Q:]
        n_nack = is_nack[Q:]
        nack_off = nep - src_track.base[jnp.where(n_nack, nf, 0)].astype(jnp.int32)
        if not all_rod:
            n_ok = n_nack & (nack_off >= 0) & (nack_off < mp)
            if mixed_rod:
                n_ok = n_ok & ~rod_mask[jnp.where(n_nack, nf, 0)]
            # duplicate-safe OR of the NACKed PSN bits into the rtx ring
            # (kernels/nack_mark.py; jnp oracle scatters one bit per lane
            # onto an [F, mp] bool plane and packs it into ring words).
            # Replaces the [F, W, E-Q] dense OR-fold — the tick's largest
            # intermediate by an order of magnitude.
            rtx = kops.nack_mark(rtx, nf, jnp.clip(nack_off, 0, mp - 1),
                                 n_ok)
        rod_gbn = hot_nack.any(axis=1)

        # EV-based loss detection (Sec. 3.2.4), RR_SLOTS layout:
        # slot i carries PSNs i, i+K, i+2K...; an ACK for PSN x implies
        # every unacked PSN x-K, x-2K... in the same slot was lost.
        slot_last_ack = s.slot_last_ack
        if profile.lb == LBScheme.RR_SLOTS and not all_rod:
            has_ack_rr = has_ack & ~rod_mask if mixed_rod else has_ack
            sl = ack_psn % K
            prev = jnp.take_along_axis(slot_last_ack, sl[:, None],
                                       axis=1)[:, 0]
            # mark up to 2 predecessors (losses per ACK are almost always <=1)
            for back in (1, 2):
                miss = ack_psn - back * K
                off = miss - src_track.base.astype(jnp.int32)
                # skip PSNs already SACKed at the source (not actually lost)
                w_i = jnp.clip(off, 0, rtx.shape[1] * 32 - 1)
                sacked = (_own_word(src_track.ring, off)
                          >> (w_i % 32).astype(jnp.uint32)) & jnp.uint32(1)
                lost = has_ack_rr & (miss > prev) & (miss >= 0) & (sacked == 0)
                rtx = _set_own_bit(rtx, off, lost)
            hot_sl = (jnp.arange(K)[None, :] == sl[:, None]) \
                & has_ack_rr[:, None]
            slot_last_ack = jnp.where(
                hot_sl, jnp.maximum(slot_last_ack, ack_psn[:, None]),
                slot_last_ack)

        # consume the slot: clear only the EVF_TYPE lane (every read of
        # the other lanes is masked by type != NONE, and the slot is
        # fully rewritten when it next comes up as out_slot) — a [E, 1]
        # dynamic-update-slice instead of the whole [E, EVF_FIELDS]
        # record, and no zeros materialized
        ev_buf = s.ev_buf.at[slot, :, EVF_TYPE].set(jnp.int32(EV_NONE))

        # ------------------------------------------- 2. RCCC receiver grants
        phase("tick.grants")
        done = src_track.base.astype(jnp.int32) >= wl.size
        # dependency lane: flow f is eligible only once flow dep[f] has
        # completed at ITS source (CACK == size) — gated in-scan like
        # `start`, so multi-phase collectives run inside one scan. dep is
        # traced: dep = -1 everywhere reproduces the ungated schedule.
        safe_dep = jnp.where(wl.dep >= 0, wl.dep, 0)
        dep_ok = (wl.dep < 0) | done[safe_dep]
        active = ~done & (tick >= wl.start) & dep_ok
        if pdc_on:
            # a torn-down PDC holds no receiver credit claim
            active = active & ~s.quarantined
        cc_st = cc_pol.on_grant_tick(cc_st, flow_dst, active, H)

        # --------------------------------------------------- 3. injection
        phase("tick.injection")
        has_rtx = (rtx != 0).any(axis=1)
        if all_rod:
            has_rtx = jnp.zeros((F,), jnp.bool_)
        elif mixed_rod:
            has_rtx = has_rtx & ~rod_mask
        # Shared RTO time predicate. Hoisting ONLY the clock comparison is
        # bitwise-safe for both consumers (ROD rewind here, RUD stall in
        # section 9): rewind mutates last_progress solely on ROD lanes,
        # which section 9 masks back out, and `inflight` — which injection
        # DOES mutate between the two sites — stays site-local.
        overdue = tick - last_progress > rto
        # ROD go-back-N: on NACK or timeout, rewind next_psn to base
        next_psn = s.next_psn
        timeout_rod = jnp.zeros((F,), jnp.bool_)
        if any_rod:
            timeout_rod = (inflight > 0) & overdue
            if pdc_on:
                timeout_rod = timeout_rod & ~s.quarantined
            rewind = rod_gbn | timeout_rod
            if mixed_rod:
                rewind = rewind & rod_mask
                timeout_rod = timeout_rod & rod_mask
            next_psn = jnp.where(rewind, src_track.base.astype(jnp.int32), next_psn)
            inflight = jnp.where(rewind, 0, inflight)
            last_progress = jnp.where(rewind, tick, last_progress)

        win_ok = cc_pol.on_send_gate(cc_st, inflight)
        if any_rod:
            # in-order CACK gate (ROD): the ordered window may not race
            # more than one congestion window past the cumulative ACK
            rod_win = jnp.maximum(
                jnp.floor(cc_pol.cwnd_view(cc_st, F)).astype(jnp.int32), 1)
            rod_ok = (next_psn - src_track.base.astype(jnp.int32)) < rod_win
            win_ok = win_ok & jnp.where(rod_mask, rod_ok, True)
        mp_ok = (next_psn - src_track.base.astype(jnp.int32)) < p.mp_range
        can_new = (next_psn < wl.size) & mp_ok
        eligible = (tick >= wl.start) & ~done & dep_ok & win_ok \
            & (has_rtx | can_new)
        if hosty:
            # frozen injectors: dead source hosts and stalled NICs emit
            # nothing. A stalled NIC's flows stay ACK-live and simply
            # wait; a dead host's flows decay into the timeout path.
            eligible = eligible & ~inj_frozen
        if pdc_on:
            # a quarantined flow gets no retransmit bandwidth
            eligible = eligible & ~s.quarantined

        # fair per-host pick: per-tick pseudo-random rotation, flow id in
        # the low bits so exactly one winner exists per host
        from repro.core.lb.schemes import _mix32
        rot = (_mix32(jnp.arange(F, dtype=jnp.uint32) * jnp.uint32(2654435761)
                      ^ tick.astype(jnp.uint32)) >> 16).astype(jnp.int32)
        key = rot * F + jnp.arange(F)
        key = jnp.where(eligible, key, jnp.int32(2 ** 30))
        hot_host = flow_src[None, :] == jnp.arange(H)[:, None]   # [H, F]
        host_min = jnp.min(jnp.where(hot_host, key[None, :], 2 ** 30), axis=1)
        injected = eligible & (key == host_min[flow_src]) & (key < 2 ** 30)

        rtx_off = _first_set_bit(rtx)
        rtx_psn = src_track.base.astype(jnp.int32) + rtx_off
        use_rtx = injected & has_rtx & (rtx_off >= 0)
        psn_out = jnp.where(use_rtx, rtx_psn, next_psn)

        lbs2, ev_sel = lb_pol.select(lbs, psn_out.astype(jnp.uint32), tick)
        if mixed_rod:
            # ROD lanes are pinned to their static single-path EV and do
            # not advance the spraying state
            ev_sel = jnp.where(rod_mask, lb_pol.static_ev(lbs), ev_sel)
        inj_q = rt.injection_queue(flow_src, flow_dst, ev_sel)

        def commit_injection(injected, use_rtx, rtx, next_psn, lbs,
                             inflight, cc_st):
            """Sender-state commit for this tick's injections. With CBFC
            off it runs right here (the pre-feature program); with CBFC
            on it is deferred past the section-7 credit gate, which may
            cancel injections (`stall_inj`) — a cancelled injection must
            leave NO sender-state trace, or the flow would leak PSNs and
            window."""
            rtx = _clear_own_bit(rtx, rtx_off, use_rtx)
            next_psn = jnp.where(injected & ~use_rtx, next_psn + 1,
                                 next_psn)
            commit = injected & ~rod_mask if mixed_rod else injected
            lbs = jax.tree_util.tree_map(
                lambda a, b: jnp.where(
                    commit.reshape((-1,) + (1,) * (a.ndim - 1)), b, a),
                lbs, lbs2)
            if evict_on:
                # remember each flow's most recent EV: the path a later
                # RTO expiry implicates (covers ROD lanes, whose pinned
                # EV never passes through commit_selection)
                lbs = replace(lbs, last_ev=jnp.where(
                    injected, ev_sel.astype(jnp.int32), lbs.last_ev))
            inflight = inflight + injected.astype(jnp.int32)
            cc_st = cc_pol.on_inject(cc_st, injected)
            retransmits = s.retransmits + use_rtx.sum(dtype=jnp.int32)
            return rtx, next_psn, lbs, inflight, cc_st, retransmits

        if not cbfc:
            rtx, next_psn, lbs, inflight, cc_st, retransmits = \
                commit_injection(injected, use_rtx, rtx, next_psn, lbs,
                                 inflight, cc_st)

        # ------------------------------------------------- 4. forwarding
        phase("tick.forwarding")
        qidx = jnp.arange(Q)
        nonempty = s.q_len > 0
        # link-layer transmission gate: `txq` is the set of queues whose
        # head frame actually REACHES the next hop this tick, `leaves`
        # the set whose head frame leaves its queue. With the link
        # statics off both are `nonempty` and the block compiles away.
        txq = nonempty
        if llr:
            # a queue mid-replay is re-sending the corrupted window at
            # the link layer: nothing reaches the next hop until
            # `llr_busy_until` (the hop-confined go-back-N penalty)
            txq = txq & (tick >= s.llr_busy_until)
        if corrupty:
            # per-transmission BER draw hashed from (seed, tick, queue)
            # — an independent stream from the gray-link draw (distinct
            # hash constants), equally reproducible across batch/shard/
            # chunk boundaries. One frame transmits per queue per tick,
            # so one draw per queue IS per transmission — and replayed
            # or retransmitted frames re-draw: a bad cable hits those
            # too.
            uc = _mix32(_mix32(tick.astype(jnp.uint32)
                               ^ fault.seed * jnp.uint32(0x85EBCA77))
                        ^ qidx.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35))
            corrupt_hit = txq & (uc < loss_threshold(fault.corrupt_p))
        else:
            corrupt_hit = jnp.zeros((Q,), jnp.bool_)
        if llr:
            # LLR confines the loss to the hop: the corrupted frame is
            # link-NACKed and the queue holds it for a go-back-N replay
            # window — delivery is DELAYED, never dropped, and nothing
            # downstream or end-to-end ever sees the corruption
            txq = txq & ~corrupt_hit
            leaves = txq
            llr_busy_until = jnp.where(
                corrupt_hit, tick + jnp.int32(llr_rtt), s.llr_busy_until)
            llr_replays = s.llr_replays + corrupt_hit.sum(dtype=jnp.int32)
            corrupt_lost = jnp.zeros((Q,), jnp.bool_)
        else:
            # no link-layer recovery: the corrupted frame was
            # transmitted and died on the wire — a silent drop charged
            # at the transmitting hop (section 7), recovered end-to-end
            # (RTO / OOO inference) exactly like a gray-link loss
            corrupt_lost = corrupt_hit
            leaves = txq
            txq = txq & ~corrupt_hit
            llr_busy_until = s.llr_busy_until
            llr_replays = s.llr_replays
        hpos = s.q_head
        head_pkt = jnp.take_along_axis(
            s.q_pkt, hpos[:, None, None], axis=1)[:, 0]        # [Q, 5]
        pf = head_pkt[:, PKT_FLOW]
        pp = head_pkt[:, PKT_PSN]
        pe = head_pkt[:, PKT_EV]
        pm = head_pkt[:, PKT_META]
        pt = head_pkt[:, PKT_TSENT]
        # egress ECN marking: queue length at departure above threshold
        mark = txq & (s.q_len > p.ecn_threshold)
        pm = jnp.where(mark, pm | META_ECN, pm)
        if not cbfc:
            # with CBFC the dequeue commit is deferred past the section-7
            # credit gate, which can hold a head frame in place
            q_head = jnp.where(leaves, (s.q_head + 1) % C, s.q_head)
            q_len = jnp.where(leaves, s.q_len - 1, s.q_len)

        safe_pf = jnp.where(nonempty, pf, 0)
        nq = rt.route_step(qidx, flow_src[safe_pf], flow_dst[safe_pf], pe)
        deliver = txq & (nq == DELIVERED)
        if hosty:
            # packets dequeued toward a dead destination vanish at the
            # dead NIC (silent drops, counted in section 7): the
            # dead-queue mask only eats ENQUEUES, so packets already
            # queued when the host died drain through here — and a dead
            # host must not ACK, so they may not count as deliveries
            dst_gone = deliver & dst_dead[safe_pf]
            deliver = deliver & ~dst_gone
        forward = txq & (nq >= 0)

        # --------------------------------------------- 5. delivery at FEPs
        phase("tick.delivery")
        dtrim = deliver & ((pm & META_TRIMMED) != 0)
        ddata = deliver & ~dtrim
        # one host downlink per destination => at most one delivery per
        # flow per tick: densify the [Q] delivery lanes to per-flow [F]
        # values and the whole receive path goes elementwise (no scatter)
        hot_d = (pf[None, :] == flow_ids[:, None]) & ddata[None, :]  # [F, Q]
        has_d = hot_d.any(axis=1)
        d_psn = _pick(hot_d, pp)
        d_off = (d_psn.astype(jnp.uint32)
                 - s.dst_track.base).astype(jnp.int32)
        d_in_range = has_d & (d_off >= 0) & (d_off < mp)
        if any_rod:
            # ROD receiver accepts only the next in-order PSN (go-back-N
            # semantics): out-of-order arrivals are discarded and NACKed
            # with the first-gap PSN so the source rewinds immediately
            rod_rej_f = d_in_range & (d_off != 0)
            if mixed_rod:
                rod_rej_f = rod_rej_f & rod_mask
            d_rec = d_in_range & ~rod_rej_f
        else:
            d_rec = d_in_range
        d_bit = jnp.uint32(1) << (d_off % 32).astype(jnp.uint32)
        d_already = d_rec & (
            (_own_word(s.dst_track.ring, d_off) & d_bit) != 0)
        fresh_f = d_rec & ~d_already
        d_ring = s.dst_track.ring | _bit_plane(d_off, d_rec, W)
        d_ring, d_base, _ = kops.sack_advance(d_ring, s.dst_track.base)
        dst_track = pds.PSNTracker(
            base=d_base, ring=d_ring,
            rx_ok=s.dst_track.rx_ok + jnp.where(fresh_f, one, 0),
            dup=s.dst_track.dup + jnp.where(d_already, one, 0),
            oor=s.dst_track.oor + jnp.where(has_d & ~d_in_range, one, 0),
        )
        if any_rod:
            dups = s.dups + (has_d & ~fresh_f & ~rod_rej_f).sum(
                dtype=jnp.int32)
            rod_rejects = s.rod_rejects + rod_rej_f.sum(dtype=jnp.int32)
        else:
            dups = s.dups + (has_d & ~fresh_f).sum(dtype=jnp.int32)
            rod_rejects = s.rod_rejects
        delivered_ctr = s.delivered + fresh_f.astype(jnp.int32)
        # RUDI lanes: idempotent ops are re-applied on duplicates (no
        # receiver dedup state needed); stats still count first copies
        hot_seen = (pf[None, :] == flow_ids[:, None]) & deliver[None, :]
        cc_st = cc_pol.on_rx_seen(cc_st, hot_seen.any(axis=1))

        # ------------------------------------- 6. OOO-count loss inference
        phase("tick.ooo")
        ooo_fire = jnp.zeros((F,), jnp.bool_)
        if p.ooo_threshold > 0:
            dist = pds.ooo_distance(dst_track)
            due = (dist > p.ooo_threshold) & (
                tick - s.last_ooo_nack > jnp.int32(p.base_rtt))
            ooo_fire = due
        last_ooo_nack = jnp.where(ooo_fire, tick, s.last_ooo_nack)

        # ---------------------------------- 6b. in-network reduction (INC)
        phase("tick.inc")
        # Forwarded packets about to enter their destination host downlink
        # and belonging to a reduction group are offered to the ToR's
        # accumulator context: all but the bitmap-completing child are
        # absorbed (switch ACKs the source, lane leaves the enqueue set);
        # the completing child forwards as the aggregate. Static flag:
        # INC-off profiles compile the exact pre-INC tick.
        inc_st = s.inc
        inc_absorb = jnp.zeros((Q,), jnp.bool_)
        inc_emit = jnp.zeros((Q,), jnp.bool_)
        if profile.inc:
            member, grank, gsz = inc.member_ranks(
                wl.red, rt.host_leaf[flow_src] != rt.host_leaf[flow_dst],
                (~rod_mask) if any_rod else None)
            into_host = forward & (rt.stage[jnp.clip(nq, 0, Q - 1)]
                                   == jnp.int32(int(Stage.HOST))) \
                & ((pm & META_TRIMMED) == 0)
            inc_st, inc_absorb, inc_emit = inc.process(
                inc_st, lane_flow=safe_pf, lane_psn=pp,
                lane_cand=into_host, member=member, rank=grank, gsz=gsz,
                red=wl.red, has_delivery=has_d)
        inc_reduced = s.inc_reduced + inc_absorb.sum(dtype=jnp.int32)
        inc_emits = s.inc_emits + inc_emit.sum(dtype=jnp.int32)

        # ------------------------------------------------- 7. enqueue phase
        phase("tick.enqueue")
        # candidates: forwarded packets (Q lanes, minus INC absorptions) +
        # injections (F lanes)
        cand_q = jnp.concatenate([jnp.where(forward & ~inc_absorb, nq, -1),
                                  jnp.where(injected, inj_q, -1)])
        cand_flow = jnp.concatenate([pf, jnp.arange(F)])
        cand_psn = jnp.concatenate([pp, psn_out])
        cand_ev = jnp.concatenate([pe, ev_sel])
        cand_meta = jnp.concatenate([pm, jnp.zeros((F,), jnp.int32)])
        cand_ts = jnp.concatenate([pt, jnp.full((F,), 1, jnp.int32) * tick])
        cvalid = cand_q >= 0
        safe_cq = jnp.where(cvalid, cand_q, 0)
        # failed links (traced window mask): packets routed into them vanish
        is_dead = dead[safe_cq] & cvalid
        cvalid = cvalid & ~is_dead
        # gray links: counter-based per-packet loss draw hashed from
        # (scenario seed, tick, enqueue lane) — stateless, so the stream
        # is reproducible across batch/shard/chunk boundaries. The draw
        # is only compiled in when the dispatching schedule has nonzero
        # loss_p (`lossy` static): loss-free runs pay nothing for it.
        if lossy:
            with jax.named_scope("tick.enqueue.loss"):
                u = _mix32(_mix32(tick.astype(jnp.uint32)
                                  ^ fault.seed * jnp.uint32(0x9E3779B1))
                           ^ lane_ids * jnp.uint32(0x85EBCA77))
                is_lost = cvalid & (
                    u < loss_threshold(fault.loss_p)[safe_cq])
                cvalid = cvalid & ~is_lost
        else:
            is_lost = jnp.zeros_like(cvalid)
        if cbfc:
            # CBFC credit gate (repro.core.link.CBFCState semantics,
            # vectorized): available = capacity - (consumed - freed)
            # over 20-bit cyclic counters, where `freed` lags the actual
            # dequeues by the credit-return latency (the delay ring).
            # A candidate without credited space is back-pressured IN
            # PLACE: a forwarded frame never left its upstream queue
            # (that dequeue is cancelled below) and an injection waits
            # at the NIC with zero sender-state trace (the deferred
            # commit_injection). Nothing overflows, so a CBFC fabric
            # never trims for lack of buffer. Deliveries, INC
            # absorptions, and dead/gray-eaten candidates are not
            # enqueues and bypass the gate — no credit leak, and the
            # sink hop always drains, so credits always return (the
            # fabric is a DAG: no credit deadlock).
            arriving = s.cbfc_ret[tick % Rd]
            freed_now = (s.cbfc_freed + arriving.astype(jnp.uint32)) \
                & MASK20
            avail = jnp.int32(C) - ((s.cbfc_consumed - freed_now)
                                    & MASK20).astype(jnp.int32)
            # arrival rank within the target queue: candidates past the
            # credited space stall. Freed credits lag dequeues, so
            # credit-occupancy >= true occupancy and survivors always
            # fit (`fits` below stays all-true under CBFC). Stalled
            # lanes are the per-target rank suffix, so survivor ranks —
            # and hence enqueue positions — are unchanged.
            _, crank = _rank_within(cand_q, cvalid,
                                    jnp.zeros((Q,), jnp.int32))
            stall = cvalid & (crank >= avail[safe_cq])
            cvalid = cvalid & ~stall
            stall_fwd = stall[:Q]
            stall_inj = stall[Q:]
            dequeued = leaves & ~stall_fwd
            q_head = jnp.where(dequeued, (s.q_head + 1) % C, s.q_head)
            q_len = jnp.where(dequeued, s.q_len - 1, s.q_len)
            injected = injected & ~stall_inj
            use_rtx = use_rtx & ~stall_inj
            rtx, next_psn, lbs, inflight, cc_st, retransmits = \
                commit_injection(injected, use_rtx, rtx, next_psn, lbs,
                                 inflight, cc_st)
            credit_stall_ticks = s.credit_stall_ticks \
                + stall.any().astype(jnp.int32)
        else:
            dequeued = leaves
            credit_stall_ticks = s.credit_stall_ticks
        pos, _ = _rank_within(cand_q, cvalid, q_len)
        fits = cvalid & (pos < C)
        overflow = cvalid & ~fits

        wslot = (q_head[jnp.where(cvalid, cand_q, 0)] + pos) % C
        tq = jnp.where(fits, cand_q, Q)
        cand_pkt = jnp.stack(
            [cand_flow, cand_psn, cand_ev, cand_meta, cand_ts], axis=-1)
        with jax.named_scope("tick.enqueue.write"):
            q_pkt = kops.queue_write(s.q_pkt, tq, wslot, cand_pkt)
        hot_enq = (cand_q[None, :] == qidx[:, None]) & fits[None, :]  # [Q, n]
        added = hot_enq.sum(axis=1, dtype=jnp.int32)
        q_len = q_len + added
        if cbfc:
            # commit the cyclic counters: enqueues consume, this tick's
            # dequeues become the credit-update message that reaches the
            # senders `credit_return_ticks` later (the slot just read as
            # `arriving` is exactly Rd ticks old — overwrite it)
            cbfc_consumed = (s.cbfc_consumed + added.astype(jnp.uint32)) \
                & MASK20
            cbfc_freed = freed_now
            cbfc_ret = s.cbfc_ret.at[tick % Rd].set(
                dequeued.astype(jnp.int32))
        else:
            cbfc_consumed = s.cbfc_consumed
            cbfc_freed = s.cbfc_freed
            cbfc_ret = s.cbfc_ret

        # overflow: trim (fast NACK via control TC) or drop
        if p.trimming:
            trims = s.trims + overflow.sum(dtype=jnp.int32)
            drops = s.drops
            nack_mask = overflow
        else:
            trims = s.trims
            drops = s.drops + overflow.sum(dtype=jnp.int32)
            nack_mask = jnp.zeros_like(overflow)
        # failed + gray links drop silently: no trim header, no NACK —
        # only timeout / EV-based inference recovers (Sec. 3.2.4 config
        # and corruption drops)
        drops = drops + is_dead.sum(dtype=jnp.int32) \
            + is_lost.sum(dtype=jnp.int32)
        if corrupty and not llr:
            # corruption without link-layer recovery is a silent drop,
            # charged at the transmitting hop (disjoint from the
            # enqueue-side dead/gray counts above)
            drops = drops + corrupt_lost.sum(dtype=jnp.int32)
        if hosty:
            # dequeue-time losses at a dead destination NIC (section 5)
            drops = drops + dst_gone.sum(dtype=jnp.int32)

        # ------------------------------------------- 8. schedule control TC
        phase("tick.control_tc")
        out_slot = (tick + p.ack_return_ticks) % D
        # lanes [0, Q): ACKs from deliveries and from INC absorptions
        # (the switch ACKs an absorbed child exactly like a delivery
        # would; disjoint from ddata — an absorbed packet never reached
        # the downlink). ROD rejects become OOO NACKs carrying the
        # receiver's first-gap PSN.
        ack_like = ddata | inc_absorb
        if any_rod:
            rod_rej_lane = ddata & rod_rej_f[safe_pf]
            ack_lane_t = jnp.where(
                rod_rej_lane, EV_OOO,
                jnp.where(ack_like, EV_ACK, EV_NONE))
            ack_lane_psn = jnp.where(
                rod_rej_lane,
                dst_track.base[safe_pf].astype(jnp.int32), pp)
        else:
            ack_lane_t = jnp.where(ack_like, EV_ACK, EV_NONE)
            ack_lane_psn = pp
        # lanes [Q, Q + (Q+F)): trim NACKs from enqueue overflow
        nack_lane_t = jnp.where(nack_mask, EV_NACK, EV_NONE)
        # lanes [2Q+F, 2Q+2F): OOO NACKs (psn = receiver base = first gap)
        ooo_lane_t = jnp.where(ooo_fire, EV_OOO, EV_NONE)
        new_type = jnp.concatenate([ack_lane_t, nack_lane_t, ooo_lane_t])
        new_flow = jnp.concatenate([safe_pf, cand_flow, jnp.arange(F)])
        new_psn = jnp.concatenate(
            [ack_lane_psn, cand_psn, dst_track.base.astype(jnp.int32)])
        new_val = jnp.concatenate([pe, cand_ev, jnp.zeros((F,), jnp.int32)])
        new_ecn = jnp.concatenate(
            [((pm & META_ECN) != 0).astype(jnp.int32),
             jnp.zeros((Q + F,), jnp.int32), jnp.zeros((F,), jnp.int32)])
        new_ts = jnp.concatenate([pt, cand_ts, jnp.zeros((F,), jnp.int32)])
        ev_buf = ev_buf.at[out_slot].set(jnp.stack(
            [new_type, new_flow, new_psn, new_val, new_ecn, new_ts],
            axis=-1))

        # ------------------------------------------------- 9. timeouts + QA
        phase("tick.timeouts")
        timeout_fire = timeout_rod  # ROD rewinds already counted as expiries
        if not all_rod:
            # A flow needs the RTO not only while packets are (believed)
            # in flight but whenever sent PSNs are unacked with nothing
            # left to trigger recovery: after a silent loss (dead/gray
            # link) the last ACK can drain `inflight` to 0 with gaps
            # still open, no rtx pending and next_psn == size — without
            # the `unacked` term the flow deadlocks there forever (the
            # terminal phase of every flap scenario).
            unacked = src_track.base.astype(jnp.int32) < next_psn
            stalled = ((inflight > 0) | unacked) & overdue & ~done
            if hosty:
                # a dead endpoint is itself a stall trigger: a frozen
                # source never sends, so `unacked` can't arm — yet the
                # flow can only end via liveness teardown. Keep its RTO
                # clock running so strikes accrue and quarantine fires.
                # (NIC stalls are excluded on purpose: the host is
                # ACK-live, the flow just waits for the heal.)
                stalled = stalled | ((src_dead | dst_dead)
                                     & overdue & ~done)
            if pdc_on:
                # a torn-down PDC stops timing out (and stops striking)
                stalled = stalled & ~s.quarantined
            if mixed_rod:
                stalled = stalled & ~rod_mask  # ROD timeouts rewind instead
            rtx = _set_own_bit(rtx, jnp.zeros((F,), jnp.int32),
                               stalled)  # offset 0 == oldest unacked PSN
            # a timeout implies the outstanding packets are gone (dropped
            # without trim); reset the inflight estimate so the window
            # reopens — otherwise non-trimmed drops leak inflight forever.
            inflight = jnp.where(stalled, 0, inflight)
            last_progress = jnp.where(stalled, tick, last_progress)
            cc_st = cc_pol.on_timeout(cc_st, stalled)
            timeout_fire = timeout_fire | stalled
        cc_st = cc_pol.end_of_tick(cc_st, tick)

        # ---------------------------------------- 10. recovery loop lanes
        phase("tick.recovery")
        # (both arms statically gated: default profiles compile the exact
        # pre-fault-engine tick)
        if backoff_on:
            # exponential RTO backoff on expiry, capped: under a long
            # outage repeated timeouts space out instead of hammering the
            # dead window; any ACK resets to base (section 1).
            rto = jnp.where(
                timeout_fire,
                jnp.minimum(
                    (rto.astype(jnp.float32)
                     * jnp.float32(profile.rto_backoff)).astype(jnp.int32),
                    jnp.int32(rto_cap)),
                rto)
        if evict_on:
            # close the loop: a trim NACK implicates the exact EV it
            # carries (any scheme); an RTO expiry implicates the flow's
            # last-used EV — exact ONLY where selection is pinned
            # (STATIC scheme, incl. the all-ROD pin, and ROD lanes of
            # mixed profiles). Sprayed lanes take no timeout eviction:
            # `last_ev` there is just the most recent random draw, so
            # the guess mostly blacklists healthy EVs and tombstones
            # REPS's known-good recycle ring (observed strictly worse
            # than no eviction on a half-dead fabric) — and spraying
            # escapes dead paths by construction anyway.
            if lb_pol.scheme == LBScheme.STATIC:
                timeout_evict = timeout_fire
            elif mixed_rod:
                timeout_evict = timeout_fire & rod_mask
            else:
                timeout_evict = jnp.zeros((F,), jnp.bool_)
            evict_ev = jnp.where(nack_evict, nack_ev, lbs.last_ev)
            evict_valid = (nack_evict | timeout_evict) & (evict_ev >= 0)
            lbs = lb_pol.evict(lbs, evict_ev, evict_valid)
            ev_evictions = s.ev_evictions + evict_valid.sum(dtype=jnp.int32)
        else:
            ev_evictions = s.ev_evictions
        timeouts = s.timeouts + timeout_fire.sum(dtype=jnp.int32)
        ticks_degraded = s.ticks_degraded + dead.any().astype(jnp.int32)
        if pdc_on:
            # PDC liveness teardown (the fabric-engine mirror of
            # repro.core.pdc.unreachable / InitEvent.PEER_DEAD):
            # consecutive zero-progress RTO expiries accumulate strikes;
            # any ACK is forward progress and resets the count. At
            # `pdc_dead_after` strikes the peer is declared dead and the
            # flow quarantined — no retransmit bandwidth (section 3), no
            # further expiries (section 9), and the quiescence predicate
            # counts it as settled, so permanent endpoint death
            # terminates the run early. A quarantined flow can never
            # complete, so its dependents can never start: collapse the
            # dependency chain (one hop per tick) so those scenarios
            # terminate too.
            rto_strikes = (jnp.where(has_ack, 0, s.rto_strikes)
                           + timeout_fire.astype(jnp.int32))
            newly = (~s.quarantined & ~done
                     & pdc_fsm.unreachable(rto_strikes, dead_after))
            newly = newly | (~s.quarantined & ~done & (wl.dep >= 0)
                             & s.quarantined[safe_dep])
            quarantined = s.quarantined | newly
            inflight = jnp.where(quarantined, 0, inflight)
            flows_abandoned = s.flows_abandoned \
                + newly.sum(dtype=jnp.int32)
            ticks_unreachable = s.ticks_unreachable \
                + quarantined.any().astype(jnp.int32)
        else:
            rto_strikes = s.rto_strikes
            quarantined = s.quarantined
            flows_abandoned = s.flows_abandoned
            ticks_unreachable = s.ticks_unreachable

        ns = SimState(
            q_pkt=q_pkt, q_head=q_head, q_len=q_len,
            next_psn=next_psn, inflight=inflight, src_track=src_track,
            rtx=rtx, last_progress=last_progress, slot_last_ack=slot_last_ack,
            dst_track=dst_track, last_ooo_nack=last_ooo_nack,
            cc=cc_st, lb=lbs,
            ev_buf=ev_buf, inc=inc_st,
            delivered=delivered_ctr, trims=trims, drops=drops, dups=dups,
            inc_reduced=inc_reduced, inc_emits=inc_emits,
            rod_rejects=rod_rejects, retransmits=retransmits,
            rto=rto, timeouts=timeouts, ev_evictions=ev_evictions,
            ticks_degraded=ticks_degraded,
            rto_strikes=rto_strikes, quarantined=quarantined,
            flows_abandoned=flows_abandoned,
            ticks_unreachable=ticks_unreachable,
            llr_busy_until=llr_busy_until, llr_replays=llr_replays,
            cbfc_consumed=cbfc_consumed, cbfc_freed=cbfc_freed,
            cbfc_ret=cbfc_ret, credit_stall_ticks=credit_stall_ticks,
        )
        # the out lanes (the full tier's per-tick record) and the probe
        phase("tick.telemetry")
        out = {
            "delivered": fresh_f.astype(jnp.int32),
            "cwnd": cc_pol.cwnd_view(cc_st, F),
            "qlen_max": q_len.max(),
            "rx_base": dst_track.base,
            "src_base": src_track.base,
        }
        if tel_on:
            # telemetry probe: per-queue event increments off signals
            # the tick already computed. Trim vs silent-drop follows the
            # transport's own split (no-trim profiles drop overflow);
            # dead/gray losses are silent drops by definition. safe_cq
            # holds each event lane's target queue (events are subsets
            # of the pre-filter candidate set).
            if p.trimming:
                trim_ev, drop_ev = overflow, is_dead | is_lost
            else:
                trim_ev = jnp.zeros_like(overflow)
                drop_ev = is_dead | is_lost | overflow
            hot_cand = safe_cq[None, :] == qidx[:, None]       # [Q, Q+F]
            drop_q = (hot_cand & drop_ev[None, :]).sum(
                axis=1, dtype=jnp.int32)
            if corrupty and not llr:
                # unrecovered corruption drops are charged at the
                # TRANSMITTING queue (the loss is on its egress wire)
                drop_q = drop_q + corrupt_lost.astype(jnp.int32)
            out["probe"] = {
                "mark": mark.astype(jnp.int32),
                "trim": (hot_cand & trim_ev[None, :]).sum(
                    axis=1, dtype=jnp.int32),
                "drop": drop_q,
                "rtt": rtt, "has_rtt": has_ack, "cwnd": out["cwnd"],
                # link-layer channels: per-queue LLR replays fired and
                # per-target-queue credit stalls this tick (all-zero
                # lanes when the respective spec is off)
                "llr": (corrupt_hit.astype(jnp.int32) if llr
                        else jnp.zeros((Q,), jnp.int32)),
                "stall": ((hot_cand & stall[None, :]).sum(
                    axis=1, dtype=jnp.int32) if cbfc
                    else jnp.zeros((Q,), jnp.int32)),
            }
        return ns, out

    return step


@dataclass(frozen=True)
class SimResult:
    """One scenario's outcome, in one of two trace tiers.

    ``trace="stats"`` (the default) carries only streaming statistics
    computed inside the scan — per-flow completion ticks, the delivered
    count over one pre-registered goodput window, and the peak queue
    length. Memory traffic is independent of the horizon. The dense
    per-tick lanes are ``None``.

    ``trace="full"`` additionally carries the dense per-tick lanes
    (``delivered_per_tick`` etc.), chunk-buffered on device and
    concatenated on the host — exactly the pre-chunking ``SimResult``.

    ``horizon`` is the number of ticks actually executed: the run exits
    at the first chunk boundary at which the scenario is quiescent (all
    sources CACK-complete, nothing inflight, queues and control-event
    buffers drained), clamped to ``max_ticks`` (the requested budget).
    Every tick past the horizon is provably a protocol no-op, so
    windowed statistics treat missing ticks as zero-delivery — the
    values equal a fixed-``max_ticks`` run bit for bit.

    ``telemetry`` is a :class:`~repro.network.telemetry.FabricTrace`
    when the run was dispatched with ``telemetry=TelemetrySpec.on(...)``
    (``trace="stats"`` only), else ``None``.

    ``driver_chunks`` is ``(fast, masked)``: the chunks the driver's
    while loop ran through its select-free fast body and through its
    masked body (frozen lanes or the budget's last chunk). The counts
    are the executable's (a sharded run's: this lane's device's), the
    same for every lane it ran; its executed ticks are
    ``(fast + masked) * chunk_ticks``. ``trace="full"`` drives the
    chunks from the host and leaves the field ``None``.

    Scalar stat counters (streamed in both trace tiers; each also a
    property here):

    ==================  ====================================================
    property            counts
    ==================  ====================================================
    ``trims``           packets trimmed on queue overflow (fast NACK sent)
    ``drops``           silent drops: dead-link, gray-link, and no-trim
                        overflow losses (no NACK — timeout/OOO recovery)
    ``dups``            duplicate deliveries discarded at the receiver
    ``timeouts``        RTO expiries (RUD stalls + ROD timeout rewinds)
    ``rtx_packets``     retransmitted packets injected
    ``ev_evictions``    path (EV) evictions by the recovery loop
    ``ticks_degraded``  executed ticks with at least one dead link/host
    ``flows_abandoned`` PDCs declared unreachable and torn down
    ``ticks_unreachable``  executed ticks with >= 1 quarantined flow
    ``llr_replays``     frames corrupted on a BER lane and replayed at the
                        hop by LLR (``link=LinkConfig(llr=True)``)
    ``credit_stall_ticks``  executed ticks with >= 1 enqueue back-pressured
                        by CBFC credit exhaustion (``cbfc=True``)
    ==================  ====================================================
    """

    state: SimState
    msg_size: np.ndarray            # [F] message sizes (packets)
    #: ticks actually executed (chunk-aligned early exit; <= max_ticks)
    horizon: int
    #: the requested tick budget (``max_ticks`` arg / ``SimParams.ticks``)
    max_ticks: int
    trace: str = "full"
    # ---- dense lanes (trace="full"; [horizon, ...] on the tick axis) ----
    delivered_per_tick: "np.ndarray | None" = None  # [H, F]
    cwnd_per_tick: "np.ndarray | None" = None       # [H, F]
    qlen_max: "np.ndarray | None" = None            # [H]
    rx_base_per_tick: "np.ndarray | None" = None    # [H, F] receiver CACK
    src_base_per_tick: "np.ndarray | None" = None   # [H, F] source CACK
    # ---- streaming stat lanes (trace="stats") ---------------------------
    stat_completion: "np.ndarray | None" = None      # [F] tick or -1
    stat_src_completion: "np.ndarray | None" = None  # [F] tick or -1
    stat_win_delivered: "np.ndarray | None" = None   # [F] packets in window
    goodput_window: "tuple[int, int] | None" = None
    qlen_peak: "int | None" = None
    #: first tick any PDC teardown fired (-1 = none; stats tier only)
    stat_abandon_tick: "int | None" = None
    #: reconstructed probe-lane time series (telemetry=TelemetrySpec.on())
    telemetry: "telem.FabricTrace | None" = None
    #: (fast, masked) chunks the driver ran (trace="stats"; else None)
    driver_chunks: "tuple[int, int] | None" = None

    def completion_ticks(self) -> np.ndarray:
        """Per-flow first tick by which the full message was delivered
        (-1 where the flow did not finish within the run).

        Completion means the message SIZE was reached — a run that ends
        mid-transfer reports -1, it does not silently count the last
        delivery as "done" (the pre-profile API's bug)."""
        if self.trace == "stats":
            return self.stat_completion.copy()
        cum = self.delivered_per_tick.cumsum(axis=0)
        reached = cum >= self.msg_size[None, :]
        return np.where(reached.any(0), reached.argmax(axis=0), -1)

    def completion_tick(self) -> int:
        """Tick by which EVERY flow completed, as a plain int; -1 if any
        flow was still unfinished when the run ended."""
        ct = self.completion_ticks()
        return -1 if bool((ct < 0).any()) else int(ct.max())

    def source_completion_ticks(self) -> np.ndarray:
        """Per-flow first tick at which the SOURCE saw its whole message
        acknowledged (CACK == size; -1 = unfinished). This is the
        completion notion the dependency lane gates on, and the right
        one under INC, where switch-absorbed packets are ACKed to the
        source but never surface at the receiver."""
        if self.trace == "stats":
            return self.stat_src_completion.copy()
        reached = (self.src_base_per_tick.astype(np.int64)
                   >= self.msg_size[None, :].astype(np.int64))
        return np.where(reached.any(0), reached.argmax(axis=0), -1)

    def source_completion_tick(self) -> int:
        """Tick by which every flow source-completed; -1 if any didn't."""
        ct = self.source_completion_ticks()
        return -1 if bool((ct < 0).any()) else int(ct.max())

    def goodput(self, window: "tuple[int, int] | None" = None) -> np.ndarray:
        """Per-flow delivered packets / tick over a window (fraction of
        line rate, since line rate == 1 packet/tick).

        The window is in budget coordinates: ``[w0, min(w1, max_ticks))``.
        Ticks past the early-exit ``horizon`` count as zero delivery
        (post-quiescence ticks deliver nothing by construction), so the
        value is identical to a fixed-``max_ticks`` run's. Windows that
        start at or past the budget select no ticks and raise.

        ``trace="stats"`` results answer only ``window=None`` (the whole
        budget) or the window pre-registered via ``goodput_window=`` at
        ``simulate()`` time; anything else needs ``trace="full"``.
        """
        mt = self.max_ticks
        w0, w1 = (0, mt) if window is None else window
        w1 = min(int(w1), mt)
        w0 = int(w0)
        if w0 < 0 or w1 <= w0:
            raise ValueError(
                f"goodput window {window!r} selects no ticks within the "
                f"{mt}-tick budget")
        if self.trace == "stats":
            if window is None:
                return np.asarray(self.state.delivered) / float(mt)
            if (self.goodput_window is not None
                    and tuple(int(w) for w in window)
                    == tuple(int(w) for w in self.goodput_window)):
                return self.stat_win_delivered / float(w1 - w0)
            raise ValueError(
                f"trace='stats' recorded only the pre-registered goodput "
                f"window {self.goodput_window!r}; pass goodput_window="
                f"{tuple(window)!r} to simulate()/simulate_batch() or use "
                f"trace='full' for arbitrary windows")
        d = self.delivered_per_tick[w0:min(w1, self.horizon)]
        return d.sum(axis=0) / float(w1 - w0)

    # ---- scalar stat counters (streamed in both trace tiers; see the
    # ---- class docstring table) -----------------------------------------
    @property
    def trims(self) -> int:
        """Packets trimmed on queue overflow (each sent a fast NACK)."""
        return int(self.state.trims)

    @property
    def drops(self) -> int:
        """Silent drops — dead-link, gray-link, and (no-trim profiles)
        overflow losses. No NACK: only timeout/OOO inference recovers."""
        return int(self.state.drops)

    @property
    def dups(self) -> int:
        """Duplicate deliveries discarded at the receiver."""
        return int(self.state.dups)

    @property
    def timeouts(self) -> int:
        """RTO expiries over the run (RUD stalls + ROD timeout rewinds)."""
        return int(self.state.timeouts)

    @property
    def rtx_packets(self) -> int:
        """Retransmitted packets injected over the run."""
        return int(self.state.retransmits)

    @property
    def ev_evictions(self) -> int:
        """Path (EV) evictions performed by the recovery loop (0 unless
        ``TransportProfile.ev_eviction`` is on)."""
        return int(self.state.ev_evictions)

    @property
    def ticks_degraded(self) -> int:
        """Executed ticks during which at least one link or host was
        dead."""
        return int(self.state.ticks_degraded)

    @property
    def flows_abandoned(self) -> int:
        """Flows whose PDC was declared unreachable and torn down (0
        unless ``TransportProfile.pdc_dead_after`` is set)."""
        return int(self.state.flows_abandoned)

    @property
    def ticks_unreachable(self) -> int:
        """Executed ticks during which at least one flow sat
        quarantined (the unavailability window a recovery controller
        would observe)."""
        return int(self.state.ticks_unreachable)

    @property
    def llr_replays(self) -> int:
        """Frames corrupted on a BER lane (``FaultSchedule.corrupt``)
        and replayed at the hop by link-level retry — each one a loss
        that never reached end-to-end recovery (0 unless the run was
        dispatched with ``link=LinkConfig(llr=True)``)."""
        return int(self.state.llr_replays)

    @property
    def credit_stall_ticks(self) -> int:
        """Executed ticks on which at least one enqueue was
        back-pressured by CBFC credit exhaustion instead of overflowing
        (0 unless ``link=LinkConfig(cbfc=True)``)."""
        return int(self.state.credit_stall_ticks)

    @property
    def abandon_tick(self) -> int:
        """First tick at which any PDC teardown fired (-1 = none).
        Streamed on the ``trace="stats"`` tier — the detection-time
        signal the recovery-pricing path converts to seconds."""
        if self.stat_abandon_tick is None:
            raise ValueError(
                "abandon_tick is streamed on the trace='stats' tier "
                "only; rerun with trace='stats'")
        return int(self.stat_abandon_tick)


# --------------------------------------------------------------------------
# scenario engine: chunked while-scan driver + compiled-run cache
# --------------------------------------------------------------------------

TRACE_MODES = ("stats", "full")


def _quiescent(s: SimState, wl: Workload) -> jax.Array:
    """Scenario-wide quiescence: no future tick can make protocol
    progress. Requires every source CACK-complete, nothing inflight, all
    queues empty, and the control-TC delay ring free of pending events.
    (Flows that never became eligible — future ``start``, unsatisfied
    ``dep`` — keep ``done`` false, so such scenarios run to the budget.)

    Post-quiescence ticks still mutate tick-stamped bookkeeping (CC
    epoch state, stale control-ring timestamp lanes), so the engine
    FREEZES the carry once a scenario is quiescent: the executed prefix,
    final counters, and completion ticks are bitwise what a longer fixed
    run would produce.

    A quarantined flow (PDC liveness teardown, `pdc_dead_after`) counts
    as settled: it can make no further progress by construction, so a
    permanently dead endpoint no longer pins the scenario to the full
    tick budget. (With the lane all-False — every default — the
    predicate is value-identical to the pre-quarantine one.)"""
    done = ((s.src_track.base.astype(jnp.int32) >= wl.size)
            | s.quarantined).all()
    idle = (s.inflight == 0).all() & (s.q_len == 0).all()
    drained = (s.ev_buf[:, :, EVF_TYPE] == EV_NONE).all()
    return done & idle & drained


def _freeze(run, new, old):
    """Carry-wide select: keep `new` where `run` is set. `run` is a
    scalar (serial driver) or a per-lane [B] vector (the hand-batched
    driver), broadcast against each leaf's trailing axes."""
    def sel(a, b):
        r = run.reshape(run.shape + (1,) * (a.ndim - run.ndim))
        return jnp.where(r, a, b)
    return jax.tree_util.tree_map(sel, new, old)


def _stats_init(F: int) -> dict:
    return {
        "comp": jnp.full((F,), -1, jnp.int32),
        "src_comp": jnp.full((F,), -1, jnp.int32),
        "win_delivered": jnp.zeros((F,), jnp.int32),
        "qlen_peak": jnp.int32(0),
        "abandon_tick": jnp.int32(-1),
    }


def _stats_update(st: dict, prev: SimState, s: SimState, wl: Workload,
                  tick, w0, w1) -> dict:
    """In-scan streaming statistics — the trace="stats" lanes. Each is
    an elementwise [F] update off state the tick already computed, so
    recording costs no extra memory traffic on the horizon axis."""
    fresh = s.delivered - prev.delivered
    inwin = (tick >= w0) & (tick < w1)
    rx_done = s.delivered >= wl.size
    src_done = s.src_track.base.astype(jnp.int32) >= wl.size
    return {
        "comp": jnp.where((st["comp"] < 0) & rx_done, tick, st["comp"]),
        "src_comp": jnp.where((st["src_comp"] < 0) & src_done, tick,
                              st["src_comp"]),
        "win_delivered": st["win_delivered"] + jnp.where(inwin, fresh, 0),
        "qlen_peak": jnp.maximum(st["qlen_peak"], s.q_len.max()),
        # first tick any PDC teardown fired — the recovery-pricing
        # detection-time signal (-1 = no abandonment this run)
        "abandon_tick": jnp.where(
            (st["abandon_tick"] < 0) & (s.flows_abandoned > 0),
            tick, st["abandon_tick"]),
    }


#: compiled run cache. Keyed on (topology identity, profile, params
#: minus the horizon, flow count, batch mode, trace tier): workloads,
#: seeds, failure masks AND the tick budget are traced, so scenario
#: sweeps at any horizon reuse one executable; profiles are static and
#: pick the executable. `id(g)` is part of the key because the compiled
#: step bakes in g's wiring tables — two graphs sharing a name must not
#: share an executable. (The cached closure keeps `g` alive via its
#: RoutingTables, so a live entry's id can't be recycled by a different
#: graph.)
_RUN_CACHE: dict = {}


def _cache_key(g: QueueGraph, profile: TransportProfile, p: SimParams,
               F: int, batched: bool, trace: str = "stats", shard=None,
               lossy: bool = False, tel: "TelemetrySpec | None" = None,
               hosty: bool = False, corrupty: bool = False,
               link: "LinkConfig | None" = None):
    # the horizon (p.ticks) is a traced bound, not a compiled constant:
    # strip it so one executable serves every tick budget. `shard` is
    # None (unsharded) or the device-id tuple a sharded executable was
    # built for (repro.network.shard). `lossy` selects the executable
    # with the gray-link loss draw compiled in (see make_step). `tel`
    # (a TelemetrySpec, static like the profile) selects the executable
    # with the probe lanes compiled in; None and the off spec share the
    # pre-telemetry entry.
    # `hosty` selects the executable with the endpoint-fault lanes
    # compiled in (host/NIC outage windows; see make_step) — schedules
    # without host lanes share the pre-endpoint entry.
    # `corrupty` (schedule-derived, like lossy/hosty) selects the
    # executable with the PHY-corruption draw compiled in; `link` (a
    # LinkConfig, user-static like tel) selects the one with the
    # LLR/CBFC lanes armed — None and the off spec share the
    # pre-link-layer entry.
    if tel is not None and not tel.enabled:
        tel = None
    if link is not None and not link.enabled:
        link = None
    return (id(g), g.name, profile, replace(p, ticks=0), F, batched, trace,
            shard, lossy, tel, hosty, corrupty, link)


def _build_fns(g: QueueGraph, profile: TransportProfile, p: SimParams,
               F: int, batched: bool, trace: str, lossy: bool = False,
               tel: "TelemetrySpec | None" = None, hosty: bool = False,
               corrupty: bool = False,
               link: "LinkConfig | None" = None):
    """(init, run) pair for one trace tier — UN-jitted, so the sharded
    engine (repro.network.shard) can wrap the same driver in shard_map
    before compiling. `_get_fns` jits and caches; behavior contract:

    ``trace="stats"`` builds the whole adaptive-horizon run as ONE
    device program: a ``lax.while_loop`` whose body scans a
    ``chunk_ticks``-long chunk (streaming the stat lanes in the scan
    carry) and whose predicate stops once every lane is quiescent or at
    the (traced) budget. The loop counts the chunks it ran through each
    branch below (stat leaves ``fast_chunks`` and ``masked_chunks``).

    ``trace="full"`` builds ONE CHUNK (scan + per-tick out lanes +
    quiescence flag, time-major: ``[chunk, B?, ...]``); the host drives
    the chunk loop and concatenates the buffered lanes.

    Batching is by hand — the scenario axis is an explicit leading [B]
    axis (the per-tick step/stat/quiescence functions are vmapped, the
    chunk loop is written once over lane vectors) rather than a vmap of
    the whole driver. That keeps the chunk dispatch a SCALAR decision,
    which buys the driver fast path: whenever no lane is frozen and the
    chunk lies strictly below the budget (every chunk of a
    never-quiescing sweep except a non-multiple remainder), a
    ``lax.cond`` runs a select-free tick body — bitwise identical to
    the masked body, whose selects all have a true predicate there —
    and the carry-wide freeze/budget selects are paid only by the
    residual chunks that can actually need them. Per-lane trajectories
    are unchanged: a stopped lane is frozen at its own chunk boundary,
    and a partial final chunk cannot overrun the budget.
    """
    tel_on = tel is not None and tel.enabled
    if tel_on and trace != "stats":
        raise ValueError(
            "telemetry lanes ride the streaming stats carry — enabled "
            "TelemetrySpec requires trace='stats' (the full tier already "
            "records dense per-tick lanes)")
    step = make_step(g, profile, p, F, lossy, tel if tel_on else None,
                     hosty=hosty, corrupty=corrupty, link=link)
    chunk = int(p.chunk_ticks)
    if chunk < 1:
        raise ValueError(f"chunk_ticks must be >= 1, got {chunk}")
    xs = jnp.arange(chunk, dtype=jnp.int32)

    def init_one(wl, seed):
        return init_state(g, wl, profile, p, seed, link=link)

    # the stat transition with the telemetry lanes riding inside it:
    # st["tel"] carries the probe rings (see repro.network.telemetry).
    # Off (the default), the wrapper ignores the step's out dict and the
    # carry/stat tree — and therefore the compiled program — is exactly
    # the pre-telemetry one.
    if tel_on:
        tel_up = telem.make_update(tel, g.num_queues, F)

        def stat_one(st, prev, s, wl, tick, w0, w1, out):
            nst = _stats_update(st, prev, s, wl, tick, w0, w1)
            nst["tel"] = tel_up(st["tel"], s, out["probe"], tick)
            return nst
    else:
        def stat_one(st, prev, s, wl, tick, w0, w1, out):
            del out
            return _stats_update(st, prev, s, wl, tick, w0, w1)

    def stats_init():
        st = _stats_init(F)
        if tel_on:
            st["tel"] = telem.create(tel, g.num_queues, F)
        return st

    if batched:
        init_fn = jax.vmap(init_one)
        stepf = jax.vmap(step, in_axes=(0, None, 0, 0))
        quiet = jax.vmap(_quiescent)
        statf = jax.vmap(stat_one,
                         in_axes=(0, 0, 0, 0, None, None, None, 0))
    else:
        init_fn, stepf, quiet, statf = (init_one, step, _quiescent,
                                        stat_one)

    if trace == "stats":
        def run(s0, wl, fault, budget, w0, w1):
            bshape = wl.src.shape[:-1]          # () serial, (B,) batched

            def chunk_scan(s, st, tick0, stop):
                # ONE tick body serves both cond branches, so the
                # fast-path contract (fast == masked with all-true
                # predicates; where(True, a, b) == a, bitwise) lives
                # in one place: `stop=None` builds the select-free fast
                # body, a lane vector builds the masked residual body
                # (select against the budget + per-lane freeze flags —
                # the only carry leaves selected are the ones that can
                # change, SimState + stat lanes).
                def tick_body(c, i):
                    s, st = c
                    tick = tick0 + i
                    ns, out = stepf(s, tick, wl, fault)
                    with jax.named_scope("driver.stats"):
                        nst = statf(st, s, ns, wl, tick, w0, w1, out)
                    if stop is None:
                        return (ns, nst), None
                    with jax.named_scope("driver.freeze"):
                        live = (tick < budget) & ~stop
                        return _freeze(live, (ns, nst), (s, st)), None

                (s, st), _ = jax.lax.scan(tick_body, (s, st), xs)
                return s, st

            def fast_chunk(ops):
                s, st, tick0, _ = ops
                return chunk_scan(s, st, tick0, None)

            def masked_chunk(ops):
                s, st, tick0, stop = ops
                return chunk_scan(s, st, tick0, stop)

            def body(c):
                s, st, tick0, stop, hz, n_fast, n_masked = c
                fast = (tick0 + chunk <= budget) & ~stop.any()
                s, st = jax.lax.cond(fast, fast_chunk, masked_chunk,
                                     (s, st, tick0, stop))
                with jax.named_scope("driver.stats"):
                    # the chunks this loop ran through each branch
                    n_fast = n_fast + fast.astype(jnp.int32)
                    n_masked = n_masked + (~fast).astype(jnp.int32)
                tick0 = tick0 + jnp.int32(chunk)
                with jax.named_scope("driver.quiescent"):
                    nstop = stop | quiet(s, wl) | (tick0 >= budget)
                    hz = jnp.where(nstop & ~stop,
                                   jnp.minimum(tick0, budget), hz)
                return s, st, tick0, nstop, hz, n_fast, n_masked

            stop0 = jnp.broadcast_to(budget <= jnp.int32(0), bshape)
            hz0 = jnp.where(stop0, jnp.minimum(jnp.int32(0), budget), -1)
            st0 = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, bshape + a.shape),
                stats_init())
            zero = jnp.zeros(bshape, jnp.int32)
            s, st, _, _, hz, n_fast, n_masked = jax.lax.while_loop(
                lambda c: ~c[3].all(), body,
                (s0, st0, jnp.int32(0), stop0, hz0, zero, zero))
            # per-lane [B] leaves, so a sharded run keeps its specs and
            # each lane reports its own executable's (or shard's) loop
            return s, dict(st, fast_chunks=n_fast, masked_chunks=n_masked), hz

        return init_fn, run

    if trace == "full":
        def run_chunk(s0, stopped, tick0, wl, fault, budget):
            def chunk_scan(s0, stop):
                # stop=None -> the select-free fast body (see the stats
                # tier: one tick body keeps the bitwise contract)
                def tick_body(s, i):
                    tick = tick0 + i
                    ns, out = stepf(s, tick, wl, fault)
                    if stop is None:
                        return ns, out
                    with jax.named_scope("driver.freeze"):
                        live = (tick < budget) & ~stop
                        return _freeze(live, ns, s), out

                return jax.lax.scan(tick_body, s0, xs)

            do_fast = (tick0 + chunk <= budget) & ~stopped.any()
            s, outs = jax.lax.cond(do_fast,
                                   lambda s0: chunk_scan(s0, None),
                                   lambda s0: chunk_scan(s0, stopped), s0)
            with jax.named_scope("driver.quiescent"):
                return s, stopped | quiet(s, wl), outs

        return init_fn, run_chunk

    raise ValueError(
        f"unknown trace tier {trace!r}; choose from {TRACE_MODES}")


def _get_fns(g: QueueGraph, profile: TransportProfile, p: SimParams,
             F: int, batched: bool, trace: str, lossy: bool = False,
             tel: "TelemetrySpec | None" = None, hosty: bool = False,
             corrupty: bool = False, link: "LinkConfig | None" = None):
    """Jitted + cached (init, run) pair — see `_build_fns` for the
    driver contract. Both runs donate the carry."""
    key = _cache_key(g, profile, p, F, batched, trace, lossy=lossy, tel=tel,
                     hosty=hosty, corrupty=corrupty, link=link)
    fns = _RUN_CACHE.get(key)
    if fns is None:
        init_fn, run = _build_fns(g, profile, p, F, batched, trace, lossy,
                                  tel, hosty, corrupty, link)
        fns = (jax.jit(init_fn), jax.jit(run, donate_argnums=(0,)))
        _RUN_CACHE[key] = fns
    return fns


def driver_fns(g: QueueGraph, profile: TransportProfile, p: SimParams,
               F: int, fault: FaultSchedule, trace: str, batched: bool,
               tel: "TelemetrySpec | None" = None,
               link: "LinkConfig | None" = None, devs=None):
    """The cached (init, run) pair that ``simulate`` (``batched=False``)
    or ``simulate_batch`` runs for `fault`; `devs` (a device tuple)
    selects the sharded executable. The one place the schedule-derived
    statics (``lossy``, ``hosty``, ``corrupty``) are read off a schedule."""
    statics = dict(lossy=bool(np.asarray(fault.loss_p).any()),
                   hosty=fault.has_host_faults,
                   corrupty=fault.has_corruption, tel=tel, link=link)
    if devs is None:
        return _get_fns(g, profile, p, F, batched=batched, trace=trace,
                        **statics)
    from repro.network import shard
    return shard._sharded_fns(g, profile, p, F, trace, devs, **statics)


def _run_full_host(run_chunk, s0, wl, fault, budget: int, chunk: int,
                   batch: "int | None"):
    """Drive the trace="full" chunk executable from the host: run chunks
    until every scenario is quiescent or the budget is spent, buffering
    the dense out lanes per chunk and concatenating once at the end.

    Returns (final_state, outs, horizon[np int64 array]) — `horizon[b]`
    is scenario b's own stop boundary (min(chunk end, budget)), which is
    also where its carry froze, so slicing lane b to `horizon[b]` reproduces
    the serial run of that scenario exactly. The dense out lanes are
    time-major: ``[T]`` serial, ``[T, B, ...]`` batched.
    """
    serial = batch is None
    nb = 1 if serial else batch
    stopped = jnp.zeros((() if serial else (nb,)), bool)
    horizon = np.full((nb,), -1, np.int64)
    s = s0
    chunks: list = []
    tick0 = 0
    while True:
        s, stopped, outs = run_chunk(s, stopped, jnp.int32(tick0), wl, fault,
                                     jnp.int32(budget))
        chunks.append(jax.device_get(outs))
        tick0 += chunk
        t_end = min(tick0, budget)
        stop_np = np.atleast_1d(np.asarray(stopped))
        horizon[(horizon < 0) & stop_np] = t_end
        if tick0 >= budget or stop_np.all():
            break
    horizon[horizon < 0] = budget
    outs = {k: np.concatenate([c[k] for c in chunks], axis=0)
            for k in chunks[0]}
    return s, outs, horizon


def _window_bounds(goodput_window, budget: int) -> "tuple[int, int]":
    if goodput_window is None:
        return 0, budget
    w0, w1 = goodput_window
    return int(w0), int(w1)


def _check_trace(trace: str):
    if trace not in TRACE_MODES:
        raise ValueError(f"unknown trace tier {trace!r}; choose from "
                         f"{TRACE_MODES}")


def _normalize_call(profile, p, failed):
    """The single conversion point from the public signatures to the
    engine's (profile, numeric-only params, failure spec).

    The pre-profile positional form — ``simulate(g, wl, SimParams(...))``
    — is accepted for one more release: it warns and runs the default
    ai_full() composition, which is exactly what the removed legacy
    transport knobs composed to when left unset. The knobs themselves
    (``mode``/``lb``/``nscc``/``rccc``/``failed_queues``) are gone from
    SimParams: call sites that set them now fail at construction.
    """
    if isinstance(profile, SimParams):
        if p is not None:
            raise TypeError("got SimParams in the profile position AND a "
                            "params argument — pass (profile, params)")
        warnings.warn(
            "simulate(g, wl, SimParams(...)) is deprecated: pass the "
            "transport composition explicitly — "
            "simulate(g, wl, TransportProfile.ai_full(), SimParams(...))",
            DeprecationWarning, stacklevel=3)
        p = profile
        profile = TransportProfile.ai_full()
    else:
        if profile is None:
            profile = TransportProfile.ai_full()
        if p is None:
            p = SimParams()
    return profile, p, failed


def _failed_to_mask(g: QueueGraph, failed) -> np.ndarray:
    """[Q] bool mask from None / queue-id iterable / bool mask."""
    if failed is None:
        return np.zeros((g.num_queues,), bool)
    arr = np.asarray(failed)
    if arr.dtype == bool:
        if arr.shape != (g.num_queues,):
            raise ValueError(f"failed mask must be [Q={g.num_queues}], "
                             f"got {arr.shape}")
        return arr
    if arr.size and (arr.min() < 0 or arr.max() >= g.num_queues):
        raise ValueError(f"failed queue ids must be in [0, {g.num_queues}); "
                         f"pass a bool array to give a mask instead")
    mask = np.zeros((g.num_queues,), bool)
    mask[arr.astype(np.int64)] = True
    return mask


def _full_result(final: SimState, outs: dict, msg_size, horizon: int,
                 budget: int) -> SimResult:
    return SimResult(
        state=final, msg_size=np.asarray(msg_size),
        horizon=int(horizon), max_ticks=int(budget), trace="full",
        delivered_per_tick=np.asarray(outs["delivered"])[:horizon],
        cwnd_per_tick=np.asarray(outs["cwnd"])[:horizon],
        qlen_max=np.asarray(outs["qlen_max"])[:horizon],
        rx_base_per_tick=np.asarray(outs["rx_base"])[:horizon],
        src_base_per_tick=np.asarray(outs["src_base"])[:horizon],
    )


def _stats_result(final: SimState, st: dict, msg_size, horizon: int,
                  budget: int, goodput_window,
                  tel: "TelemetrySpec | None" = None) -> SimResult:
    trace_obj = None
    if tel is not None and tel.enabled:
        trace_obj = telem.FabricTrace.from_lanes(tel, st["tel"],
                                                 int(horizon))
    return SimResult(
        state=final, msg_size=np.asarray(msg_size),
        horizon=int(horizon), max_ticks=int(budget), trace="stats",
        stat_completion=np.asarray(st["comp"]),
        stat_src_completion=np.asarray(st["src_comp"]),
        stat_win_delivered=np.asarray(st["win_delivered"]),
        goodput_window=(None if goodput_window is None
                        else tuple(int(w) for w in goodput_window)),
        qlen_peak=int(st["qlen_peak"]),
        stat_abandon_tick=int(st["abandon_tick"]),
        telemetry=trace_obj,
        driver_chunks=(int(st["fast_chunks"]), int(st["masked_chunks"])),
    )


def _to_result(final: SimState, outs: dict, msg_size) -> SimResult:
    """Wrap a fixed-length scan's raw (state, out-lanes) as a full-trace
    SimResult (horizon == the recorded length; bench/diagnostic helper
    for hand-rolled scans outside the chunked driver)."""
    t = int(np.asarray(outs["delivered"]).shape[0])
    return _full_result(jax.device_get(final), outs, msg_size, t, t)


def _check_telemetry(telemetry, trace: str) -> "TelemetrySpec | None":
    """Normalize/validate the telemetry= kwarg: None or an off spec is
    the free pre-telemetry path; enabled specs need trace='stats'."""
    if telemetry is None:
        return None
    if not isinstance(telemetry, TelemetrySpec):
        raise TypeError(f"telemetry= takes a TelemetrySpec, got "
                        f"{type(telemetry).__name__}")
    if not telemetry.enabled:
        return None
    if trace != "stats":
        raise ValueError(
            "telemetry lanes ride the streaming stats carry — enabled "
            "TelemetrySpec requires trace='stats'")
    return telemetry


def _check_link(link) -> "LinkConfig | None":
    """Normalize/validate the link= kwarg: None or an off spec is the
    free pre-link-layer path (identical cache key, identical program)."""
    if link is None:
        return None
    if not isinstance(link, LinkConfig):
        raise TypeError(f"link= takes a LinkConfig, got "
                        f"{type(link).__name__}")
    if not link.enabled:
        return None
    return link


def simulate(g: QueueGraph, wl: Workload,
             profile: "TransportProfile | SimParams | None" = None,
             p: "SimParams | None" = None, *,
             seed: int = DEFAULT_SEED, failed=None, faults=None,
             trace: str = "stats", max_ticks: "int | None" = None,
             goodput_window: "tuple[int, int] | None" = None,
             telemetry: "TelemetrySpec | None" = None,
             link: "LinkConfig | None" = None) -> SimResult:
    """Run one scenario for at most ``max_ticks`` (default p.ticks),
    exiting early at the first chunk boundary where the scenario is
    quiescent.

    profile: the transport composition (defaults to ai_full()). Passing a
             SimParams here takes the deprecated pre-profile path.
    failed:  queue ids (tuple) or [Q] bool mask of dead links.
    faults:  a [Q] :class:`~repro.network.faults.FaultSchedule` — link
             flaps and gray (lossy) links with per-queue timing. Mutually
             exclusive with ``failed`` (which is sugar for the static
             ``from_mask`` schedule). Traced: sweeping schedules reuses
             the executable.
    trace:   "stats" (default — streaming stat lanes only, one device
             program) or "full" (dense per-tick lanes, chunk-buffered).
    max_ticks: plain tick-budget bound; traced, so sweeping it reuses
             the compiled executable.
    goodput_window: (w0, w1) to record in-scan for trace="stats" so
             ``result.goodput((w0, w1))`` works without a dense trace.
    telemetry: a :class:`~repro.network.telemetry.TelemetrySpec`. The
             spec is STATIC (it picks the executable, like the profile);
             enabled specs stream the selected probe channels into
             fixed-size decimated ring lanes riding the stats carry and
             attach the reconstructed :class:`~repro.network.telemetry.
             FabricTrace` as ``result.telemetry``. ``None`` / the off
             spec compile the identical pre-telemetry program.
    link:    a :class:`~repro.core.link.LinkConfig` (static, like the
             profile and the telemetry spec): arms per-queue LLR replay
             and/or the CBFC credit gate — see ``make_step``. ``None`` /
             ``LinkConfig.off()`` compile the identical pre-link-layer
             program.
    """
    profile, p, failed = _normalize_call(profile, p, failed)
    _check_trace(trace)
    tel = _check_telemetry(telemetry, trace)
    link = _check_link(link)
    budget = int(p.ticks if max_ticks is None else max_ticks)
    F = int(wl.src.shape[0])
    profile.delivery_modes(F)  # validate per-flow tuples early
    fault = as_schedule(g.num_queues, failed, faults,
                        g_num_hosts=g.num_hosts)
    if fault is None:
        fault = FaultSchedule.from_mask(_failed_to_mask(g, failed))
    init, run = driver_fns(g, profile, p, F, fault, trace, batched=False,
                           tel=tel, link=link)
    s0 = init(wl, jnp.uint32(seed))
    if trace == "stats":
        w0, w1 = _window_bounds(goodput_window, budget)
        final, st, horizon = run(s0, wl, fault, jnp.int32(budget),
                                 jnp.int32(w0), jnp.int32(w1))
        return _stats_result(jax.device_get(final), jax.device_get(st),
                             wl.size, int(horizon), budget, goodput_window,
                             tel=tel)
    final, outs, horizon = _run_full_host(run, s0, wl, fault, budget,
                                          p.chunk_ticks, batch=None)
    return _full_result(jax.device_get(final), outs, wl.size,
                        int(horizon[0]), budget)


def _split_stats_results(final, st, sizes, horizon, budget, goodput_window,
                         B: int,
                         tel: "TelemetrySpec | None" = None
                         ) -> "list[SimResult]":
    """Per-scenario SimResults from host-side batched stats lanes (lanes
    past B — shard padding — are dropped)."""
    return [
        _stats_result(
            jax.tree_util.tree_map(lambda a: a[b], final),
            jax.tree_util.tree_map(lambda a: a[b], st),
            sizes[b], int(horizon[b]), budget, goodput_window, tel=tel)
        for b in range(B)
    ]


def _split_full_results(final, outs, sizes, horizon, budget,
                        B: int) -> "list[SimResult]":
    """Per-scenario SimResults from time-major dense out lanes
    ([T, Bp, ...]; lanes past B — shard padding — are dropped)."""
    return [
        _full_result(
            jax.tree_util.tree_map(lambda a: a[b], final),
            {k: v[:, b] for k, v in outs.items()},
            sizes[b], int(horizon[b]), budget)
        for b in range(B)
    ]


def _run_batch(g, wls, profile, p, fault, seeds, trace, budget,
               goodput_window, devices=None, tel=None,
               link=None) -> "list[SimResult]":
    if devices is not None:
        from repro.network import shard
        return shard.run_sharded(g, wls, profile, p, fault, seeds, trace,
                                 budget, goodput_window, devices, tel=tel,
                                 link=link)
    with _Spans(jax.profiler.TraceAnnotation) as span:
        span("fabric.prepare")
        B, F = wls.src.shape
        profile.delivery_modes(F)
        init, run = driver_fns(g, profile, p, F, fault, trace,
                               batched=True, tel=tel, link=link)
        sizes = np.asarray(wls.size)
        span("fabric.init")
        s0 = init(wls, seeds)
        if trace == "stats":
            w0, w1 = _window_bounds(goodput_window, budget)
            span("fabric.run")
            final, st, horizon = run(s0, wls, fault, jnp.int32(budget),
                                     jnp.int32(w0), jnp.int32(w1))
            span("fabric.fetch")
            final = jax.device_get(final)
            st = jax.device_get(st)
            horizon = np.asarray(horizon)
            span("fabric.split")
            return _split_stats_results(final, st, sizes, horizon, budget,
                                        goodput_window, B, tel=tel)
        span("fabric.run")
        final, outs, horizon = _run_full_host(run, s0, wls, fault, budget,
                                              p.chunk_ticks, batch=B)
        span("fabric.fetch")
        final = jax.device_get(final)
        span("fabric.split")
        return _split_full_results(final, outs, sizes, horizon, budget, B)


@partial(jax.profiler.annotate_function, name="fabric.simulate_batch")
def simulate_batch(g: QueueGraph, wls: Workload,
                   profile=None, p: "SimParams | None" = None, *,
                   failed=None, faults=None, seeds=None,
                   trace: str = "stats", max_ticks: "int | None" = None,
                   goodput_window: "tuple[int, int] | None" = None,
                   shard: bool = False, devices=None,
                   telemetry: "TelemetrySpec | None" = None,
                   link: "LinkConfig | None" = None
                   ) -> "list[SimResult]":
    """Run B scenarios as compiled, batched chunked while-scans.

    g:       one QueueGraph for every scenario, or a length-B list of
             per-scenario graphs. Topologies, like profiles, are static
             (the compiled step bakes in a graph's wiring tables), so a
             per-scenario list groups the batch by (graph, profile) —
             one executable per distinct pair, with groups running on
             worker threads and results reassembled by scenario index.
             This is what makes a co-design sweep (topology x profile x
             workload, see `repro.network.traffic`) ONE call.
    wls:     Workload with a leading scenario axis ([B, F]); build with
             ``Workload.stack`` or pass a list of same-F Workloads.
    profile: one TransportProfile for every scenario, or a length-B list
             of per-scenario profiles. Profiles are static, so the batch
             is grouped by distinct profile — each group runs as one
             vmapped scan sharing one executable (a profile-ablation grid
             is one call here and one compile per profile).
    failed:  optional per-scenario failed-queue spec: [B, Q] bool, one
             [Q] mask, or a queue-id tuple (broadcast to every scenario).
    faults:  optional [B, Q] (or [Q], broadcast) FaultSchedule — dynamic
             flap windows + gray-link loss per scenario. Mutually
             exclusive with ``failed``; rides the scenario axis like
             workloads and seeds (traced, shard-compatible).
    seeds:   optional [B] — per-scenario LB/EV seeds (default: the same
             DEFAULT_SEED every ``simulate`` call uses).
    trace / max_ticks / goodput_window: as in :func:`simulate`. The tick
             budget is traced — sweeping it reuses the executable — and
             each group runs until its slowest scenario is quiescent,
             with faster lanes frozen at their own stop boundary.
    shard / devices: shard the scenario axis across devices with
             ``shard_map`` (repro.network.shard). ``shard=True`` uses
             every ``jax.devices()``; ``devices=`` takes an int (first n
             devices) or an explicit device sequence. Composes with
             per-scenario profiles (each profile group is sharded);
             ragged scenario counts are padded with inert no-op lanes
             and the padding is dropped from the results. Per-lane
             results stay bitwise identical to the unsharded path.
    telemetry: one :class:`~repro.network.telemetry.TelemetrySpec` for
             the whole batch (static: the spec picks the executable,
             like the profile). Enabled specs stream each scenario's
             probe channels into its own ring lanes — vmapped on the
             scenario axis, sharded with it, inert on padding lanes —
             and attach per-scenario ``result.telemetry`` traces,
             bitwise identical to the serial ``simulate`` call's.
    link:    one :class:`~repro.core.link.LinkConfig` for the whole
             batch (static, like the telemetry spec): arms the LLR /
             CBFC lanes on every scenario. ``None`` / the off spec
             compile the identical pre-link-layer program.

    Returns one SimResult per scenario, bitwise identical to the
    corresponding serial ``simulate`` call: the tick function is the same
    compiled code, vmapped over the scenario axis with the carry donated,
    and each lane freezes at the same chunk boundary the serial run
    exits at. (``driver_chunks`` alone differs: it counts the chunks of
    the loop that ran the lane, which here runs until the batch's, or
    the device's, slowest lane stops.)

    Under ``jax.profiler`` the call is the host span
    ``fabric.simulate_batch``, cut into ``fabric.prepare``, ``.init``,
    ``.run``, ``.fetch`` and ``.split`` (DESIGN.md, "Spans and
    counters").
    """
    # closed once the batch is ready to dispatch (on an error, with the
    # frame); the dispatch opens its own spans
    prep = _Spans(jax.profiler.TraceAnnotation)
    prep("fabric.prepare")
    if isinstance(wls, (list, tuple)):
        wls = Workload.stack(wls)
    if shard or devices is not None:
        from repro.network.shard import resolve_devices
        devices = resolve_devices(devices, shard)
    else:
        devices = None
    graphs = None
    if isinstance(g, (list, tuple)):
        graphs = list(g)
        if not graphs:
            raise ValueError("per-scenario topology list is empty")
        if not all(isinstance(gr, QueueGraph) for gr in graphs):
            raise TypeError("per-scenario topologies must all be "
                            "QueueGraph instances")
        g = graphs[0]
        if all(gr is graphs[0] for gr in graphs):
            graphs = None               # degenerate list: one graph
    profiles = None
    if isinstance(profile, (list, tuple)):
        profiles = list(profile)
        profile = None
        if not all(isinstance(q, TransportProfile) for q in profiles):
            raise TypeError("per-scenario profiles must all be "
                            "TransportProfile instances")
    profile, p, failed = _normalize_call(profile, p, failed)
    _check_trace(trace)
    tel = _check_telemetry(telemetry, trace)
    link = _check_link(link)
    budget = int(p.ticks if max_ticks is None else max_ticks)
    B, F = wls.src.shape
    if graphs is not None and len(graphs) != B:
        raise ValueError(f"got {len(graphs)} topologies for B={B} scenarios")
    if seeds is None:
        seeds = np.full((B,), DEFAULT_SEED, np.uint32)
    seeds = jnp.asarray(seeds, jnp.uint32)
    # fault lanes are [B, Q]: with per-scenario topologies of DIFFERING
    # queue counts there is no uniform Q to normalize against, so the
    # failure spec must stay empty (per-group healthy schedules are
    # built below); equal-Q graph lists compose with faults normally.
    mixed_q = (graphs is not None
               and len({gr.num_queues for gr in graphs}) > 1)
    if mixed_q and (failed is not None or faults is not None):
        raise ValueError(
            "failed=/faults= with per-scenario topologies requires all "
            "graphs to share num_queues — run unequal groups separately")
    fault = None
    if not mixed_q:
        fault = as_schedule(g.num_queues, failed, faults, batch=B,
                            g_num_hosts=g.num_hosts)
        if fault is None:
            if failed is None:
                dead = np.zeros((B, g.num_queues), bool)
            else:
                arr = np.asarray(failed)
                if arr.ndim == 2:
                    # any 2-D array is a per-scenario mask (0/1 ints
                    # included — the pre-profile API accepted those)
                    dead = arr.astype(bool)
                else:
                    dead = np.broadcast_to(_failed_to_mask(g, failed),
                                           (B, g.num_queues))
            if dead.shape != (B, g.num_queues):
                raise ValueError(f"failed mask must be [B={B}, "
                                 f"Q={g.num_queues}], got {dead.shape}")
            fault = FaultSchedule.from_mask(jnp.asarray(dead, bool))
    prep.close()

    if profiles is None and graphs is None:
        return _run_batch(g, wls, profile, p, fault, seeds, trace, budget,
                          goodput_window, devices=devices, tel=tel,
                          link=link)

    # per-scenario profiles and/or topologies: group scenarios by the
    # (static) pair and run each group as one vmapped scan — one
    # executable per distinct (graph, profile). Groups are independent
    # device programs, so they run on worker threads: their compiles
    # (the dominant cold cost of an ablation) and executions overlap
    # instead of serializing. Results are reassembled by scenario index
    # — ordering, and every lane's bits, are unaffected.
    if profiles is not None and len(profiles) != B:
        raise ValueError(f"got {len(profiles)} profiles for B={B} scenarios")
    per_g = graphs if graphs is not None else [g] * B
    per_q = profiles if profiles is not None else [profile] * B
    groups: "dict[tuple, tuple]" = {}
    for i, (gr, q) in enumerate(zip(per_g, per_q)):
        key = (id(gr), q)
        if key not in groups:
            groups[key] = (gr, q, [])
        groups[key][2].append(i)
    items = []
    for gr, prof, idxs in groups.values():
        sel = np.asarray(idxs)
        sub_wls = jax.tree_util.tree_map(lambda a, s=sel: a[s], wls)
        if fault is None:
            sub_fault = FaultSchedule.from_mask(
                np.zeros((len(idxs), gr.num_queues), bool))
        else:
            sub_fault = jax.tree_util.tree_map(lambda a, s=sel: a[s], fault)
        items.append((gr, prof, idxs, sub_wls, sub_fault, seeds[sel]))

    def _run_group(item):
        gr, prof, idxs, sub_wls, sub_fault, sub_seeds = item
        return idxs, _run_batch(gr, sub_wls, prof, p, sub_fault, sub_seeds,
                                trace, budget, goodput_window,
                                devices=devices, tel=tel, link=link)

    if len(items) > 1:
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(max_workers=min(len(items), 8)) as ex:
            group_results = list(ex.map(_run_group, items))
    else:
        group_results = [_run_group(items[0])]
    results: "list[SimResult | None]" = [None] * B
    for idxs, rs in group_results:
        for j, i in enumerate(idxs):
            results[i] = rs[j]
    return results
