"""Plain reference of the fabric simulator, for the benchmark's check.

An independent, straightforward implementation of the semantics that the
benchmark's configurations run: a 3-tier k-ary fat tree, one FIFO per
directed link, one MTU per link per tick, oblivious per-packet spraying
(RUD), NSCC window control with Quick Adapt, trimming on overflow with
NACKs on the control class, selective retransmit, a fixed RTO, link
flaps and gray links. It imports nothing of the program under test and
takes nothing it made: the topology, routes, hashes and the tick are
written here from the model's definition, one scenario at a time (vmap
only batches the sampled lanes), with the receive and retransmit
bitmaps as plain bool planes and queue arrival order by a sort.

``run_reference(...)`` runs a few lanes to quiescence, checking it at
chunk boundaries as the program's driver does, and returns each lane's
observable outcome (horizon, completion ticks, counters, window and
tracker state). ``fdtype`` is the float type of the NSCC window state
and arithmetic: float32 is the configuration's, bfloat16 the control.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

U32 = jnp.uint32
I32 = jnp.int32
EV_SPACE = 1 << 16
NONE, ACK, NACK = 0, 1, 2
ECN_BIT, TRIM_BIT = 2, 1
DELIVERED = -2
BIG = 2 ** 30
# queue kinds
UP1, UP2, DOWN2, DOWN1, HOSTQ = 0, 1, 2, 3, 4


def mix32(x):
    """xxhash-style avalanche finalizer on uint32."""
    x = x.astype(U32)
    x = x ^ (x >> 16)
    x = x * U32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * U32(0x846CA68B)
    return x ^ (x >> 16)


def ecmp_hash(src, dst, ev, salt):
    """The switches' port hash over (src, dst, EV, switch id)."""
    x = (src.astype(U32) * U32(0x9E3779B1) ^ dst.astype(U32) * U32(0x85EBCA77)
         ^ ev.astype(U32) * U32(0xC2B2AE3D) ^ salt.astype(U32) * U32(0x27D4EB2F))
    x = x ^ (x >> 15)
    x = x * U32(0x2C1B3C6D)
    x = x ^ (x >> 12)
    x = x * U32(0x297A2D39)
    return x ^ (x >> 15)


@dataclass(frozen=True)
class FatTree:
    """k-ary 3-tier fat tree with `pods` pods, queues numbered by tier:
    leaf->agg uplinks, agg->core uplinks, core->agg downlinks,
    agg->leaf downlinks, then one downlink per host. Switch ids: leaves,
    then aggregation switches, then cores."""

    k: int
    pods: int

    @property
    def half(self):
        return self.k // 2

    @property
    def leaves(self):
        return self.pods * self.half

    @property
    def aggs(self):
        return self.pods * self.half

    @property
    def cores(self):
        return self.half * self.half

    @property
    def hosts(self):
        return self.leaves * self.half

    # first queue id of each tier
    @property
    def base_up2(self):
        return self.leaves * self.half

    @property
    def base_down2(self):
        return self.base_up2 + self.aggs * self.half

    @property
    def base_down1(self):
        return self.base_down2 + self.cores * self.pods

    @property
    def base_host(self):
        return self.base_down1 + self.aggs * self.half

    @property
    def num_queues(self):
        return self.base_host + self.hosts

    def uplink(self, leaf: int, j: int) -> int:
        """Queue id of leaf `leaf`'s j-th uplink (to its pod's agg j)."""
        return leaf * self.half + j

    def queue_tables(self):
        """Per queue: its kind, and the aggregation switch (global index)
        or core it feeds, -1 where none."""
        h, Q = self.half, self.num_queues
        kind = np.full(Q, HOSTQ, np.int32)
        agg = np.full(Q, -1, np.int32)
        core = np.full(Q, -1, np.int32)
        for leaf in range(self.leaves):
            for j in range(h):
                q = self.uplink(leaf, j)
                kind[q], agg[q] = UP1, (leaf // h) * h + j
        for a in range(self.aggs):
            for c in range(h):
                q = self.base_up2 + a * h + c
                kind[q], core[q] = UP2, (a % h) * h + c
        for c in range(self.cores):
            for p in range(self.pods):
                q = self.base_down2 + c * self.pods + p
                kind[q], agg[q] = DOWN2, p * h + c // h
        kind[self.base_down1:self.base_host] = DOWN1
        return kind, agg, core


@dataclass(frozen=True)
class Model:
    """The numbers the reference needs from a configuration file."""

    tree: FatTree
    capacity: int
    ecn_threshold: int
    ack_return: int
    mp: int
    rto: int
    chunk: int
    max_cwnd: float
    base_rtt: float
    target_factor: float
    md: float
    quick_gain: float
    ai: float
    min_cwnd: float
    qa_min_frac: float

    @staticmethod
    def from_config(cfg: dict) -> "Model":
        topo, p, n = cfg["topology"], cfg["params"], cfg["nscc"]
        if topo["family"] != "fat_tree3":
            raise ValueError(f"the reference models fat_tree3, not "
                             f"{topo['family']!r}")
        prof = cfg["transport"]
        want = {"cc": "nscc", "lb": "oblivious", "delivery": "rud",
                "inc": False}
        if {k: prof[k] for k in want} != want:
            raise ValueError(f"the reference models {want}, got {prof}")
        if not p["trimming"] or p["ooo_threshold"] != 0:
            raise ValueError("the reference models trimming on, OOO "
                             "inference off")
        return Model(FatTree(topo["k"], topo["pods"]), p["queue_capacity"],
                     p["ecn_threshold"], p["ack_return_ticks"],
                     p["mp_range"], p["timeout_ticks"], p["chunk_ticks"],
                     p["max_cwnd"], p["base_rtt"], n["target_factor"],
                     n["md"], n["quick_gain"], n["ai"], n["min_cwnd"],
                     n["qa_min_frac"])


def _leading_ones(plane):
    """Count of consecutive True from column 0, per row."""
    return jnp.where(plane.all(1), plane.shape[1],
                     jnp.argmin(plane, axis=1)).astype(I32)


def _drop_front(plane, n):
    """Row r loses its first n[r] columns; False shifts in at the end."""
    m = plane.shape[1]
    idx = jnp.arange(m)[None, :] + n[:, None]
    return jnp.take_along_axis(plane, jnp.clip(idx, 0, m - 1), 1) & (idx < m)


def _one_bit(off, valid, m):
    return valid[:, None] & (jnp.arange(m)[None, :] == off[:, None])


def _arrival_rank(target, valid, num_targets):
    """Rank of each valid lane among the valid lanes before it that go to
    the same target (FIFO arrival order = lane order)."""
    n = target.shape[0]
    key = jnp.where(valid, target, num_targets) * n + jnp.arange(n)
    order = jnp.argsort(key)
    pos = jnp.zeros(n, I32).at[order].set(jnp.arange(n, dtype=I32))
    first = jnp.searchsorted(key[order], jnp.where(valid, target, 0) * n)
    return pos - first.astype(I32)


def build(m: Model, F: int, fdtype=jnp.float32):
    """(init, tick, quiescent) of one scenario."""
    T = m.tree
    Q, H, C, h = T.num_queues, T.hosts, m.capacity, T.half
    D, mp = m.ack_return + 1, m.mp
    n = Q + F                           # enqueue candidates: hops, then NICs
    E = Q + n                           # control lanes: ACKs, then NACKs
    kind_np, agg_np, core_np = T.queue_tables()
    kind, far_agg, far_core = (jnp.asarray(a) for a in
                               (kind_np, agg_np, core_np))
    flows = jnp.arange(F, dtype=I32)
    fd = fdtype
    target = m.base_rtt * m.target_factor

    def leaf_of(host):
        return host // h

    def pod_of(host):
        return host // (h * h)

    def down1(agg, host):
        return T.base_down1 + agg * h + leaf_of(host) % h

    def injection_queue(src, dst, ev):
        sl = leaf_of(src)
        j = (ecmp_hash(src, dst, ev, sl) % U32(h)).astype(I32)
        return jnp.where(sl == leaf_of(dst), T.base_host + dst,
                         T.uplink(sl, j))

    def next_queue(q, src, dst, ev):
        k = kind[q]
        a = far_agg[q]
        j = (ecmp_hash(src, dst, ev, T.leaves + a) % U32(h)).astype(I32)
        at_agg_up = jnp.where(a // h == pod_of(dst), down1(a, dst),
                              T.base_up2 + a * h + j)
        at_core = T.base_down2 + far_core[q] * T.pods + pod_of(dst)
        return jnp.select(
            [k == UP1, k == UP2, k == DOWN2, k == DOWN1],
            [at_agg_up, at_core, down1(a, dst), T.base_host + dst],
            DELIVERED)

    def init(seed):
        seed = jnp.asarray(seed).astype(U32)
        zf = jnp.zeros(F, I32)
        zu = jnp.zeros(F, U32)
        plane = jnp.zeros((F, mp), bool)
        return {
            "q": jnp.zeros((5, Q, C), I32),     # flow, psn, ev, meta, tsent
            "q_head": jnp.zeros(Q, I32), "q_len": jnp.zeros(Q, I32),
            "ctl": jnp.zeros((5, D, E), I32),   # type, flow, psn, ecn, tsent
            "next_psn": zf, "inflight": zf, "last_progress": zf,
            "src_base": zu, "src_rx": plane, "rtx": plane,
            "src_ok": zu, "src_dup": zu, "src_oor": zu,
            "dst_base": zu, "dst_rx": plane,
            "dst_ok": zu, "dst_dup": zu, "dst_oor": zu,
            "cwnd": jnp.full(F, m.max_cwnd, fd),
            "ep_acked": zf, "ep_lost": zf, "ep_tick": zf,
            "salt": mix32(jnp.arange(F, dtype=U32) + seed * U32(2654435761)),
            "delivered": zf,
            "trims": I32(0), "drops": I32(0), "dups": I32(0),
            "retransmits": I32(0), "timeouts": I32(0), "degraded": I32(0),
            "clash": I32(0),   # ticks that broke one-event-per-flow
        }

    def per_flow(lane_flow, active, *vals):
        idx = jnp.where(active, lane_flow, F)
        count = jnp.zeros(F, I32).at[idx].add(1, mode="drop")
        return count, [jnp.zeros(F, v.dtype).at[idx].set(v, mode="drop")
                       for v in vals]

    def window_delta(cwnd, ecn, rtt):
        high = rtt > target
        overload = jnp.clip((rtt - target) / jnp.maximum(rtt, 1e-6), 0.0, 1.0)
        dec = -m.md * overload
        gap = jnp.clip((target - rtt) / target, 0.0, 1.0)
        quick = m.quick_gain * gap
        gentle = m.ai / jnp.maximum(cwnd, 1.0)
        return jnp.where(ecn, jnp.where(high, dec, 0.0),
                         jnp.where(high, gentle, quick))

    def tick(s, t, wl, fs):
        src, dst, size, dep = wl["src"], wl["dst"], wl["size"], wl["dep"]
        s = dict(s)
        slot = t % D

        # -- control events that arrive this tick
        typ, lf, lp, lecn, lts = (s["ctl"][i, slot] for i in range(5))
        n_ack, (ack_psn, ack_ecn, ack_ts) = per_flow(
            lf, typ == ACK, lp, lecn, lts)
        has_ack = n_ack > 0
        nack_count, _ = per_flow(lf, typ == NACK)
        clash = (n_ack > 1).any()
        s["ctl"] = s["ctl"].at[0, slot].set(NONE)

        # source: record the ACKed PSN, advance the cumulative ACK
        off = (ack_psn.astype(U32) - s["src_base"]).astype(I32)
        inr = has_ack & (off >= 0) & (off < mp)
        hit = _one_bit(off, inr, mp)
        already = (s["src_rx"] & hit).any(1)
        rx = s["src_rx"] | hit
        adv = _leading_ones(rx)
        s["src_rx"] = _drop_front(rx, adv)
        rtx = _drop_front(s["rtx"], adv)
        base = s["src_base"] + adv.astype(U32)
        s["src_base"] = base
        s["src_ok"] += (inr & ~already).astype(U32)
        s["src_dup"] += already.astype(U32)
        s["src_oor"] += (has_ack & ~inr).astype(U32)
        inflight = jnp.maximum(s["inflight"] - has_ack - nack_count, 0)

        # NSCC: per-ACK window change, loss evidence for Quick Adapt
        rtt = (t - ack_ts).astype(fd)
        cwnd = s["cwnd"]
        cwnd = jnp.where(has_ack, cwnd + window_delta(cwnd, ack_ecn != 0, rtt),
                         cwnd)
        cwnd = jnp.clip(cwnd, m.min_cwnd, m.max_cwnd)
        ep_acked = s["ep_acked"] + has_ack
        ep_lost = s["ep_lost"] + nack_count
        last_progress = jnp.where(has_ack, t, s["last_progress"])

        # an ACKed PSN no longer needs a retransmit; a NACKed one does
        base_i = base.astype(I32)
        aoff = ack_psn - base_i
        rtx = rtx & ~_one_bit(aoff, has_ack & (aoff >= 0) & (aoff < mp), mp)
        is_nack = typ == NACK
        noff = lp - base_i[jnp.where(is_nack, lf, 0)]
        nok = is_nack & (noff >= 0) & (noff < mp)
        rtx = rtx.at[jnp.where(nok, lf, F), jnp.clip(noff, 0, mp - 1)].set(
            True, mode="drop")

        # -- injection: one flow per host NIC per tick
        done = base_i >= size
        dep_ok = (dep < 0) | done[jnp.maximum(dep, 0)]
        has_rtx = rtx.any(1)
        overdue = t - last_progress > m.rto
        win_ok = inflight < jnp.floor(cwnd).astype(I32)
        can_new = (s["next_psn"] < size) & (s["next_psn"] - base_i < mp)
        eligible = (t >= wl["start"]) & ~done & dep_ok & win_ok \
            & (has_rtx | can_new)
        rot = (mix32(jnp.arange(F, dtype=U32) * U32(2654435761)
                     ^ t.astype(U32)) >> 16).astype(I32)
        key = jnp.where(eligible, rot * F + flows, BIG)
        best = jnp.full(H, BIG, I32).at[src].min(key)
        injected = eligible & (key == best[src])
        rtx_off = jnp.where(has_rtx, jnp.argmax(rtx, axis=1), -1).astype(I32)
        use_rtx = injected & (rtx_off >= 0)
        psn_out = jnp.where(use_rtx, base_i + rtx_off, s["next_psn"])
        ev = (mix32(s["salt"] ^ mix32(psn_out.astype(U32)
                                      + (t.astype(U32) << 8)))
              % U32(EV_SPACE)).astype(I32)
        inj_q = injection_queue(src, dst, ev)
        rtx = rtx & ~_one_bit(rtx_off, use_rtx, mp)
        next_psn = s["next_psn"] + (injected & ~use_rtx)
        inflight = inflight + injected
        s["retransmits"] += use_rtx.sum(dtype=I32)

        # -- every non-empty queue sends its head packet one hop
        qi = jnp.arange(Q)
        head = s["q"][:, qi, s["q_head"]]                   # [5, Q]
        pf, pp, pe, pm, pt = head
        busy = s["q_len"] > 0
        pm = jnp.where(busy & (s["q_len"] > m.ecn_threshold), pm | ECN_BIT, pm)
        q_head = jnp.where(busy, (s["q_head"] + 1) % C, s["q_head"])
        q_len = s["q_len"] - busy
        spf = jnp.where(busy, pf, 0)
        nq = next_queue(qi, src[spf], dst[spf], pe)
        forward = busy & (nq >= 0)
        data_in = busy & (nq == DELIVERED) & ((pm & TRIM_BIT) == 0)

        # destination: record the PSN, advance its cumulative ACK
        n_rx, (d_psn,) = per_flow(pf, data_in, pp)
        has_d = n_rx > 0
        clash = clash | (n_rx > 1).any()
        doff = (d_psn.astype(U32) - s["dst_base"]).astype(I32)
        dinr = has_d & (doff >= 0) & (doff < mp)
        dhit = _one_bit(doff, dinr, mp)
        dalready = (s["dst_rx"] & dhit).any(1)
        fresh = dinr & ~dalready
        drx = s["dst_rx"] | dhit
        dadv = _leading_ones(drx)
        s["dst_rx"] = _drop_front(drx, dadv)
        s["dst_base"] = s["dst_base"] + dadv.astype(U32)
        s["dst_ok"] += fresh.astype(U32)
        s["dst_dup"] += dalready.astype(U32)
        s["dst_oor"] += (has_d & ~dinr).astype(U32)
        s["dups"] += (has_d & ~fresh).sum(dtype=I32)
        s["delivered"] = s["delivered"] + fresh

        # -- enqueue: forwarded packets in queue order, then injections
        cq = jnp.concatenate([jnp.where(forward, nq, -1),
                              jnp.where(injected, inj_q, -1)])
        cand = jnp.stack([jnp.concatenate([pf, flows]),
                          jnp.concatenate([pp, psn_out]),
                          jnp.concatenate([pe, ev]),
                          jnp.concatenate([pm, jnp.zeros(F, I32)]),
                          jnp.concatenate([pt, jnp.full(F, t, I32)])])
        valid = cq >= 0
        scq = jnp.where(valid, cq, 0)
        dead = (fs["fail_at"] <= t) & (t < fs["heal_at"])
        gone = valid & dead[scq]
        valid = valid & ~gone
        u = mix32(mix32(t.astype(U32) ^ fs["seed"] * U32(0x9E3779B1))
                  ^ jnp.arange(n, dtype=U32) * U32(0x85EBCA77))
        lost = valid & (u < fs["loss_thr"][scq])
        valid = valid & ~lost
        pos = q_len[scq] + _arrival_rank(scq, valid, Q)
        fits = valid & (pos < C)
        over = valid & ~fits
        tq = jnp.where(fits, scq, Q)
        slot_w = (q_head[scq] + pos) % C
        s["q"] = s["q"].at[:, tq, slot_w].set(cand, mode="drop")
        s["q_head"] = q_head
        s["q_len"] = q_len + jnp.zeros(Q, I32).at[tq].add(1, mode="drop")
        s["trims"] += over.sum(dtype=I32)
        s["drops"] += gone.sum(dtype=I32) + lost.sum(dtype=I32)

        # control class: ACKs of data delivered now, NACKs of trimmed
        # packets, both arriving `ack_return` ticks later
        out = (t + m.ack_return) % D
        ctl_new = jnp.stack([
            jnp.concatenate([jnp.where(data_in, ACK, NONE),
                             jnp.where(over, NACK, NONE)]),
            jnp.concatenate([pf, cand[0]]),
            jnp.concatenate([pp, cand[1]]),
            jnp.concatenate([(pm & ECN_BIT) != 0, jnp.zeros(n, bool)]
                            ).astype(I32),
            jnp.concatenate([pt, cand[4]])])
        s["ctl"] = s["ctl"].at[:, out].set(ctl_new)

        # -- retransmission timeout, then Quick Adapt
        stalled = ((inflight > 0) | (base_i < next_psn)) & overdue & ~done
        rtx = rtx.at[:, 0].set(rtx[:, 0] | stalled)
        s["inflight"] = jnp.where(stalled, 0, inflight)
        s["last_progress"] = jnp.where(stalled, t, last_progress)
        ep_lost = ep_lost + stalled
        s["timeouts"] += stalled.sum(dtype=I32)
        s["degraded"] += dead.any().astype(I32)
        due = t - s["ep_tick"] >= int(target)
        got = ep_acked.astype(fd)
        frac = got / jnp.maximum(got + ep_lost.astype(fd), 1.0)
        cwnd = jnp.where(due & (ep_lost > 0),
                         jnp.clip(cwnd * frac, m.qa_min_frac * m.max_cwnd,
                                  m.max_cwnd), cwnd)
        s["cwnd"] = jnp.maximum(cwnd, m.min_cwnd)
        s["ep_acked"] = jnp.where(due, 0, ep_acked)
        s["ep_lost"] = jnp.where(due, 0, ep_lost)
        s["ep_tick"] = jnp.where(due, t, s["ep_tick"])
        s["rtx"] = rtx
        s["next_psn"] = next_psn
        s["clash"] += clash.astype(I32)
        return s, fresh

    def quiescent(s, wl):
        return ((s["src_base"].astype(I32) >= wl["size"]).all()
                & (s["inflight"] == 0).all() & (s["q_len"] == 0).all()
                & (s["ctl"][0] == NONE).all())

    return init, tick, quiescent


def run_reference(m: Model, lanes: "list[dict]", budget: int,
                  fdtype=jnp.float32) -> "list[dict]":
    """Run each lane (its flow table, fault lanes and seed) from tick 0
    until it is quiescent at a chunk boundary or reaches `budget`.

    A lane is a dict: src, dst, size, dep ([F] int host ids / flow
    index), fail_at, heal_at ([Q] int), loss_p ([Q] float), seed (int).
    Returns one dict of numpy outcomes per lane.
    """
    F = len(lanes[0]["src"])
    init, tick, quiescent = build(m, F, fdtype)
    wl = {k: jnp.asarray(np.stack([ln[k] for ln in lanes]), I32)
          for k in ("src", "dst", "size", "dep")}
    wl["start"] = jnp.zeros_like(wl["src"])
    loss_p = jnp.asarray(np.stack([ln["loss_p"] for ln in lanes]),
                         jnp.float32)
    fs = {"fail_at": jnp.asarray(np.stack([ln["fail_at"] for ln in lanes]),
                                 I32),
          "heal_at": jnp.asarray(np.stack([ln["heal_at"] for ln in lanes]),
                                 I32),
          # a packet is lost iff its uniform u32 draw lies below this
          "loss_thr": (jnp.clip(loss_p, 0.0, 1.0)
                       * jnp.float32(4294967040.0)).astype(U32),
          "seed": jnp.asarray([ln["seed"] for ln in lanes], U32)}
    state = jax.vmap(init)(fs["seed"])
    stats = {"comp": jnp.full((len(lanes), F), -1, I32),
             "src_comp": jnp.full((len(lanes), F), -1, I32),
             "win": jnp.zeros((len(lanes), F), I32),
             "qpeak": jnp.zeros(len(lanes), I32)}

    def lane_chunk(s, st, t0, live, wl, fs):
        def body(c, i):
            s, st = c
            t = t0 + i
            ns, fresh = tick(s, t, wl, fs)
            nst = {
                "comp": jnp.where((st["comp"] < 0)
                                  & (ns["delivered"] >= wl["size"]), t,
                                  st["comp"]),
                "src_comp": jnp.where(
                    (st["src_comp"] < 0)
                    & (ns["src_base"].astype(I32) >= wl["size"]), t,
                    st["src_comp"]),
                "win": st["win"] + jnp.where(t < budget, fresh, 0),
                "qpeak": jnp.maximum(st["qpeak"], ns["q_len"].max()),
            }
            keep = live & (t < budget)
            return jax.tree_util.tree_map(
                lambda a, b: jnp.where(keep, a, b), (ns, nst), (s, st)), None

        (s, st), _ = jax.lax.scan(body, (s, st),
                                  jnp.arange(m.chunk, dtype=I32))
        return s, st, quiescent(s, wl)

    chunk = jax.jit(jax.vmap(lane_chunk, in_axes=(0, 0, None, 0, 0, 0)))
    stopped = np.zeros(len(lanes), bool)
    horizon = np.full(len(lanes), -1, np.int64)
    t0 = 0
    while not stopped.all():
        state, stats, quiet = chunk(state, stats, I32(t0),
                                    jnp.asarray(~stopped), wl, fs)
        t0 += m.chunk
        newly = ~stopped & (np.asarray(quiet) | (t0 >= budget))
        horizon[newly] = min(t0, budget)
        stopped |= newly
    state, stats = jax.device_get((state, stats))
    out = []
    for i in range(len(lanes)):
        s = {k: np.asarray(v[i]) for k, v in state.items()}
        out.append({
            "horizon": int(horizon[i]),
            "completion": stats["comp"][i], "src_completion":
                stats["src_comp"][i], "win_delivered": stats["win"][i],
            "qlen_peak": int(stats["qpeak"][i]),
            "delivered": s["delivered"], "next_psn": s["next_psn"],
            "inflight": s["inflight"], "last_progress": s["last_progress"],
            "src_base": s["src_base"], "src_ring": pack(s["src_rx"]),
            "src_rx_ok": s["src_ok"], "src_dup": s["src_dup"],
            "src_oor": s["src_oor"], "rtx": pack(s["rtx"]),
            "dst_base": s["dst_base"], "dst_ring": pack(s["dst_rx"]),
            "dst_rx_ok": s["dst_ok"], "dst_dup": s["dst_dup"],
            "dst_oor": s["dst_oor"],
            "cwnd": np.asarray(s["cwnd"], np.float32),
            "epoch_acked": s["ep_acked"], "epoch_lost": s["ep_lost"],
            "epoch_tick": s["ep_tick"],
            "trims": int(s["trims"]), "drops": int(s["drops"]),
            "dups": int(s["dups"]), "retransmits": int(s["retransmits"]),
            "timeouts": int(s["timeouts"]),
            "ticks_degraded": int(s["degraded"]),
            "q_len": s["q_len"], "clash_ticks": int(s["clash"]),
        })
    return out


def pack(plane: np.ndarray) -> np.ndarray:
    """[F, W*32] bool -> [F, W] uint32, bit j of word k = column 32k+j."""
    words = np.asarray(plane).reshape(plane.shape[0], -1, 32)
    return (words.astype(np.uint64) << np.arange(32, dtype=np.uint64)
            ).sum(-1).astype(np.uint32)
