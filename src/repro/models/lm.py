"""The unified decoder LM: pattern-based blocks, scan-over-layers, remat.

A model is `repeats` copies of a repeating *unit* (cfg.pattern), each unit a
short list of (mixer, ffn) positions — one position for uniform models,
eight for Jamba's 1:7 mamba:attention interleave. Parameters are stored
stacked over repeats ([R, ...] leading dim) and the layer stack runs as a
single `jax.lax.scan` whose body is `jax.checkpoint`-ed — one compiled
layer body regardless of depth, which keeps both compile time and HLO size
flat across the 24..72-layer architecture zoo.

MoE blocks run under `shard_map` so expert routing (top-k, sort,
ragged_dot grouped GEMM) stays *local to each data shard* — a global
argsort over a sharded token axis would otherwise turn into a giant
collective. The FFN dim of every expert is tensor-parallel over "model"
and contributes one psum per MoE block.

Decode carries a per-position cache pytree stacked over repeats, threaded
through the same scan.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import (ArchConfig, FFN_MLP, FFN_MOE, FFN_RWKV,
                                MIXER_ATTN, MIXER_MAMBA, MIXER_RWKV)
from repro.models import layers as L


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg: ArchConfig, key: jax.Array,
                dtype=jnp.bfloat16) -> dict:
    """Parameter pytree. Leaves of block params carry a leading [R] dim."""
    R = cfg.repeats
    keys = jax.random.split(key, 2 + len(cfg.pattern))
    D, V = cfg.d_model, cfg.vocab

    def stack(fn):
        """init fn(key)->tree, stacked over repeats."""
        def stacked(k):
            ks = jax.random.split(k, R)
            return jax.vmap(fn)(ks)
        return stacked

    blocks = {}
    for i, (mixer, ffn) in enumerate(cfg.pattern):
        def pos_init(k, mixer=mixer, ffn=ffn):
            km, kf = jax.random.split(k)
            p = {"norm1": L.init_rms(D, dtype), "norm2": L.init_rms(D, dtype)}
            if mixer == MIXER_ATTN:
                p["mixer"] = L.init_attention(km, D, cfg.n_q, cfg.n_kv,
                                              cfg.head_dim, dtype)
            elif mixer == MIXER_MAMBA:
                p["mixer"] = L.init_mamba(km, D, cfg.ssm_state,
                                          cfg.mamba_expand, dtype)
            elif mixer == MIXER_RWKV:
                p["mixer"] = _init_rwkv_padded(km, cfg, dtype)
            if ffn == FFN_MLP:
                p["ffn"] = L.init_mlp(kf, D, cfg.d_ff, dtype)
            elif ffn == FFN_MOE:
                p["ffn"] = L.init_moe(kf, D, cfg.d_ff, cfg.num_experts, dtype)
            elif ffn == FFN_RWKV:
                p["ffn"] = L.init_rwkv_mlp(kf, D, cfg.d_ff, dtype)
            return p
        blocks[f"pos{i}"] = stack(pos_init)(keys[2 + i])

    return {
        "embed": jax.random.normal(keys[0], (V, D), dtype) * 0.02,
        "head": jax.random.normal(keys[1], (D, V), dtype) * D ** -0.5,
        "final_norm": L.init_rms(D, dtype),
        "blocks": blocks,
    }


def _init_rwkv_padded(key, cfg: ArchConfig, dtype):
    """RWKV with inner dim padded so heads shard over TP=16."""
    D, DI = cfg.d_model, cfg.rwkv_inner
    ks = jax.random.split(key, 6)
    s = D ** -0.5
    return {
        "w_r": jax.random.normal(ks[0], (D, DI), dtype) * s,
        "w_k": jax.random.normal(ks[1], (D, DI), dtype) * s,
        "w_v": jax.random.normal(ks[2], (D, DI), dtype) * s,
        "w_g": jax.random.normal(ks[3], (D, DI), dtype) * s,
        "w_o": jax.random.normal(ks[4], (DI, D), dtype) * (DI ** -0.5),
        "w_decay": jax.random.normal(ks[5], (D, DI), dtype) * s,
        "decay_bias": jnp.full((DI,), -6.0, jnp.float32),
        "bonus": jnp.zeros((cfg.rwkv_heads, cfg.rwkv_head_dim), jnp.float32),
        "mix": jnp.full((5, D), 0.5, jnp.float32),
    }


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, cache_len: int,
               dtype=jnp.bfloat16) -> dict:
    """Decode cache pytree, stacked [R, ...] per pattern position.

    Attention: ring KV cache of `cache_len` (the sliding window for SWA).
    Mamba: [B, d_inner, N] state. RWKV: wkv matrix state + prev-token."""
    R = cfg.repeats
    caches = {}
    for i, (mixer, ffn) in enumerate(cfg.pattern):
        c: dict[str, Any] = {}
        if mixer == MIXER_ATTN:
            clen = min(cache_len, cfg.sliding_window) if cfg.sliding_window \
                else cache_len
            c["attn"] = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (R,) + x.shape),
                L.init_attention_cache(batch, cfg.n_kv, clen, cfg.head_dim,
                                       dtype))
        elif mixer == MIXER_MAMBA:
            di = cfg.mamba_expand * cfg.d_model
            c["mamba"] = jnp.zeros((R, batch, di, cfg.ssm_state), jnp.float32)
        elif mixer == MIXER_RWKV:
            c["rwkv"] = {
                "wkv": jnp.zeros((R, batch, cfg.rwkv_heads, cfg.rwkv_head_dim,
                                  cfg.rwkv_head_dim), jnp.float32),
                "prev": jnp.zeros((R, batch, cfg.d_model), dtype),
            }
        if ffn == FFN_RWKV:
            c["ffn_prev"] = jnp.zeros((R, batch, cfg.d_model), dtype)
        caches[f"pos{i}"] = c
    return caches


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _moe_block(cfg: ArchConfig, mesh, dp_axes, token_spec,
               capacity_factor: float = 1.25, sequential: bool = True):
    """Build the (optionally shard_map'd) MoE application fn.

    Dropless-ish capacity dispatch: tokens are sorted by expert and each
    expert processes a fixed-capacity contiguous slice (capacity =
    cf * T * k / E; overflow tokens are dropped, Switch-style). The expert
    GEMMs are batched einsums over [E, cap, D] — XLA counts their FLOPs
    exactly and, unlike `jax.lax.ragged_dot`, their VJP does not
    materialize dense [E, T, D] intermediates (the reason ragged_dot was
    abandoned here — see DESIGN.md §MoE).
    """
    top_k = cfg.experts_per_token

    def local_moe(xt, router, w_gate, w_up, w_down):
        T, D = xt.shape
        E = router.shape[1]
        # token chunking bounds the gather/scatter adjoint transients
        n_chunks = 1
        while T // n_chunks > 16384:
            n_chunks *= 2
        Tc = T // n_chunks
        cap = max(8, int(Tc * top_k * capacity_factor) // E)

        # NOTE: no preferred_element_type=f32 here — its VJP would emit an
        # f32 [T, D] d_xt and promote the whole token cotangent chain.
        logits = (xt @ router.astype(xt.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_all, experts_all = jax.lax.top_k(probs, top_k)
        gate_all = gate_all / jnp.sum(gate_all, axis=-1, keepdims=True)

        # per-expert FFN, checkpointed; Python-unrolled over experts (a
        # lax.scan body would be FLOP-counted once by XLA cost analysis)
        @partial(jax.checkpoint,
                 policy=jax.checkpoint_policies.nothing_saveable)
        def ffn(wg, wu, wd, xin):
            act = (jax.nn.silu(xin @ wg) * (xin @ wu)).astype(xin.dtype)
            return act @ wd

        @partial(jax.checkpoint,
                 policy=jax.checkpoint_policies.nothing_saveable)
        def chunk_moe(xc, gates, experts):
            """One token chunk: sort locally, one gather, E FFNs, one
            scatter. xc: [Tc, D]."""
            flat_expert = experts.reshape(-1)                  # [Tc*K]
            flat_token = jnp.repeat(jnp.arange(Tc), top_k)
            order = jnp.argsort(flat_expert)
            sorted_token = flat_token[order]
            group_sizes = jnp.bincount(flat_expert, length=E).astype(
                jnp.int32)
            starts = jnp.cumsum(group_sizes) - group_sizes
            gates_flat = gates.reshape(-1)[order].astype(xc.dtype)

            pos = starts[:, None] + jnp.arange(cap)[None]      # [E, cap]
            valid = jnp.arange(cap)[None] < group_sizes[:, None]
            pos_c = jnp.clip(pos, 0, Tc * top_k - 1).reshape(-1)
            tok_all = sorted_token[pos_c]                      # [E*cap]
            vmask = valid.reshape(-1)
            xin_all = xc[tok_all] * vmask[:, None].astype(xc.dtype)
            g_all = gates_flat[pos_c] * vmask.astype(xc.dtype)

            yos = [ffn(w_gate[e], w_up[e], w_down[e],
                       xin_all[e * cap:(e + 1) * cap]) for e in range(E)]
            yo_all = jnp.concatenate(yos, 0) * g_all[:, None]
            drop_tok = jnp.where(vmask, tok_all, Tc)           # OOB => drop
            return jnp.zeros((Tc, D), xc.dtype).at[drop_tok].add(
                yo_all, mode="drop")

        if sequential and n_chunks > 1:
            # lax.scan serializes chunk processing (bounds live memory);
            # used by the full/memory build. The FLOP-calibration variants
            # use the Python loop below so XLA counts every chunk.
            _, ys = jax.lax.scan(
                lambda c, xs: (c, chunk_moe(*xs)), 0,
                (xt.reshape(n_chunks, Tc, D),
                 gate_all.reshape(n_chunks, Tc, top_k),
                 experts_all.reshape(n_chunks, Tc, top_k)))
            out = ys.reshape(T, D)
        else:
            outs = [chunk_moe(xt[i * Tc:(i + 1) * Tc],
                              gate_all[i * Tc:(i + 1) * Tc],
                              experts_all[i * Tc:(i + 1) * Tc])
                    for i in range(n_chunks)]
            out = jnp.concatenate(outs, 0)
        if mesh is not None:
            out = jax.lax.psum(out, "model")
        me = probs.mean(axis=0)
        ce = jnp.bincount(experts_all.reshape(-1), length=E).astype(
            jnp.float32) / (T * top_k)
        aux = E * jnp.sum(me * ce)
        if mesh is not None:
            aux = jax.lax.pmean(aux, dp_axes)
        return out, aux

    if mesh is None:
        return local_moe

    wspec_in = P(None, None, "model")    # [E, D, F/tp]
    wspec_out = P(None, "model", None)   # [E, F/tp, D]
    return jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(token_spec, P(None, None), wspec_in, wspec_in, wspec_out),
        out_specs=(token_spec, P()),
        check_vma=False,
    )


def build_forward(cfg: ArchConfig, mesh=None, dp_axes=("data",),
                  decode: bool = False, remat: bool = True,
                  moe_token_spec=None, select_write: bool = False,
                  act_spec=None, output: str = "logits",
                  scan_layers: bool = True, attn_head_specs=None,
                  sharded_cache_attn: bool = False,
                  remat_policy: str = "nothing"):
    """Return fwd(params, tokens_or_embeds, cache=None, pos0=0).

    Training/prefill: full-sequence forward, returns (logits, aux, cache').
    Decode: single-token step against the cache.
    """
    if moe_token_spec is None:
        moe_token_spec = P(dp_axes, None) if mesh is not None else None
    moe_fn = _moe_block(cfg, mesh, dp_axes, moe_token_spec,
                        sequential=scan_layers)
    cache_attn = (L.sharded_cache_attention(mesh, dp_axes)
                  if sharded_cache_attn and mesh is not None else None)
    has_moe = any(f == FFN_MOE for _, f in cfg.pattern)

    def unit_fn(x, positions, unit_params, unit_cache):
        """Apply one repeating unit. x: [B, S, D]."""
        aux_total = jnp.zeros((), jnp.float32)
        new_cache = {} if unit_cache is not None else None
        for i, (mixer, ffn) in enumerate(cfg.pattern):
            p = unit_params[f"pos{i}"]
            c = unit_cache[f"pos{i}"] if unit_cache is not None else None
            nc: dict[str, Any] = {}
            h = L.rms_norm(x, p["norm1"]["scale"])
            if mixer == MIXER_ATTN:
                out, ac = L.attention_fwd(
                    p["mixer"], h, positions, n_q=cfg.n_q, n_kv=cfg.n_kv,
                    head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                    window=cfg.sliding_window,
                    cache=c["attn"] if c is not None else None,
                    select_write=select_write,
                    head_shardings=attn_head_specs,
                    cache_attn=cache_attn)
                if ac is not None:
                    nc["attn"] = ac
                from jax.ad_checkpoint import checkpoint_name
                out = checkpoint_name(out, "mixer_out")
            elif mixer == MIXER_MAMBA:
                out, st = L.mamba_fwd(p["mixer"], h,
                                      state=c["mamba"] if c is not None
                                      else None)
                if c is not None:
                    nc["mamba"] = st
            else:  # rwkv
                out, st = L.rwkv_fwd(p["mixer"], h,
                                     state=c["rwkv"] if c is not None
                                     else None, n_heads=cfg.rwkv_heads)
                if c is not None:
                    nc["rwkv"] = st
            x = x + out

            h = L.rms_norm(x, p["norm2"]["scale"])
            if ffn == FFN_MLP:
                out = L.mlp_fwd(p["ffn"], h)
            elif ffn == FFN_MOE:
                B, S, D = h.shape
                ht = h.reshape(B * S, D)
                out, aux = moe_fn(ht, p["ffn"]["router"], p["ffn"]["w_gate"],
                                  p["ffn"]["w_up"], p["ffn"]["w_down"])
                out = out.reshape(B, S, D)
                aux_total = aux_total + aux
            else:  # rwkv channel mix
                out, prev = L.rwkv_mlp_fwd(
                    p["ffn"], h,
                    prev=c["ffn_prev"] if c is not None else None)
                if c is not None:
                    nc["ffn_prev"] = prev
            x = x + out
            if new_cache is not None:
                new_cache[f"pos{i}"] = nc
        return x, aux_total, new_cache

    def fwd(params, inputs, cache=None, pos0=0):
        if cfg.frontend == "vit_stub" and inputs.ndim == 3:
            x = inputs.astype(params["embed"].dtype)  # precomputed embeds
        else:
            x = params["embed"][inputs]               # [B, S, D]
        B, S = x.shape[0], x.shape[1]
        positions = pos0 + jnp.arange(S, dtype=jnp.int32)

        body = unit_fn
        if remat and cache is None:
            policy = (jax.checkpoint_policies.save_only_these_names(
                "mixer_out") if remat_policy == "save_mixer"
                else jax.checkpoint_policies.nothing_saveable)
            body = jax.checkpoint(unit_fn, policy=policy, static_argnums=())

        def constrain(x):
            if act_spec is not None:
                return jax.lax.with_sharding_constraint(x, act_spec)
            return x

        x = constrain(x)
        if not scan_layers:
            # Python-unrolled layer stack: used by the dry-run's R=1/R=2
            # FLOP-calibration lowers (XLA cost analysis counts a while-loop
            # body once; unrolling makes per-unit costs measurable).
            aux = jnp.zeros((), jnp.float32)
            R = jax.tree_util.tree_leaves(params["blocks"])[0].shape[0]
            new_caches = []
            for r in range(R):
                up = jax.tree_util.tree_map(lambda a: a[r], params["blocks"])
                uc = (jax.tree_util.tree_map(lambda a: a[r], cache)
                      if cache is not None else None)
                x, a, nc = body(x, positions, up, uc)
                x = constrain(x)
                aux = aux + a
                if cache is not None:
                    new_caches.append(nc)
            new_cache = (jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *new_caches)
                if cache is not None else None)
        elif cache is None:
            def scan_body(carry, unit_params):
                x, aux = carry
                x, a, _ = body(x, positions, unit_params, None)
                return (constrain(x), aux + a), None
            (x, aux), _ = jax.lax.scan(
                scan_body, (x, jnp.zeros((), jnp.float32)), params["blocks"])
            new_cache = None
        else:
            def scan_body(carry, xs):
                x, aux = carry
                unit_params, unit_cache = xs
                x, a, nc = body(x, positions, unit_params, unit_cache)
                return (x, aux + a), nc
            (x, aux), new_cache = jax.lax.scan(
                scan_body, (x, jnp.zeros((), jnp.float32)),
                (params["blocks"], cache))

        x = L.rms_norm(x, params["final_norm"]["scale"])
        if output == "hidden":
            return x, aux, new_cache
        logits = x @ params["head"]
        return logits, aux, new_cache

    return fwd
