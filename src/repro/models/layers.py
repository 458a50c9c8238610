"""Model building blocks: GQA attention (full / sliding-window), dense and
MoE FFNs, Mamba and RWKV6 mixers.

Everything is a pure function over explicit parameter dicts (no module
framework): `init_*` returns a param pytree, `*_fwd` consumes it. All
matmul dims are chosen/padded so they shard cleanly over the production
mesh's "model" axis (see repro/distributed/sharding.py).

Attention uses a blockwise online-softmax (flash-style) scan so that
[B, H, S, S] score tensors never materialize — mandatory for the 32k
prefill shapes. MoE uses top-k gating + sort + `jax.lax.ragged_dot`
(dropless grouped GEMM), the XLA-native shape of an expert dispatch.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------------------
# basics
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale


def init_rms(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rope(x, positions, theta: float):
    """Rotary embedding. x: [..., S, H, hd]; positions: [..., S].

    cos/sin are cast to x.dtype BEFORE the multiply: an f32 factor here
    would promote the whole backward cotangent chain (d_q, d_x, ...) to
    f32 and double activation memory across every layer.
    """
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (jnp.log(theta) / half))
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(ang)[..., :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[..., :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# attention (GQA, RoPE, optional sliding window), flash-style blockwise
# ---------------------------------------------------------------------------


def init_attention(key, d_model, n_q, n_kv, head_dim, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d_model ** -0.5
    return {
        "wq": jax.random.normal(k1, (d_model, n_q * head_dim), dtype) * s,
        "wk": jax.random.normal(k2, (d_model, n_kv * head_dim), dtype) * s,
        "wv": jax.random.normal(k3, (d_model, n_kv * head_dim), dtype) * s,
        "wo": jax.random.normal(k4, (n_q * head_dim, d_model), dtype) * s,
    }


def _flash_block(q, k, v, q_pos, k_pos, window):
    """One (q-chunk x kv-chunk) attention tile with causal (+SWA) mask.

    q: [B, H, Tq, hd]; k,v: [B, H, Tk, hd] (kv already repeated to H).
    Returns (scores_max, exp_sums, out_chunk) for online softmax.
    """
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    mask = k_pos[None, None, None, :] <= q_pos[None, None, :, None]
    if window > 0:
        mask &= k_pos[None, None, None, :] > (
            q_pos[None, None, :, None] - window)
    s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, axis=-1)                          # [B,H,Tq]
    p = jnp.exp(s - m[..., None])
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)                          # [B,H,Tq]
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return m, l, o


def flash_attention(q, k, v, q_positions, k_positions, window: int = 0,
                    q_chunk: int = 1024, kv_chunk: int = 1024):
    """Blockwise causal attention; never materializes [S, S].

    q: [B, Hq, Sq, hd]; k,v: [B, Hkv, Sk, hd]; positions are absolute token
    indices (enables decode with cache and sequence-sharded layouts).

    The kv-step body is checkpointed so the backward pass recomputes each
    (q-chunk x kv-chunk) score block instead of saving it — O(S) residual
    memory like a flash kernel, not O(S^2).
    """
    B, Hq, Sq, hd = q.shape
    Hkv = k.shape[1]
    rep = Hq // Hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    Sk = k.shape[2]
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    nq = Sq // q_chunk
    nk = Sk // kv_chunk

    q_r = q.reshape(B, Hq, nq, q_chunk, hd).transpose(2, 0, 1, 3, 4)
    qp_r = q_positions.reshape(nq, q_chunk)

    k_r = k.reshape(B, Hq, nk, kv_chunk, hd).transpose(2, 0, 1, 3, 4)
    v_r = v.reshape(B, Hq, nk, kv_chunk, hd).transpose(2, 0, 1, 3, 4)
    kp_r = k_positions.reshape(nk, kv_chunk)

    def per_q_chunk(qc, qpc):
        @partial(jax.checkpoint,
                 policy=jax.checkpoint_policies.nothing_saveable)
        def kv_step(carry, xs):
            m_run, l_run, o_run = carry
            kc, vc, kpc = xs
            m, l, o = _flash_block(qc, kc, vc, qpc, kpc, window)
            m_new = jnp.maximum(m_run, m)
            alpha = jnp.exp(m_run - m_new)
            beta = jnp.exp(m - m_new)
            l_new = l_run * alpha + l * beta
            o_new = o_run * alpha[..., None] + o * beta[..., None]
            return (m_new, l_new, o_new), None

        m0 = jnp.full((B, Hq, q_chunk), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Hq, q_chunk), jnp.float32)
        o0 = jnp.zeros((B, Hq, q_chunk, hd), jnp.float32)
        (m_f, l_f, o_f), _ = jax.lax.scan(
            kv_step, (m0, l0, o0), (k_r, v_r, kp_r))
        return o_f / jnp.maximum(l_f[..., None], 1e-30)

    out = jax.lax.map(lambda xs: per_q_chunk(*xs), (q_r, qp_r))
    out = out.transpose(1, 2, 0, 3, 4).reshape(B, Hq, Sq, hd)
    return out.astype(q.dtype)


def sharded_cache_attention(mesh, dp_axes):
    """Hand-distributed decode attention over a sequence-sharded KV cache.

    The SPMD partitioner, left to itself, re-shards the cache toward a
    kv-head layout and emits full-cache all-gathers (in f32!) every token
    — the dominant collective of baseline decode. Under shard_map the C
    (cache sequence) dim stays explicitly local and the softmax reduces
    with psum-max / psum-sum of [B,H,1]-sized tensors; the attention
    output psum is [B,H,1,hd] — a few hundred KB per layer instead of
    gigabytes. (§Perf decode iteration 3.)
    """
    from jax.sharding import PartitionSpec as P
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]

    def local_attn(q, kk, vv, kpos, valid, pos_now, window_arr):
        # q [B,H,1,hd] replicated; kk/vv [B,H,C_loc,hd]; kpos/valid [C_loc]
        scale = q.shape[-1] ** -0.5
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kk,
                       preferred_element_type=jnp.float32) * scale
        mask = (kpos[None, None, None, :] <= pos_now) & \
            valid[None, None, None, :]
        w = window_arr[0]
        mask &= (w <= 0) | (kpos[None, None, None, :] > pos_now - w)
        s = jnp.where(mask, s, -jnp.inf)
        m = jax.lax.pmax(jnp.max(s, axis=-1), "model")        # [B,H,1]
        e = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
        l = jax.lax.psum(jnp.sum(e, axis=-1), "model")        # [B,H,1]
        o = jnp.einsum("bhqk,bhkd->bhqd", e.astype(vv.dtype), vv,
                       preferred_element_type=jnp.float32)
        o = jax.lax.psum(o, "model")                          # [B,H,1,hd]
        return (o / jnp.maximum(l[..., None], 1e-30))

    return jax.shard_map(
        local_attn, mesh=mesh,
        in_specs=(P(dp, None, None, None), P(dp, None, "model", None),
                  P(dp, None, "model", None), P("model"), P("model"),
                  P(), P(None)),
        out_specs=P(dp, None, None, None),
        check_vma=False)


def attention_fwd(params, x, positions, *, n_q, n_kv, head_dim,
                  rope_theta, window=0, cache=None, select_write=False,
                  head_shardings=None, cache_attn=None):
    """GQA attention. x: [B, S, D].

    cache: None for training, else dict(k=[B, n_kv, C, hd], v=...,
    pos=[C], valid=[C]) for decode — returns the updated cache. The cache
    is a ring over C slots (C == window for SWA, == context for full).

    select_write: write the new token via iota-compare-select instead of
    dynamic_update_slice — required when C is sharded (long-context decode
    shards the KV sequence over "data"); DUS on a sharded dim would gather.
    """
    B, S, D = x.shape
    q = (x @ params["wq"]).reshape(B, S, n_q, head_dim)
    k = (x @ params["wk"]).reshape(B, S, n_kv, head_dim)
    v = (x @ params["wv"]).reshape(B, S, n_kv, head_dim)
    q = rope(q, positions, rope_theta).transpose(0, 2, 1, 3)
    k = rope(k, positions, rope_theta).transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    if head_shardings is not None:
        # Megatron-SP transition: residual is sequence-sharded over
        # "model"; attention computes with heads over "model" and full S.
        # These constraints pin the reshard point so SPMD does not drag
        # S-sharding (and replicated heads) through the flash scan.
        q_s, kv_s = head_shardings
        q = jax.lax.with_sharding_constraint(q, q_s)
        k = jax.lax.with_sharding_constraint(k, kv_s)
        v = jax.lax.with_sharding_constraint(v, kv_s)

    if cache is None:
        out = flash_attention(q, k, v, positions, positions, window)
        new_cache = None
    else:
        C = cache["k"].shape[2]
        slot = positions[0] % C
        kc = k.astype(cache["k"].dtype)
        vc = v.astype(cache["v"].dtype)
        if select_write:
            sel = jax.lax.broadcasted_iota(jnp.int32, (C,), 0) == slot
            ck = jnp.where(sel[None, None, :, None], kc, cache["k"])
            cv = jnp.where(sel[None, None, :, None], vc, cache["v"])
            cpos = jnp.where(sel, positions[0], cache["pos"])
            cvalid = cache["valid"] | sel
        else:
            ck = jax.lax.dynamic_update_slice(cache["k"], kc, (0, 0, slot, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], vc, (0, 0, slot, 0))
            cpos = jax.lax.dynamic_update_slice(
                cache["pos"], positions.astype(cache["pos"].dtype), (slot,))
            cvalid = cache["valid"].at[slot].set(True)
        kk = jnp.repeat(ck, n_q // n_kv, axis=1)
        vv = jnp.repeat(cv, n_q // n_kv, axis=1)
        if cache_attn is not None:
            out = cache_attn(q, kk, vv, cpos, cvalid, positions[0],
                             jnp.asarray([window], jnp.int32))
        else:
            scale = head_dim ** -0.5
            s = jnp.einsum("bhqd,bhkd->bhqk", q, kk,
                           preferred_element_type=jnp.float32) * scale
            mask = (cpos[None, None, None, :]
                    <= positions[None, None, :, None])
            mask &= cvalid[None, None, None, :]
            if window > 0:
                mask &= cpos[None, None, None, :] > (
                    positions[None, None, :, None] - window)
            s = jnp.where(mask, s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vv.dtype), vv,
                             preferred_element_type=jnp.float32)
        new_cache = {"k": ck, "v": cv, "pos": cpos, "valid": cvalid}

    out = out.astype(x.dtype).transpose(0, 2, 1, 3).reshape(B, S, -1)
    return out @ params["wo"], new_cache


def init_attention_cache(batch, n_kv, cache_len, head_dim, dtype):
    return {
        "k": jnp.zeros((batch, n_kv, cache_len, head_dim), dtype),
        "v": jnp.zeros((batch, n_kv, cache_len, head_dim), dtype),
        "pos": jnp.zeros((cache_len,), jnp.int32),
        "valid": jnp.zeros((cache_len,), jnp.bool_),
    }


# ---------------------------------------------------------------------------
# FFNs: dense SwiGLU and dropless MoE (top-k, ragged_dot)
# ---------------------------------------------------------------------------


def init_mlp(key, d_model, d_ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    s = d_model ** -0.5
    return {
        "w_gate": jax.random.normal(k1, (d_model, d_ff), dtype) * s,
        "w_up": jax.random.normal(k2, (d_model, d_ff), dtype) * s,
        "w_down": jax.random.normal(k3, (d_ff, d_model), dtype) * (d_ff ** -0.5),
    }


def mlp_fwd(params, x):
    h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    return h @ params["w_down"]


def init_moe(key, d_model, d_ff, n_experts, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d_model ** -0.5
    return {
        "router": jax.random.normal(k1, (d_model, n_experts), jnp.float32) * s,
        "w_gate": jax.random.normal(k2, (n_experts, d_model, d_ff), dtype) * s,
        "w_up": jax.random.normal(k3, (n_experts, d_model, d_ff), dtype) * s,
        "w_down": jax.random.normal(k4, (n_experts, d_ff, d_model), dtype)
        * (d_ff ** -0.5),
    }


def moe_fwd(params, x, *, top_k: int):
    """Dropless token-choice MoE via sort + grouped GEMM (ragged_dot).

    x: [B, S, D] -> [B, S, D]. Aux losses (load balance) returned for
    training. Tokens stay on their data shard; experts' FFN dim is
    tensor-parallel over "model" (see sharding rules).
    """
    B, S, D = x.shape
    E = params["router"].shape[1]
    xt = x.reshape(B * S, D)
    T = B * S

    logits = (xt.astype(jnp.float32) @ params["router"])       # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, experts = jax.lax.top_k(probs, top_k)           # [T, K]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # flatten (token, k) assignments and sort by expert id
    flat_expert = experts.reshape(-1)                          # [T*K]
    flat_token = jnp.repeat(jnp.arange(T), top_k)
    order = jnp.argsort(flat_expert)
    sorted_expert = flat_expert[order]
    sorted_token = flat_token[order]
    group_sizes = jnp.bincount(sorted_expert, length=E).astype(jnp.int32)

    xin = xt[sorted_token]                                     # [T*K, D]
    h = jax.lax.ragged_dot(xin, params["w_gate"], group_sizes)
    u = jax.lax.ragged_dot(xin, params["w_up"], group_sizes)
    act = jax.nn.silu(h) * u
    yo = jax.lax.ragged_dot(act, params["w_down"], group_sizes)  # [T*K, D]

    gates_sorted = gate_vals.reshape(-1)[order]
    yo = yo * gates_sorted[:, None].astype(yo.dtype)
    out = jnp.zeros((T, D), yo.dtype).at[sorted_token].add(yo)

    # load-balance aux loss (Switch-style)
    me = probs.mean(axis=0)
    ce = jnp.bincount(flat_expert, length=E).astype(jnp.float32) / (T * top_k)
    aux = E * jnp.sum(me * ce)
    return out.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# Mamba mixer (simplified selective SSM, Jamba-style)
# ---------------------------------------------------------------------------


def init_mamba(key, d_model, d_state, expand, dtype):
    d_inner = expand * d_model
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    s = d_model ** -0.5
    return {
        "w_in": jax.random.normal(k1, (d_model, 2 * d_inner), dtype) * s,
        "w_out": jax.random.normal(k2, (d_inner, d_model), dtype)
        * (d_inner ** -0.5),
        "w_bcdt": jax.random.normal(k3, (d_inner, 2 * d_state + 1), dtype)
        * (d_inner ** -0.5),
        "a_log": jnp.zeros((d_inner, d_state), jnp.float32)
        + jnp.log(jnp.arange(1, d_state + 1, dtype=jnp.float32)),
        "dt_bias": jnp.zeros((), jnp.float32),
    }


def mamba_fwd(params, x, state=None):
    """Selective SSM scan. x: [B, S, D]; state: [B, d_inner, N] for decode.

    Linear-time in S (lax.scan over time, associative-scan-friendly form).
    """
    B, S, D = x.shape
    xi = x @ params["w_in"]
    d_inner = xi.shape[-1] // 2
    u, gate = jnp.split(xi, 2, axis=-1)                      # [B, S, d_inner]
    bcdt = u @ params["w_bcdt"]                               # [B,S,2N+1]
    N = params["a_log"].shape[1]
    Bc, Cc, dt = (bcdt[..., :N], bcdt[..., N:2 * N], bcdt[..., -1:])
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["a_log"])                             # [d_inner, N]
    dA = jnp.exp(dt[..., None] * A[None, None])               # [B,S,d_inner,N]
    dBu = (dt * u.astype(jnp.float32))[..., None] * Bc[..., None, :].astype(
        jnp.float32)                                          # [B,S,d_inner,N]

    def step(h, xs):
        dA_t, dBu_t, C_t = xs
        h = h * dA_t + dBu_t                                  # [B,d_inner,N]
        y = jnp.einsum("bdn,bn->bd", h, C_t)
        return h, y

    h0 = (jnp.zeros((B, d_inner, N), jnp.float32) if state is None
          else state.astype(jnp.float32))
    hT, ys = jax.lax.scan(
        step, h0,
        (dA.transpose(1, 0, 2, 3), dBu.transpose(1, 0, 2, 3),
         Cc.transpose(1, 0, 2).astype(jnp.float32)))
    y = ys.transpose(1, 0, 2).astype(x.dtype)                 # [B,S,d_inner]
    out = (y * jax.nn.silu(gate)) @ params["w_out"]
    return out, hT.astype(jnp.float32)


# ---------------------------------------------------------------------------
# RWKV6 (Finch) mixer: data-dependent decay, per-head matrix state
# ---------------------------------------------------------------------------


def init_rwkv(key, d_model, n_heads, dtype):
    hd = d_model // n_heads
    ks = jax.random.split(key, 6)
    s = d_model ** -0.5
    return {
        "w_r": jax.random.normal(ks[0], (d_model, d_model), dtype) * s,
        "w_k": jax.random.normal(ks[1], (d_model, d_model), dtype) * s,
        "w_v": jax.random.normal(ks[2], (d_model, d_model), dtype) * s,
        "w_g": jax.random.normal(ks[3], (d_model, d_model), dtype) * s,
        "w_o": jax.random.normal(ks[4], (d_model, d_model), dtype) * s,
        "w_decay": jax.random.normal(ks[5], (d_model, d_model), dtype) * s,
        "decay_bias": jnp.full((d_model,), -6.0, jnp.float32),
        "bonus": jnp.zeros((n_heads, hd), jnp.float32),
        "mix": jnp.full((5, d_model), 0.5, jnp.float32),
    }


def rwkv_fwd(params, x, state=None, *, n_heads):
    """RWKV6 time-mix. x: [B, S, D].

    state: dict(wkv=[B, H, hd, hd], prev=[B, D]) for decode; None = train
    (zero-init state, token shift from the sequence itself).
    Data-dependent decay w_t = exp(-exp(decay(x_t))) is the Finch feature.
    """
    B, S, D = x.shape
    DI = params["w_r"].shape[1]  # padded inner dim (heads * head_dim)
    hd = DI // n_heads
    prev = (jnp.zeros((B, 1, D), x.dtype) if state is None
            else state["prev"][:, None, :].astype(x.dtype))
    x_shift = jnp.concatenate([prev, x[:, :-1]], axis=1)

    def mixed(i):
        m = params["mix"][i].astype(x.dtype)
        return x * m + x_shift * (1 - m)

    r = (mixed(0) @ params["w_r"]).reshape(B, S, n_heads, hd)
    k = (mixed(1) @ params["w_k"]).reshape(B, S, n_heads, hd)
    v = (mixed(2) @ params["w_v"]).reshape(B, S, n_heads, hd)
    g = jax.nn.silu(mixed(3) @ params["w_g"])
    decay = (mixed(4) @ params["w_decay"]).astype(jnp.float32)
    w = jnp.exp(-jnp.exp(decay + params["decay_bias"]))       # [B,S,DI] in (0,1)
    w = w.reshape(B, S, n_heads, hd)
    bonus = params["bonus"][None, :, :, None]                  # [1,H,hd_k,1]

    def step(h, xs):
        r_t, k_t, v_t, w_t = xs  # [B,H,hd]
        kv = jnp.einsum("bhk,bhv->bhkv", k_t.astype(jnp.float32),
                        v_t.astype(jnp.float32))
        y = jnp.einsum("bhk,bhkv->bhv", r_t.astype(jnp.float32),
                       h + bonus * kv)
        h = h * w_t.astype(jnp.float32)[..., None] + kv
        return h, y

    h0 = (jnp.zeros((B, n_heads, hd, hd), jnp.float32) if state is None
          else state["wkv"].astype(jnp.float32))
    hT, ys = jax.lax.scan(
        step, h0,
        (r.transpose(1, 0, 2, 3), k.transpose(1, 0, 2, 3),
         v.transpose(1, 0, 2, 3), w.transpose(1, 0, 2, 3)))
    y = ys.transpose(1, 0, 2, 3).reshape(B, S, DI).astype(x.dtype)
    out = (y * g) @ params["w_o"]
    new_state = {"wkv": hT, "prev": x[:, -1]}
    return out, new_state


def init_rwkv_mlp(key, d_model, d_ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    s = d_model ** -0.5
    return {
        "w_k": jax.random.normal(k1, (d_model, d_ff), dtype) * s,
        "w_v": jax.random.normal(k2, (d_ff, d_model), dtype) * (d_ff ** -0.5),
        "w_r": jax.random.normal(k3, (d_model, d_model), dtype) * s,
        "mix": jnp.full((2, d_model), 0.5, jnp.float32),
    }


def rwkv_mlp_fwd(params, x, prev=None):
    """RWKV channel-mix (squared-relu FFN with token shift + receptance)."""
    B, S, D = x.shape
    pv = (jnp.zeros((B, 1, D), x.dtype) if prev is None
          else prev[:, None, :].astype(x.dtype))
    x_shift = jnp.concatenate([pv, x[:, :-1]], axis=1)
    mk = params["mix"][0].astype(x.dtype)
    mr = params["mix"][1].astype(x.dtype)
    xk = x * mk + x_shift * (1 - mk)
    xr = x * mr + x_shift * (1 - mr)
    k = jnp.square(jax.nn.relu(xk @ params["w_k"]))
    r = jax.nn.sigmoid(xr @ params["w_r"])
    return r * (k @ params["w_v"]), x[:, -1]
