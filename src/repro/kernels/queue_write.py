"""The enqueue write of the fabric tick as one exact one-hot MXU
contraction, for the TPU.

Each tick the tick's enqueue phase writes its n = Q + F candidate packet
records ([n, 5] int32) into the ring buffers ``q_pkt`` [Q, C, 5]:
candidate i, if it fits, into slot wslot[i] of queue tq[i]. The oracle
(``ref.queue_write_ref``) is a scatter of n rows; a TPU runs a scatter's
update rows one after another.

One writer per slot. A fitting candidate i enters queue q at position
q_len[q] + rank_i (its arrival rank among this tick's candidates for q)
and writes slot (q_head[q] + q_len[q] + rank_i) % C, with
q_len[q] + rank_i < C. The ranks of one queue's candidates are
distinct, so every (q, c) slot has at most one writer per tick, and no
writer lands on a slot the ring still holds.

So the write is a contraction over the candidates,

    got[q, j, c] = sum_i hot[q, i] * at[i, c] * col[i, j],
    hot[q, i] = (tq[i] == q),  at[i, c] = (wslot[i] == c),

an MXU matmul [Q, n] x [n, J·C], where the J = 4·5 + 1 columns of a
candidate are the four bytes of each of its five fields (bit-cast to
uint32) and a 1 that marks the slot written. The operands are bfloat16,
which holds 0, 1 and every byte value exactly, and the products
accumulate in float32. Each output element has at most one nonzero
term, a byte times 1, so the float32 result is that byte exactly,
whatever the order of accumulation. The bytes are recombined into the
fields and selected over the slots the 1-column marks; the others keep
their records. The result equals the scatter's bit for bit. (Operands
in float32 would not do: the TPU's default precision rounds them
through bfloat16 passes.)

Its work grows as Q·n·C, the scatter's as n: ``ops.queue_write`` takes
this path for shapes under its measured bound only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: byte limbs of an int32 field
LIMBS = 4


def queue_write(q_pkt: jax.Array, tq: jax.Array, wslot: jax.Array,
                cand_pkt: jax.Array) -> jax.Array:
    """``ref.queue_write_ref``'s result, for inputs with at most one
    writer per (queue, slot): q_pkt [Q, C, K] int32, tq/wslot [n] int32,
    cand_pkt [n, K] int32."""
    q, c, k = q_pkt.shape
    n = tq.shape[0]
    j = k * LIMBS + 1
    shift = jnp.arange(0, 32, 8, dtype=jnp.uint32)
    u = jax.lax.bitcast_convert_type(cand_pkt, jnp.uint32)
    limbs = (u[:, :, None] >> shift) & 0xFF                        # [n, K, 4]
    col = jnp.concatenate([limbs.reshape(n, j - 1),
                           jnp.ones((n, 1), jnp.uint32)], axis=1)  # [n, J]
    at = wslot[:, None] == jnp.arange(c)                            # [n, C]
    rhs = jnp.where(at[:, None, :], col[:, :, None], 0)             # [n, J, C]
    hot = tq[None, :] == jnp.arange(q)[:, None]                     # [Q, n]
    got = jnp.dot(hot.astype(jnp.bfloat16),
                  rhs.reshape(n, j * c).astype(jnp.bfloat16),
                  preferred_element_type=jnp.float32)
    got = got.reshape(q, j, c).astype(jnp.uint32)                   # [Q, J, C]
    fields = (got[:, :-1].reshape(q, k, LIMBS, c)
              << shift[None, None, :, None]).sum(axis=2, dtype=jnp.uint32)
    new = jax.lax.bitcast_convert_type(fields, jnp.int32).transpose(0, 2, 1)
    return jnp.where(got[:, -1, :, None] > 0, new, q_pkt)
