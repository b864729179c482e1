"""Ring attention: exact attention over sequences sharded across chips.

The reference has no attention (SURVEY §5.7 — its long-context analog is
the spatial halo machinery), but a TPU framework that claims long-context
as first-class needs the real thing: sequences too long for one chip's HBM,
sharded over a mesh axis, attended exactly.  This is the standard ring
schedule: queries stay put, key/value chunks rotate around the ring via
``lax.ppermute`` (ICI neighbor traffic only — no all_gather of the full
sequence), and each hop folds its partial attention into a numerically
stable online softmax (the flash-attention recurrence: running max,
running normalizer, running weighted sum).  After ``n_shards`` hops every
query has seen every key exactly once; the result is bit-for-bit a
softmax-attention up to float associativity.

Causal masking works across shards by comparing global positions (each
chunk carries its shard offset around the ring).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   mesh: Mesh, axis: str = "seq",
                   causal: bool = False) -> jnp.ndarray:
    """Exact (optionally causal) attention with the sequence axis sharded
    over ``axis``.

    ``q, k, v``: ``(T, H, D)`` GLOBAL arrays, sharded over the leading
    (sequence) axis by shard_map; T must divide by the axis size.  Returns
    ``(T, H, D)`` — ``softmax(q k^T / sqrt(D)) v`` computed without any
    device ever holding more than its ``T / n_shards`` slice of k/v.
    """
    from jax import shard_map

    n_shards = mesh.shape[axis]
    t_local = q.shape[0] // n_shards
    scale = 1.0 / float(np.sqrt(q.shape[-1]))
    # rotate chunks backwards so shard i sees chunks i, i+1, ... in turn
    perm = [(j, (j - 1) % n_shards) for j in range(n_shards)]

    def body(ql, kl, vl):
        my = jax.lax.axis_index(axis)
        q_pos = my * t_local + jnp.arange(t_local)          # global rows

        def attend(step, kc, vc, m, l, o):
            # bf16 operands at full MXU rate, f32 accumulation
            s = jnp.einsum("thd,shd->hts", ql, kc,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                # the resident chunk at hop `step` originated at shard
                # (my + step) % n_shards — no collective needed to track it
                src = (my + step) % n_shards
                k_pos = src * t_local + jnp.arange(t_local)
                mask = q_pos[:, None] >= k_pos[None, :]
                s = jnp.where(mask[None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=2))
            # rows with no visible key yet (causal, all -inf) must not
            # poison exp(): substitute a finite max; exp(m - m_safe) is
            # then already 0 for the -inf prior state
            m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            corr = jnp.exp(m - m_safe)
            p = jnp.exp(s - m_safe[:, :, None])
            l_new = l * corr + p.sum(axis=2)
            o_new = (o * corr[..., None]
                     + jnp.einsum("hts,shd->thd", p, vc,
                                  preferred_element_type=jnp.float32
                                  ).transpose(1, 0, 2))
            return m_new, l_new, o_new

        def hop(step, carry):
            kc, vc, m, l, o = carry
            m, l, o = attend(step, kc, vc, m, l, o)
            kc = jax.lax.ppermute(kc, axis, perm)
            vc = jax.lax.ppermute(vc, axis, perm)
            return kc, vc, m, l, o

        # initial accumulators must be marked device-varying over the ring
        # axis (the loop makes them varying via the per-shard partials)
        from .stencil import device_varying

        h, d = ql.shape[1], ql.shape[2]
        m0 = device_varying(jnp.full((h, t_local), -jnp.inf, jnp.float32),
                            axis)
        l0 = device_varying(jnp.zeros((h, t_local), jnp.float32), axis)
        o0 = device_varying(jnp.zeros((h, t_local, d), jnp.float32), axis)
        carry = (kl, vl, m0, l0, o0)
        # the final hop attends without rotating (its permuted chunk would
        # be discarded — one full K+V ICI transfer saved per call)
        kc, vc, m, l, o = jax.lax.fori_loop(0, n_shards - 1, hop, carry)
        m, l, o = attend(n_shards - 1, kc, vc, m, l, o)
        out = o / jnp.maximum(l[..., None], 1e-30)
        return out.transpose(1, 0, 2).astype(ql.dtype)      # (t, H, D)

    spec = P(axis)
    return shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


def make_seq_mesh(n_shards: int, n_devices: Optional[int] = None) -> Mesh:
    """Mesh with a single ``seq`` axis for sequence/context parallelism."""
    from .mesh import single_axis_mesh

    return single_axis_mesh("seq", n_shards, n_devices)
