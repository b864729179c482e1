"""Expert parallelism: top-1 token routing with all_to_all dispatch.

The reference has no mixture-of-experts (SURVEY §2.4.9); its structural
analog is label-/edge-space sharding (§2.4.5), where work is routed by id
range instead of by a learned gate.  The TPU framework provides real expert
parallelism as a first-class primitive: experts live one-per-device along
an ``expert`` mesh axis, each device routes its local tokens to the experts
chosen by the gate, and the exchange is a single ``lax.all_to_all`` over
ICI in each direction — the canonical MoE dispatch/combine pattern.

Capacity semantics follow the standard MoE recipe: each expert accepts at
most ``capacity`` tokens per source device; overflow tokens pass through
unchanged (residual), never silently dropped.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh


def moe_apply(fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
              expert_params: Any, gate_logits: jnp.ndarray,
              tokens: jnp.ndarray, mesh: Mesh, axis: str = "expert",
              capacity: int = 0) -> jnp.ndarray:
    """Route tokens to experts along a mesh axis and combine.

    ``fn(params_e, x[C, d]) -> y[C, d]`` is one expert applied to its
    capacity buffer; ``expert_params`` has a leading ``n_experts`` axis;
    ``gate_logits``: ``(T, n_experts)`` per-token scores; ``tokens``:
    ``(T, d)``.  Both are GLOBAL arrays sharded over ``axis`` by shard_map
    (T must divide by the axis size).  Returns ``(T, d)``:
    ``g * expert(token) + (1 - g) * token`` for routed tokens (g = the
    gate's softmax weight of the chosen expert), identity for overflow.
    """
    from jax import shard_map

    n_experts = mesh.shape[axis]
    t_local = tokens.shape[0] // n_experts
    cap = capacity or -(-t_local // n_experts)  # default: even split

    def body(params, logits, x):
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        choice = jnp.argmax(logits, axis=1)                        # (T,)
        gate = jax.nn.softmax(logits, axis=1)[
            jnp.arange(t_local), choice]                           # (T,)
        onehot = jax.nn.one_hot(choice, n_experts, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - 1)[
            jnp.arange(t_local), choice]                           # (T,)
        keep = pos < cap
        slot = jnp.where(keep, pos, cap)  # overflow -> scratch slot
        # dispatch buffer: (n_experts, cap+1, d); scratch row dropped below
        disp = jnp.zeros((n_experts, cap + 1, x.shape[1]), x.dtype)
        disp = disp.at[choice, slot].set(x)
        disp = disp[:, :cap]
        # exchange: leading axis expert -> source device
        disp = jax.lax.all_to_all(disp, axis, split_axis=0, concat_axis=0,
                                  tiled=True)
        y = fn(params, disp.reshape(n_experts * cap, x.shape[1]))
        y = y.reshape(n_experts, cap, x.shape[1])
        y = jax.lax.all_to_all(y, axis, split_axis=0, concat_axis=0,
                               tiled=True)
        # combine: gather each kept token's transformed value
        routed = y[choice, jnp.where(keep, pos, 0)]
        g = (gate * keep)[:, None]
        return g * routed + (1.0 - g) * x

    spec_p = jax.sharding.PartitionSpec(axis)
    spec_t = jax.sharding.PartitionSpec(axis)
    return shard_map(body, mesh=mesh,
                     in_specs=(spec_p, spec_t, spec_t),
                     out_specs=spec_t)(expert_params, gate_logits, tokens)


def make_expert_mesh(n_experts: int, n_devices: int = None) -> Mesh:
    """Mesh with a single ``expert`` axis (one expert per device)."""
    from .mesh import single_axis_mesh

    return single_axis_mesh("expert", n_experts, n_devices)
