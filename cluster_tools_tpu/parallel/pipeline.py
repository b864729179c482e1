"""Pipeline parallelism: microbatched stage execution over a mesh axis.

The reference has no pipeline parallelism (SURVEY §2.4.9 lists it as absent;
its closest structural analog is the checkerboard two-pass schedule,
§2.4.3).  The TPU framework provides it as a first-class primitive so deep
models can be staged across chips when activations, not parameters, are the
memory bound: stage ``i`` of the model lives on device ``i`` along the
``pipe`` mesh axis, microbatches stream through the classic GPipe schedule
(``n_micro + n_stages - 1`` steps), and activations hop stage-to-stage with
``lax.ppermute`` over ICI — the same collective the sharded stencil uses
(parallel/stencil.py).

Everything is a single jitted SPMD program: no host round-trips between
stages, no data-dependent shapes.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stack_stage_params(per_stage_params) -> Any:
    """Stack a list of per-stage parameter pytrees along a new leading axis
    (the axis ``pipeline_apply`` shards over the pipe dimension)."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)


def pipeline_apply(fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   stage_params: Any, x: jnp.ndarray, mesh: Mesh,
                   axis: str = "pipe") -> jnp.ndarray:
    """Apply ``n_stages`` chained stages to microbatched input.

    ``fn(params_i, a) -> a`` is one stage (activation-shape preserving);
    ``stage_params`` has a leading ``n_stages`` axis (see
    :func:`stack_stage_params`); ``x`` is ``(n_micro, *mb_shape)``.
    Returns ``(n_micro, *mb_shape)`` equal to applying stages 0..n-1 in
    order to every microbatch.

    Schedule: T = n_micro + n_stages - 1 steps; at step t, stage 0 ingests
    microbatch t (while t < n_micro), every stage applies ``fn``, the
    result is ppermuted to the next stage, and the last stage emits
    microbatch t - (n_stages - 1).  The emitted buffer is summed over the
    pipe axis at the end (all other stages contribute zeros), so the result
    is replicated — callers re-shard as needed.
    """
    from jax import shard_map

    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    n_steps = n_micro + n_stages - 1
    # pad the microbatch axis to n_steps so step indices never leave the
    # buffer (the pads are never consumed as real output)
    pad = [(0, n_steps - n_micro)] + [(0, 0)] * (x.ndim - 1)
    x_pad = jnp.pad(x, pad)

    def stage_body(params, xp):
        # params: leading stage axis of size 1 (this device's slice)
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        idx = jax.lax.axis_index(axis)
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def step(t, carry):
            act, out = carry
            mb = jax.lax.dynamic_index_in_dim(xp, t, 0, keepdims=False)
            inp = jnp.where(idx == 0, mb, act)
            res = fn(params, inp)
            emit = jnp.where(idx == n_stages - 1, res, jnp.zeros_like(res))
            out = jax.lax.dynamic_update_index_in_dim(out, emit, t, 0)
            act = jax.lax.ppermute(res, axis, perm)
            return act, out

        # initial carries must already be marked device-varying over the
        # pipe axis (the loop body makes them varying via ppermute/where)
        from .stencil import device_varying

        act0 = device_varying(jnp.zeros_like(xp[0]), axis)
        out0 = device_varying(jnp.zeros_like(xp), axis)
        _, out = jax.lax.fori_loop(0, n_steps, step, (act0, out0))
        # only the last stage wrote non-zeros; broadcast via psum
        return jax.lax.psum(out, axis)

    spec_params = P(axis)
    spec_x = P()  # replicated input microbatches
    result = shard_map(
        stage_body, mesh=mesh,
        in_specs=(spec_params, spec_x), out_specs=spec_x,
    )(stage_params, x_pad)
    # microbatch t exits the pipe at step t + n_stages - 1
    return result[n_stages - 1:n_stages - 1 + n_micro]


def make_pipe_mesh(n_stages: int, n_devices: int = None) -> Mesh:
    """Mesh with a leading ``pipe`` axis of size ``n_stages`` (remaining
    devices ride a ``data`` axis)."""
    devices = jax.devices()
    n = n_devices or len(devices)
    assert n % n_stages == 0, (n, n_stages)
    arr = np.array(devices[:n]).reshape(n_stages, n // n_stages)
    return Mesh(arr, ("pipe", "data"))
