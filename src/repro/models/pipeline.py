"""GPipe-style pipeline parallelism via shard_map + ppermute.

Alternative schedule for very deep stacks (e.g. qwen2-72b 80L): stages are
laid out along a mesh axis; microbatch activations rotate stage-to-stage
with ``collective_permute`` while every stage computes — the classic
bubble-bounded schedule (bubble fraction = (S-1)/(M+S-1)).

``gpipe_apply`` is schedule-exact and correctness-tested against the
sequential stack (tests/test_sharding.py); the LM integration point is
``stage_fn = one scan-group of blocks`` with stage-stacked params.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def gpipe_apply(stage_fn, stage_params, x_micro, *, mesh, axis: str):
    """Run ``n_stages = mesh[axis]`` pipeline stages over microbatches.

    stage_fn: (params_of_one_stage, x (mb, …)) → (mb, …); same out shape
    stage_params: pytree with leading stage dim == n_stages (sharded on axis)
    x_micro: (n_micro, mb, …) inputs (replicated along ``axis``)
    Returns (n_micro, mb, …) outputs of the final stage (replicated).
    """
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1
    ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(params, xs):
        params = jax.tree.map(lambda a: a[0], params)  # my stage's slice
        sid = jax.lax.axis_index(axis)
        zero = jnp.zeros_like(xs[0])

        def tick(t, carry):
            buf, out = carry            # buf: activation entering my stage
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            x_in = jnp.where(sid == 0,
                             jax.lax.dynamic_index_in_dim(
                                 xs, mb_idx, 0, keepdims=False),
                             buf)
            active = (t - sid >= 0) & (t - sid < n_micro)
            y = jnp.where(active, stage_fn(params, x_in), zero)
            # the last stage emits microbatch (t - S + 1)
            emit = t - (n_stages - 1)
            do_emit = (sid == n_stages - 1) & (emit >= 0)
            out = jax.lax.cond(
                do_emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(emit, 0, n_micro - 1), 0),
                lambda o: o, out)
            buf = jax.lax.ppermute(y, axis, ring)  # stage s → s+1
            return buf, out

        _, out = jax.lax.fori_loop(
            0, ticks, tick, (zero, jnp.zeros_like(xs)))
        # outputs live on the last stage only (zeros elsewhere): share them
        return jax.lax.psum(out, axis)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x_micro)
