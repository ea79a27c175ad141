"""GPipe-style pipeline parallelism over a 'stage' mesh axis (shard_map +
collective_permute microbatch ring).

Not part of the prescribed production mesh (data x model); provided as the
at-scale option for >2-pod deployments and exercised by tests on 4-8 host
devices. Each stage holds its own layer block; microbatches flow stage to
stage via ppermute; the steady-state keeps every stage busy after the
pipeline fill (bubble fraction = (S-1)/(S-1+M) for S stages, M microbatches).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(mesh: Mesh, stage_fn: Callable, n_microbatches: int):
    """Build a pipelined forward: x (M, mb, ...) sharded over nothing,
    stage params stacked on a leading 'stage' dim sharded over the axis.

    stage_fn(params_slice, x_mb) -> x_mb.
    """
    n_stages = mesh.shape["stage"]
    assert n_microbatches >= n_stages

    def _local(params_local, x_all):
        # params_local: (1, ...) this stage's params; x_all: (M, mb, ...)
        sid = jax.lax.axis_index("stage")
        p = jax.tree.map(lambda a: a[0], params_local)
        total = n_microbatches + n_stages - 1

        def tick(carry, t):
            buf, out = carry          # buf: the microbatch entering this stage
            # stage s processes microbatch (t - s) when 0 <= t - s < M
            mb_idx = t - sid
            active = (mb_idx >= 0) & (mb_idx < n_microbatches)
            # stage 0 ingests a fresh microbatch
            fresh = x_all[jnp.clip(mb_idx, 0, n_microbatches - 1)]
            x_in = jnp.where(sid == 0, fresh, buf)
            y = stage_fn(p, x_in)
            y = jnp.where(active, y, buf)
            # last stage emits; others forward along the ring
            out = jax.lax.cond(
                (sid == n_stages - 1),
                lambda o: o.at[jnp.clip(mb_idx, 0, n_microbatches - 1)].set(
                    jnp.where(active, y, o[jnp.clip(mb_idx, 0, n_microbatches - 1)])),
                lambda o: o,
                out)
            nxt = jax.lax.ppermute(
                y, "stage", [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, out), None

        buf0 = jnp.zeros_like(x_all[0])
        out0 = jnp.zeros_like(x_all)
        (buf, out), _ = jax.lax.scan(tick, (buf0, out0), jnp.arange(total))
        # every stage holds only the true outputs on the last stage; broadcast
        out = jax.lax.psum(jnp.where(sid == n_stages - 1, out, 0.0), "stage")
        return out

    return jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P("stage"), P()),
        out_specs=P(),
        check_vma=False,
    )
