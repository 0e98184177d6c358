"""Mesh-sharded DBSCAN — the min-label recursion as one SPMD program.

Rows are sharded over the ``data`` axis; each device owns the propagation
state for ITS row shard and evaluates the blocked eps-neighborhood passes
of ops/dbscan.py against the full corpus (one ``all_gather`` of X at entry —
DBSCAN's working set is rows×features, so replicating the corpus trades
HBM it can afford for an embarrassingly parallel sweep; a ring variant
that streams corpus shards around ICI is the natural extension if rows×n
ever outgrows a chip). Per sweep, only the [rows] label vector crosses ICI
(``all_gather`` after each shard-local update), and one ``psum`` of the
change flag drives the replicated ``lax.while_loop`` so every device exits
on the same iteration — the SPMD discipline all mesh fits here share.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_ml_tpu.ops import dbscan as DB
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS


@lru_cache(maxsize=32)
def make_sharded_dbscan(mesh: Mesh, *, block_rows: int = 2048):
    """Compile ``run(x, w, valid, eps_sq, min_pts) -> labels``.

    ``x [rows, n]``, ``w [rows]`` (sample weights) and ``valid [rows]``
    (pad mask, pad rows 0) data-sharded; replicated [rows] int32 labels
    out, identical to the single-device ``ops.dbscan.dbscan_labels`` (the
    tests assert equality).
    """

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS), P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    def run(x_shard, w_shard, valid_shard, eps_sq, min_pts):
        me = lax.axis_index(DATA_AXIS)
        shard_rows = x_shard.shape[0]
        base = me * shard_rows
        my_valid = valid_shard.astype(bool)

        gx = lax.all_gather(x_shard, DATA_AXIS).reshape(-1, x_shard.shape[1])
        gw = lax.all_gather(
            jnp.where(my_valid, w_shard, 0.0), DATA_AXIS
        ).reshape(-1)
        rows = gx.shape[0]
        sentinel = jnp.int32(rows)
        blk = min(block_rows, shard_rows)

        local_counts = DB._blocked_rowpass(
            x_shard, gx, DB.make_count_fn(eps_sq), (0.0, gx.dtype),
            block_rows=blk, corpus={"w": gw},
        )
        local_core = (local_counts >= min_pts) & my_valid
        core = lax.all_gather(local_core, DATA_AXIS).reshape(-1)

        def donated_min(labels):
            """Shard-local rows' smallest core-neighbor label vs the FULL
            corpus — the same masked-min tile pass as the local kernel."""
            return DB._blocked_rowpass(
                x_shard,
                gx,
                DB.make_min_fn(eps_sq, sentinel),
                (sentinel, jnp.int32),
                block_rows=blk,
                corpus={"core": core.astype(jnp.int32), "labels": labels},
            )

        labels0 = jnp.where(core, jnp.arange(rows, dtype=jnp.int32), sentinel)

        def cond(carry):
            _, changed = carry
            return changed

        def body(carry):
            labels, _ = carry
            mine = lax.dynamic_slice(labels, (base,), (shard_rows,))
            my_core = lax.dynamic_slice(core, (base,), (shard_rows,))
            new_mine = jnp.where(
                my_core, jnp.minimum(mine, donated_min(labels)), mine
            )
            new = lax.all_gather(new_mine, DATA_AXIS).reshape(-1)
            for _ in range(2):  # pointer jumping on the replicated vector
                new = jnp.where(core, new[jnp.clip(new, 0, rows - 1)], new)
            changed = lax.psum(
                jnp.any(new != labels).astype(jnp.int32), DATA_AXIS
            )
            return (new, changed > 0)

        labels, _ = lax.while_loop(cond, body, (labels0, jnp.bool_(True)))

        donated = donated_min(labels)
        my_core = lax.dynamic_slice(core, (base,), (shard_rows,))
        mine = lax.dynamic_slice(labels, (base,), (shard_rows,))
        out_mine = jnp.where(
            my_core, mine, jnp.where(donated < sentinel, donated, -1)
        )
        out_mine = jnp.where(my_valid, out_mine, -1).astype(jnp.int32)
        return lax.all_gather(out_mine, DATA_AXIS).reshape(-1)

    return jax.jit(
        run,
        in_shardings=(
            NamedSharding(mesh, P(DATA_AXIS, None)),
            NamedSharding(mesh, P(DATA_AXIS)),
            NamedSharding(mesh, P(DATA_AXIS)),
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P()),
        ),
        out_shardings=NamedSharding(mesh, P()),
    )
