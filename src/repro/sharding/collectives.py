"""EDRA dissemination tree as TPU collectives (DESIGN.md §2, level 2).

EDRA's rules map 1:1 onto a recursive-doubling schedule over an ICI ring:

  Rule 7  (send M(l) to succ(p, 2^l))      -> lax.ppermute shift by 2^l
  Rule 3  (aggregate everything acked)     -> concatenate accumulated blocks
  Rule 8  (discharge past the reporter)    -> stop at axis size (log2 n rounds)
  Theorem 1 (exactly-once, log time)       -> each block moves exactly once
                                              per round, rho = log2(n) rounds

``edra_allgather`` is therefore a *faithful* translation of the paper's
event-dissemination pattern into jax.lax collectives — each round ships
the peer's entire "acknowledged" set one power-of-two hop clockwise —
and doubles as an alternative data-parallel gradient-sync path
(reduce-scatter + edra tree) selectable in the trainer.

``edra_broadcast`` is the single-event special case (Figure 1 of the
paper): the reporter's block reaches all n peers in log2(n) rounds.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _rounds(n: int) -> int:
    r = int(math.log2(n))
    if 2 ** r != n:
        raise ValueError(f"EDRA collective needs a power-of-two axis, got {n}")
    return r


def edra_allgather(x: jax.Array, axis_name: str) -> jax.Array:
    """All-gather along ``axis_name`` via the EDRA tree.

    Inside shard_map: x is the local block; returns (n, *x.shape) stacked
    in ring order (block j = peer j's shard).
    """
    n = jax.lax.axis_size(axis_name)
    rho = _rounds(n)
    idx = jax.lax.axis_index(axis_name)
    buf = x[None]                                   # blocks [i]
    for l in range(rho):
        m = 1 << l
        # Rule 7: every peer ships its acknowledged set to succ(p, 2^l);
        # equivalently each receives from pred(p, 2^l).
        perm = [(i, (i + m) % n) for i in range(n)]
        recv = jax.lax.ppermute(buf, axis_name, perm)
        # Rule 3 aggregation: prepend the predecessor's older blocks
        buf = jnp.concatenate([recv, buf], axis=0)
    # buf[j] = block of peer (i - n + 1 + j) mod n; rotate to canonical order
    return jnp.roll(buf, shift=idx + 1, axis=0)


def edra_broadcast(x: jax.Array, axis_name: str, source: int = 0) -> jax.Array:
    """Figure-1 dissemination: the reporter's block reaches all peers in
    log2(n) rounds; peers outside the frontier forward zeros that are
    overwritten on receipt (static schedule, exactly-once per Theorem 1).
    """
    n = jax.lax.axis_size(axis_name)
    rho = _rounds(n)
    idx = jax.lax.axis_index(axis_name)
    off = (idx - source) % n                        # offset from reporter
    have = off == 0
    val = jnp.where(have, x, jnp.zeros_like(x))
    for l in range(rho):
        m = 1 << l
        perm = [((source + i) % n, (source + i + m) % n) for i in range(m)]
        recv = jax.lax.ppermute(val, axis_name, perm)
        gets = (off >= m) & (off < 2 * m)
        val = jnp.where(gets, recv, val)
    return val


def edra_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """DP all-reduce: native reduce-scatter (the reduction half has no
    analogue in the paper) + EDRA-tree all-gather for dissemination."""
    n = jax.lax.axis_size(axis_name)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    shard = jax.lax.psum_scatter(flat.reshape(n, -1), axis_name,
                                 scatter_dimension=0, tiled=False)
    full = edra_allgather(shard, axis_name).reshape(-1)
    if pad:
        full = full[:-pad]
    return full.reshape(x.shape)


def make_edra_allreduce(mesh: Mesh, axis_name: str = "data"):
    """shard_map-wrapped pytree all-reduce over one mesh axis, usable as a
    drop-in gradient synchronizer."""
    other = tuple(a for a in mesh.axis_names if a != axis_name)

    def tree_allreduce(tree):
        def one(g):
            fn = jax.shard_map(
                partial(edra_allreduce, axis_name=axis_name),
                mesh=mesh,
                in_specs=P(*(None for _ in g.shape)),
                out_specs=P(*(None for _ in g.shape)),
                check_vma=False,
            )
            return fn(g)
        return jax.tree.map(one, tree)

    del other
    return tree_allreduce
