"""Pallas TPU kernel: batched EDRA dissemination-tree evaluation.

The vectorized churn plane needs, for millions of (event, observer)
pairs, the observer's acknowledge time plus its tree coordinates (TTL,
depth, parent, Rule-8 fan-out).  Materializing the (E, n) event-by-peer
matrix of ``jax_sim._simulate_core`` dies at n = 10^6 (E*n ~ 10^11), so
this kernel walks each pair's *ancestor chain* instead: the path from
the reporter to offset i visits the prefixes of i's set bits (high to
low), which is at most ``levels`` = ceil(log2 n) hops of pure uint32
bit-twiddling + float32 arithmetic per pair — no gathers, no
cross-pair communication, O(P * log n) total work.

Interval phases and link delays come from counter-based hashes (see
ref.tree_math), so the kernel needs NO (n,)-sized side table: every
block is self-contained and the grid is embarrassingly parallel over
pair blocks.  The math lives in ref.tree_math and is shared verbatim
with the numpy reference — the kernel body just runs it on jnp block
refs, keeping kernel == oracle by construction (modulo libm ulps in
log/ceil).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import tree_math

BP = 2048          # pairs per program (16 sublanes x 128 lanes of uint32)
NAME = "edra_tree"   # the pallas_call's name in jaxprs and traces


def _edra_tree_kernel(off_ref, n_ref, rep_ref, t0_ref, key_ref,
                      ack_ref, ttl_ref, depth_ref, par_ref, sends_ref, *,
                      levels: int, theta: float, delta_avg: float,
                      seed: int, fill_rate: float, e_cap: float):
    ack, ttl, depth, parent, sends = tree_math(
        jnp, off_ref[...], n_ref[...], rep_ref[...], t0_ref[...],
        key_ref[...], levels=levels, theta=theta, delta_avg=delta_avg,
        seed=seed, fill_rate=fill_rate, e_cap=e_cap)
    ack_ref[...] = ack
    ttl_ref[...] = ttl
    depth_ref[...] = depth
    par_ref[...] = parent
    sends_ref[...] = sends


def edra_tree_pallas(offset: jax.Array, n: jax.Array, reporter: jax.Array,
                     t_detect: jax.Array, event_key: jax.Array, *,
                     levels: int, theta: float, delta_avg: float,
                     seed: int = 0, fill_rate: float = 0.0,
                     e_cap: float = 2.0, interpret: bool = True,
                     bp: int | None = None):
    """offset/n/reporter/event_key: (P,) uint32; t_detect: (P,) float32.

    Returns (ack f32, ttl i32, depth i32, parent u32, sends i32), each
    (P,).  ``theta`` and ``delta_avg`` specialize the trace — one
    compile per operating point, never per event batch.
    """
    p = offset.shape[0]
    if bp is None:
        from ..autotune import tiles_for

        bp = tiles_for("edra_tree", p=p)["bp"]
    BP = int(bp)
    pp = (p + BP - 1) // BP * BP
    pad = pp - p
    offset = jnp.pad(offset, (0, pad))
    # pad n with 1 (never 0: the chain walk reduces indices mod n)
    n = jnp.pad(n, (0, pad), constant_values=jnp.uint32(1))
    reporter = jnp.pad(reporter, (0, pad))
    t_detect = jnp.pad(t_detect, (0, pad))
    event_key = jnp.pad(event_key, (0, pad))
    spec = pl.BlockSpec((BP,), lambda i: (i,))
    ack, ttl, depth, parent, sends = pl.pallas_call(
        functools.partial(_edra_tree_kernel, levels=levels, theta=theta,
                          delta_avg=delta_avg, seed=seed,
                          fill_rate=fill_rate, e_cap=e_cap),
        grid=(pp // BP,),
        in_specs=[spec] * 5,
        out_specs=[spec] * 5,
        out_shape=[
            jax.ShapeDtypeStruct((pp,), jnp.float32),
            jax.ShapeDtypeStruct((pp,), jnp.int32),
            jax.ShapeDtypeStruct((pp,), jnp.int32),
            jax.ShapeDtypeStruct((pp,), jnp.uint32),
            jax.ShapeDtypeStruct((pp,), jnp.int32),
        ],
        interpret=interpret,
        name=NAME,
    )(offset, n, reporter, t_detect, event_key)
    return ack[:p], ttl[:p], depth[:p], parent[:p], sends[:p]
