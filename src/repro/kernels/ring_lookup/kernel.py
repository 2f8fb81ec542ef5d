"""Pallas TPU kernel: batched consistent-hash ring lookup.

TPU adaptation of the DHT hot path (DESIGN.md §2): a binary search is
gather-heavy and serial — poison for the VPU.  Instead each program
block computes bisect_left as a *compare-and-count* reduction:

    idx(q) = sum_j [table[j] < q]

which is one broadcasted (BQ x BT) uint compare + row-sum per table tile
— pure vector lanes, no gathers, and the table tiles stream through VMEM.
For routing tables up to ~10^6 peers (the paper's largest system) the
O(N) count costs less than the lane-divergent O(log N) search on TPU.

Grid: (Q/BQ, N/BT); the table axis is the innermost (arbitrary) dim and
accumulates into the output block, which stays resident in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BQ = 1024          # queries per program (8 sublanes x 128 lanes)
BT = 2048          # table entries per tile (8 KiB of uint32 in VMEM)
# the pallas_calls' names in jaxprs and traces
NAME64 = "ring_lookup64"
NAME_BUCKETED = "ring_lookup_bucketed"


def _tiles(kernel: str, bq, bt=None, **dims):
    """Resolve (bq, bt) through the autotune cache when unset.

    Explicit arguments always win; otherwise the persisted per-backend
    winner (or the module defaults under interpret / cache miss).  Lazy
    import keeps kernels importable without the autotune package."""
    from ..autotune import tiles_for

    t = tiles_for(kernel, **dims)
    bq = int(bq) if bq else t["bq"]
    if bt is None and "bt" not in t:
        return bq
    return bq, (int(bt) if bt else t["bt"])


def _ring_lookup_kernel(q_ref, t_ref, o_ref, *, n_total: int, bt: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[...]                                  # (bq,)
    t = t_ref[...]                                  # (bt,)
    # mask table padding (last tile may exceed n_total)
    base = ti * bt
    valid = (base + jax.lax.iota(jnp.int32, bt)) < n_total
    lt = (t[None, :] < q[:, None]) & valid[None, :]
    o_ref[...] += jnp.sum(lt.astype(jnp.int32), axis=1)


def ring_lookup_pallas(keys: jax.Array, table: jax.Array, *,
                       interpret: bool = True,
                       bq: int | None = None,
                       bt: int | None = None) -> jax.Array:
    """keys: (Q,) uint32; table: (N,) sorted uint32 -> (Q,) int32."""
    q, n = keys.shape[0], table.shape[0]
    if n == 0:
        # mirror RingState.lookup's contract instead of surfacing the
        # mod-by-zero from the counts[:q] % n wraparound below
        raise LookupError("empty routing table")
    BQ, BT = _tiles("ring_lookup", bq, bt, q=q, n=n)
    qp = (q + BQ - 1) // BQ * BQ
    np_ = (n + BT - 1) // BT * BT
    keys_p = jnp.pad(keys, (0, qp - q))
    table_p = jnp.pad(table, (0, np_ - n),
                      constant_values=jnp.array(0, table.dtype))
    grid = (qp // BQ, np_ // BT)
    counts = pl.pallas_call(
        functools.partial(_ring_lookup_kernel, n_total=n, bt=BT),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BQ,), lambda qi, ti: (qi,)),
            pl.BlockSpec((BT,), lambda qi, ti: (ti,)),
        ],
        out_specs=pl.BlockSpec((BQ,), lambda qi, ti: (qi,)),
        out_shape=jax.ShapeDtypeStruct((qp,), jnp.int32),
        interpret=interpret,
    )(keys_p, table_p)
    return (counts[:q] % n).astype(jnp.int32)


def _ring_lookup64_kernel(n_ref, qhi_ref, qlo_ref, thi_ref, tlo_ref, o_ref,
                          *, bt: int):
    """Two-word (hi, lo) lexicographic compare-and-count.

    Full 64-bit ring IDs are carried as a uint32 (hi, lo) word pair
    (DESIGN.md §3): TPUs have no native uint64 lanes, and two uint32
    compares per entry keep the reduction on the VPU.  ``table < key``
    lexicographically iff  hi < qhi  or  (hi == qhi and lo < qlo).

    The live table length arrives as data (``n_ref``), not as a Python
    constant, so the jitted kernel is specialized only on the *capacity*
    (padded table shape) — membership churn never recompiles it.
    """
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    n_total = n_ref[0]
    qhi = qhi_ref[...]                              # (bq,)
    qlo = qlo_ref[...]
    thi = thi_ref[...]                              # (bt,)
    tlo = tlo_ref[...]
    base = ti * bt
    valid = (base + jax.lax.iota(jnp.int32, bt)) < n_total
    lt = (thi[None, :] < qhi[:, None]) | (
        (thi[None, :] == qhi[:, None]) & (tlo[None, :] < qlo[:, None]))
    lt = lt & valid[None, :]
    o_ref[...] += jnp.sum(lt.astype(jnp.int32), axis=1)


def ring_lookup64_pallas(keys_hi: jax.Array, keys_lo: jax.Array,
                         table_hi: jax.Array, table_lo: jax.Array,
                         n: jax.Array, *,
                         interpret: bool = True,
                         bq: int | None = None,
                         bt: int | None = None) -> jax.Array:
    """64-bit batched successor lookup over a hi/lo split table.

    keys_hi/keys_lo: (Q,) uint32 word pairs of the query IDs;
    table_hi/table_lo: (CAP,) uint32 word pairs, sorted by (hi, lo) in the
    first ``n`` slots (the rest is capacity padding, contents ignored);
    n: (1,) int32 live entry count (dynamic — no recompile on churn).
    Returns (Q,) int32 successor *indices* into the live table.
    """
    q, cap = keys_hi.shape[0], table_hi.shape[0]
    BQ, BT = _tiles("ring_lookup", bq, bt, q=q, n=cap)
    qp = (q + BQ - 1) // BQ * BQ
    capp = (cap + BT - 1) // BT * BT
    keys_hi = jnp.pad(keys_hi, (0, qp - q))
    keys_lo = jnp.pad(keys_lo, (0, qp - q))
    table_hi = jnp.pad(table_hi, (0, capp - cap))
    table_lo = jnp.pad(table_lo, (0, capp - cap))
    grid = (qp // BQ, capp // BT)
    counts = pl.pallas_call(
        functools.partial(_ring_lookup64_kernel, bt=BT),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda qi, ti: (0,)),
            pl.BlockSpec((BQ,), lambda qi, ti: (qi,)),
            pl.BlockSpec((BQ,), lambda qi, ti: (qi,)),
            pl.BlockSpec((BT,), lambda qi, ti: (ti,)),
            pl.BlockSpec((BT,), lambda qi, ti: (ti,)),
        ],
        out_specs=pl.BlockSpec((BQ,), lambda qi, ti: (qi,)),
        out_shape=jax.ShapeDtypeStruct((qp,), jnp.int32),
        interpret=interpret,
        name=NAME64,
    )(n.astype(jnp.int32), keys_hi, keys_lo, table_hi, table_lo)
    return (counts[:q] % n[0]).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Two-level bucketized lookup (DESIGN.md §7)
# ---------------------------------------------------------------------------
#
# The flat kernels above compare every query against every table tile:
# O(N) per key, which collapses at million-peer scale.  The bucketized
# kernel bounds each query to ONE row of a radix-partitioned table:
#
#   bucket(id) = top R bits of the 64-bit id  (R = log2(rows))
#   rows (B, BW): row b holds the sorted active ids whose top bits are b
#                 in its first occ[b] slots; every slot >= occ[b] holds
#                 the bucket's SUCCESSOR id (the first active id past the
#                 bucket's range, wrapping to the ring origin), so
#
#   owner(q) = row[bucket(q)][ #\{j < occ : row[j] < q\} ]
#
# with no branch: an in-bucket successor lands on a live slot, an
# overshoot (q greater than everything in its bucket) lands on the
# successor padding.  occ must stay < BW (one pad slot reserved) —
# RingState falls back to the flat kernel when a bucket overflows.
#
# The kernel returns owner IDENTITIES (hi, lo), not global indices:
# ranks would need a prefix-sum directory whose entries all shift on any
# churn, while identities keep the device update O(touched buckets).

BW = 128           # bucket row width (one VPU lane row of uint32)


def _ring_lookup_bucketed_kernel(qhi_ref, qlo_ref, rhi_ref, rlo_ref,
                                 occ_ref, ohi_ref, olo_ref):
    """Per query block: one (BQ, BW) compare-and-count over each query's
    own bucket row, then a one-hot select of the counted slot.

    The rows arrive already gathered (one per query, by the wrapper), so
    the body is pure VPU lane work: no in-kernel gather, no
    take_along_axis.  Queries and occupancies are (BQ, 1) columns that
    broadcast along the row's lanes."""
    qhi = qhi_ref[...]                               # (BQ, 1) uint32
    qlo = qlo_ref[...]
    rhi = rhi_ref[...]                               # (BQ, BW) uint32
    rlo = rlo_ref[...]
    j = jax.lax.broadcasted_iota(jnp.int32, rhi.shape, 1)
    lt = (rhi < qhi) | ((rhi == qhi) & (rlo < qlo))
    cnt = jnp.sum((lt & (j < occ_ref[...])).astype(jnp.int32), axis=1,
                  keepdims=True)                     # (BQ, 1)
    pick = j == cnt

    def select(rows):
        # exactly one lane survives, so an int32 sum is an exact select
        words = jax.lax.bitcast_convert_type(
            jnp.where(pick, rows, jnp.uint32(0)), jnp.int32)
        return jax.lax.bitcast_convert_type(
            jnp.sum(words, axis=1, keepdims=True), jnp.uint32)

    ohi_ref[...] = select(rhi)
    olo_ref[...] = select(rlo)


def ring_lookup_bucketed_pallas(keys_hi: jax.Array, keys_lo: jax.Array,
                                bkt_hi: jax.Array, bkt_lo: jax.Array,
                                occ: jax.Array, *,
                                interpret: bool = True,
                                bq: int | None = None):
    """Bucketized 64-bit successor lookup: O(BW) work per key.

    keys_hi/keys_lo: (Q,) uint32 query word pairs; bkt_hi/bkt_lo:
    (B, BW) uint32 bucket rows (B a power of two — the radix directory);
    occ: (B,) int32 live occupancy per row (< BW; the slack slots carry
    the bucket successor id).  Occupancy and row contents travel as
    data, so churn re-specializes nothing — only a directory resize
    (capacity doubling) changes the shapes.  Returns ((Q,) hi, (Q,) lo)
    owner id words.
    """
    q = keys_hi.shape[0]
    nb = bkt_hi.shape[0]
    r = nb.bit_length() - 1
    if nb != 1 << r:
        raise ValueError(f"bucket count {nb} is not a power of two")
    # BW is a data-layout constant shared with RingState._BUCKET_ROW, not
    # a tunable — only the query block size goes through the autotuner.
    BQ = _tiles("ring_lookup_bucketed", bq, q=q, b=nb)
    qp = (q + BQ - 1) // BQ * BQ
    keys_hi = jnp.pad(keys_hi, (0, qp - q))
    keys_lo = jnp.pad(keys_lo, (0, qp - q))
    # top-R radix of each key; R = 0 (one bucket) would shift by 32
    b = jax.lax.shift_right_logical(keys_hi, jnp.uint32(32 - r)).astype(
        jnp.int32) if r else jnp.zeros((qp,), jnp.int32)
    # the row gather runs in XLA over the matrix in device memory; the
    # kernel then streams one (BQ, BW) row block per query block
    rows_hi = jnp.take(bkt_hi, b, axis=0)            # (qp, BW)
    rows_lo = jnp.take(bkt_lo, b, axis=0)
    occ_q = jnp.take(occ, b)[:, None]                # (qp, 1)
    col = pl.BlockSpec((BQ, 1), lambda qi: (qi, 0))
    row = pl.BlockSpec((BQ, BW), lambda qi: (qi, 0))
    out_hi, out_lo = pl.pallas_call(
        _ring_lookup_bucketed_kernel,
        grid=(qp // BQ,),
        in_specs=[col, col, row, row, col],
        out_specs=[col, col],
        out_shape=[
            jax.ShapeDtypeStruct((qp, 1), jnp.uint32),
            jax.ShapeDtypeStruct((qp, 1), jnp.uint32),
        ],
        interpret=interpret,
        name=NAME_BUCKETED,
    )(keys_hi[:, None], keys_lo[:, None], rows_hi, rows_lo, occ_q)
    return out_hi[:q, 0], out_lo[:q, 0]
