"""Jit'd public wrapper: route key IDs to ring successor indices."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..backend import resolve_interpret
from .kernel import (ring_lookup64_pallas, ring_lookup_bucketed_pallas,
                     ring_lookup_pallas)
from .ref import (ring_lookup64_ref, ring_lookup_bucketed_ref,
                  ring_lookup_ref)


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def ring_lookup(keys: jax.Array, table: jax.Array, *,
                use_pallas: bool = True,
                interpret: Optional[bool] = None) -> jax.Array:
    """keys (Q,), sorted table (N,) -> successor indices (Q,) int32.

    ``interpret=None`` (default) autodetects: compiled on TPU,
    interpreter mode everywhere else.
    """
    if use_pallas:
        return ring_lookup_pallas(keys, table,
                                  interpret=resolve_interpret(interpret))
    return ring_lookup_ref(keys, table)


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def ring_lookup64(keys_hi: jax.Array, keys_lo: jax.Array,
                  table_hi: jax.Array, table_lo: jax.Array,
                  n: jax.Array, *,
                  use_pallas: bool = True,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Full 64-bit successor lookup on a hi/lo word-split device table.

    The table arrays are *capacity* buffers: sorted live entries in the
    first ``n`` slots (n is a (1,) int32 array, traced — membership churn
    changes only its value, so the jit cache key is the capacity and the
    kernel never recompiles until capacity doubles).  Returns successor
    indices into the live entries.
    """
    if use_pallas:
        return ring_lookup64_pallas(keys_hi, keys_lo, table_hi, table_lo, n,
                                    interpret=resolve_interpret(interpret))
    return ring_lookup64_ref(keys_hi, keys_lo, table_hi, table_lo, n)


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def ring_lookup_bucketed(keys_hi: jax.Array, keys_lo: jax.Array,
                         bkt_hi: jax.Array, bkt_lo: jax.Array,
                         occ: jax.Array, *,
                         use_pallas: bool = True,
                         interpret: Optional[bool] = None):
    """Two-level successor lookup: O(bucket-row) work per key.

    The (B, BW) bucket table and (B,) occupancy travel as data — churn
    changes only values, so the jit cache key is the directory size B
    and the kernel re-specializes only when the directory resizes (a
    capacity-doubling event), never on membership events.  Returns the
    owner id word pair ((Q,) hi, (Q,) lo) — identities, not ranks, so a
    membership batch only has to rewrite its touched rows.
    """
    if use_pallas:
        return ring_lookup_bucketed_pallas(
            keys_hi, keys_lo, bkt_hi, bkt_lo, occ,
            interpret=resolve_interpret(interpret))
    return ring_lookup_bucketed_ref(keys_hi, keys_lo, bkt_hi, bkt_lo, occ)


def _split_words(words: jax.Array):
    """(2Q,) uint32 little-endian words of (Q,) uint64 ids -> ((Q,) hi,
    (Q,) lo).  Two stride-2 slices of the 1-D buffer: no (Q, 2) array,
    whose minor dimension of 2 the TPU would pad to 128 lanes."""
    n = words.shape[0]
    return (lax.slice(words, (1,), (n,), (2,)),
            lax.slice(words, (0,), (n,), (2,)))


def _join_words(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """Inverse of ``_split_words`` on lane-dense shapes: rows of 64 words
    of each, transposed, stacked so that the pairs interleave along
    sublanes, and transposed back into (2Q,) (lo, hi) words."""
    q = lo.shape[0]
    pad = -q % 64
    lo = jnp.pad(lo, (0, pad)).reshape(-1, 64).T
    hi = jnp.pad(hi, (0, pad)).reshape(-1, 64).T
    return jnp.stack([lo, hi], axis=1).reshape(128, -1).T.reshape(-1)[:2 * q]


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def ring_lookup64_words(words: jax.Array, table_hi: jax.Array,
                        table_lo: jax.Array, n: jax.Array, *,
                        use_pallas: bool = True,
                        interpret: Optional[bool] = None) -> jax.Array:
    """``ring_lookup64`` on keys as one (2Q,) uint32 buffer: the bytes of
    a little-endian (Q,) uint64 array, split into words on the device."""
    hi, lo = _split_words(words)
    return ring_lookup64(hi, lo, table_hi, table_lo, n,
                         use_pallas=use_pallas, interpret=interpret)


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def ring_lookup_bucketed_words(words: jax.Array, bkt_hi: jax.Array,
                               bkt_lo: jax.Array, occ: jax.Array, *,
                               use_pallas: bool = True,
                               interpret: Optional[bool] = None) -> jax.Array:
    """``ring_lookup_bucketed`` with one buffer each way (DESIGN.md §3):
    keys in as the (2Q,) uint32 bytes of a little-endian (Q,) uint64
    array, owners out in the same word order, so the host uploads a view
    of its keys and views the fetched owners as uint64, with no word
    arithmetic on either side."""
    hi, lo = _split_words(words)
    ohi, olo = ring_lookup_bucketed(hi, lo, bkt_hi, bkt_lo, occ,
                                    use_pallas=use_pallas,
                                    interpret=interpret)
    return _join_words(ohi, olo)


@partial(jax.jit, donate_argnums=(0, 1, 2))
def set_bucket_rows(bkt_hi: jax.Array, bkt_lo: jax.Array, occ: jax.Array,
                    rows: jax.Array, rows_hi: jax.Array, rows_lo: jax.Array,
                    rows_occ: jax.Array):
    """Rewrite bucket rows of the device table in place: (C,) int32 row
    indices, their (C, BW) hi/lo words and (C,) occupancies.

    The three table arrays are donated, so a delta costs no copy of the
    matrix.  Callers ship a fixed chunk C per directory size, padding a
    short chunk by repeating one of its rows with that row's own data:
    duplicate indices then write identical values, and every delta of
    a directory runs one compiled program."""
    return (bkt_hi.at[rows].set(rows_hi), bkt_lo.at[rows].set(rows_lo),
            occ.at[rows].set(rows_occ))
