"""Pallas TPU kernel: batched single-token decode attention.

Decode is HBM-bandwidth-bound: each new token must stream the whole KV
cache once.  The kernel tiles the cache along the sequence axis (grid
axis 1, sequential) and keeps every kv head's query group — (g, hd),
g = H/Hkv query heads — plus the online-softmax state in VMEM, so the
cache is read EXACTLY once per step and no (B,H,S) score tensor ever
reaches HBM.

Grid: (B, S/BS).  The cache is viewed as (B, S, Hkv*hd) — a free
reshape of its (B, S, Hkv, hd) layout — so one kv block (1, BS, Hkv*hd)
is a contiguous run of cache rows covering ALL kv heads, and its last
two dims are a legal TPU tile.  Per head the score tile is
(g, hd) x (BS, hd), MXU-shaped when g is a multiple of 8 and BS = 128/256.
The per-row lengths arrive by scalar prefetch into SMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BS = 256
NEG = -1e30
NAME = "decode_attention"   # the pallas_call's name in jaxprs and traces


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, s_blocks: int, scale: float,
                   bs: int, hkv: int, hd: int):
    bi, si = pl.program_id(0), pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = si * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    valid = pos < len_ref[bi]                       # (1, bs)
    for h in range(hkv):                            # static: Hkv is small
        q = q_ref[0, h].astype(jnp.float32)         # (g, hd)
        k = k_ref[0, :, h * hd:(h + 1) * hd].astype(jnp.float32)  # (bs, hd)
        v = v_ref[0, :, h * hd:(h + 1) * hd].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG)                # (g, bs)
        m_prev = m_scr[h]                           # (g, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[h] = l_scr[h] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[h] = m_new

    @pl.when(si == s_blocks - 1)
    def _done():
        for h in range(hkv):
            o_ref[0, h] = (acc_scr[h] / jnp.maximum(l_scr[h], 1e-30)
                           ).astype(o_ref.dtype)


def decode_attention_pallas(q: jax.Array, k_cache: jax.Array,
                            v_cache: jax.Array, length: jax.Array, *,
                            interpret: bool = True,
                            bs: int | None = None) -> jax.Array:
    """q: (B,H,hd); caches: (B,S,Hkv,hd); length: (B,) -> (B,H,hd)."""
    b, h, hd = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    if bs is None:
        from ..autotune import tiles_for

        bs = tiles_for("decode_attention", b=b, s=s)["bs"]
    BS = int(bs) if s % int(bs) == 0 else globals()["BS"]
    assert s % BS == 0, "pad cache length to a BS multiple"
    qg = q.reshape(b, hkv, g, hd)
    kf = k_cache.reshape(b, s, hkv * hd)
    vf = v_cache.reshape(b, s, hkv * hd)
    kv_spec = pl.BlockSpec((1, BS, hkv * hd), lambda bi, si, ln: (bi, si, 0))
    qo_spec = pl.BlockSpec((1, hkv, g, hd), lambda bi, si, ln: (bi, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, s_blocks=s // BS,
                          scale=1.0 / math.sqrt(hd), bs=BS, hkv=hkv, hd=hd),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, s // BS),
            in_specs=[qo_spec, kv_spec, kv_spec],
            out_specs=qo_spec,
            scratch_shapes=[
                pltpu.VMEM((hkv, g, 1), jnp.float32),
                pltpu.VMEM((hkv, g, 1), jnp.float32),
                pltpu.VMEM((hkv, g, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), q.dtype),
        interpret=interpret,
        name=NAME,
    )(length.astype(jnp.int32), qg, kf, vf)
    return out.reshape(b, h, hd)
