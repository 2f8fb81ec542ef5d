"""Decoder-only transformer (dense / MoE / MLA) with scan-over-layers.

Layer weights are stacked on a leading ``layers`` axis and consumed by
``jax.lax.scan`` — one compiled layer body regardless of depth, which
keeps dry-run HLO size and compile time flat across the 94-layer configs.
Activation rematerialization is configurable (cfg.remat in
{none, dots, full}).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import layers as L

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def _layer_params(cfg: ModelConfig, rng, abstract: bool) -> Params:
    p: Params = {"ln1": _norm(cfg, abstract), "ln2": _norm(cfg, abstract)}
    r1, r2 = (jax.random.split(rng) if rng is not None else (None, None))
    if cfg.mla_kv_lora:
        p["attn"] = L.mla_params(cfg, r1, abstract)
    else:
        p["attn"] = L.attention_params(cfg, r1, abstract)
    if cfg.moe_experts:
        p["moe"] = L.moe_params(cfg, r2, abstract)
    else:
        p["mlp"] = L.mlp_params(cfg, cfg.d_ff, r2, abstract)
    return p


def _norm(cfg: ModelConfig, abstract: bool):
    if abstract:
        return jax.ShapeDtypeStruct((cfg.d_model,), L.dt(cfg))
    return jnp.ones((cfg.d_model,), L.dt(cfg))


def _stack(cfg: ModelConfig, rng, abstract: bool, n_layers: int) -> Params:
    if abstract:
        one = _layer_params(cfg, None, True)
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n_layers,) + s.shape, s.dtype), one)
    rngs = jax.random.split(rng, n_layers)
    # one layer at a time: a layer's float32 draws are the only float32
    # temporaries, never a float32 copy of the whole stack
    return jax.lax.map(lambda r: _layer_params(cfg, r, False), rngs)


def init_params(cfg: ModelConfig, rng=None, abstract: bool = False) -> Params:
    r_emb, r_layers = (jax.random.split(rng) if rng is not None else (None, None))
    return {
        "embed": L.embed_params(cfg, r_emb, abstract),
        "layers": _stack(cfg, r_layers, abstract, cfg.num_layers),
        "ln_f": _norm(cfg, abstract),
    }


def param_pspecs(cfg: ModelConfig) -> Params:
    """Pytree of logical-axis tuples matching init_params' structure."""
    layer = {"ln1": (None,), "ln2": (None,)}
    layer["attn"] = (L.mla_specs(cfg) if cfg.mla_kv_lora
                     else L.attention_specs(cfg))
    if cfg.moe_experts:
        layer["moe"] = L.moe_specs(cfg)
    else:
        layer["mlp"] = L.mlp_specs(cfg)
    stacked = jax.tree.map(lambda sp: ("layers",) + tuple(sp), layer,
                           is_leaf=lambda x: isinstance(x, tuple))
    return {"embed": L.embed_specs(cfg), "layers": stacked, "ln_f": (None,)}


# ---------------------------------------------------------------------------
# Layer body
# ---------------------------------------------------------------------------

def _layer_body(cfg: ModelConfig, lp: Params, x: jax.Array, *,
                positions: jax.Array, impl: str,
                cache: Optional[Tuple] = None,
                cache_index=None,
                decode_kernel: Optional[bool] = None,
                chunk: bool = False
                ) -> Tuple[jax.Array, Optional[Tuple]]:
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.mla_kv_lora:
        a, new_cache = L.mla_attention(lp["attn"], h, cfg, positions=positions,
                                       cache=cache, cache_index=cache_index,
                                       impl=impl)
    else:
        a, new_cache = L.attention(lp["attn"], h, cfg, positions=positions,
                                   causal=True, cache=cache,
                                   cache_index=cache_index, impl=impl,
                                   decode_kernel=decode_kernel, chunk=chunk)
    x = x + a
    h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.moe_experts:
        m = None
        if cfg.moe_impl == "ep":
            from .moe_ep import moe_block_ep
            m = moe_block_ep(lp["moe"], h, cfg)
        if m is None:
            m = L.moe_block(lp["moe"], h, cfg)
    else:
        m = L.mlp(lp["mlp"], h, cfg)
    return x + m, new_cache


def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def backbone(params: Params, x: jax.Array, cfg: ModelConfig, *,
             positions: jax.Array, impl: str = "full") -> jax.Array:
    """Embedded input -> final hidden states (no caches)."""

    def body(carry, lp):
        out, _ = _layer_body(cfg, lp, carry, positions=positions, impl=impl)
        return out, None

    x, _ = jax.lax.scan(_remat(cfg, body), x, params["layers"])
    return L.rms_norm(x, params["ln_f"], cfg.norm_eps)


def train_loss(params: Params, batch: Dict[str, jax.Array],
               cfg: ModelConfig, *, impl: str = "full") -> jax.Array:
    tokens, labels = batch["tokens"], batch["labels"]
    x = L.embed(params["embed"], tokens, cfg)
    if "image_embeds" in batch:                     # VLM: stub ViT output
        img = batch["image_embeds"].astype(x.dtype)
        x = jnp.concatenate([img, x], axis=1)
        pad = jnp.zeros(img.shape[:2], labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
        mask_img = img.shape[1]
    else:
        mask_img = 0
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    h = backbone(params, x, cfg, positions=positions, impl=impl)
    if mask_img:
        h, labels = h[:, mask_img:], labels[:, mask_img:]
    return L.chunked_ce_loss(params["embed"], h, labels, cfg)


def cache_shapes(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    lcount, hkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    dtype = L.dt(cfg)
    if cfg.mla_kv_lora:
        return {
            "c": jax.ShapeDtypeStruct(
                (lcount, batch, max_len, cfg.mla_kv_lora), dtype),
            "r": jax.ShapeDtypeStruct(
                (lcount, batch, max_len, cfg.mla_qk_rope_dim), dtype),
        }
    return {
        "k": jax.ShapeDtypeStruct((lcount, batch, max_len, hkv, hd), dtype),
        "v": jax.ShapeDtypeStruct((lcount, batch, max_len, hkv, hd), dtype),
    }


def cache_pspecs(cfg: ModelConfig) -> Dict[str, Tuple]:
    if cfg.mla_kv_lora:
        return {"c": ("layers", "batch", "kv_seq", None),
                "r": ("layers", "batch", "kv_seq", None)}
    return {"k": ("layers", "batch", "kv_seq", "kv_heads", None),
            "v": ("layers", "batch", "kv_seq", "kv_heads", None)}


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_shapes(cfg, batch, max_len))


def kv_block_shape(cfg: ModelConfig, chunk: int) -> Tuple[int, ...]:
    """Fixed block shape for ``chunk`` cache positions: (2, chunk,
    layers, kv_heads, head_dim) — k and v stacked on the leading axis so
    one DHT block carries a whole chunk's cache state."""
    return (2, chunk, cfg.num_layers, cfg.num_kv_heads,
            cfg.resolved_head_dim)


def export_kv_block(cfg: ModelConfig, cache: Dict, row: int, off: int,
                    chunk: int):
    """Pull cache positions [off, off+chunk) of one batch row to host as
    a (2, chunk, layers, kv_heads, head_dim) numpy slab (the data
    plane's wire format)."""
    import numpy as np
    k = np.asarray(cache["k"][:, row, off:off + chunk])   # (L, c, H, D)
    v = np.asarray(cache["v"][:, row, off:off + chunk])
    return np.stack([k.transpose(1, 0, 2, 3), v.transpose(1, 0, 2, 3)])


def export_kv_block_shards(cfg: ModelConfig, cache: Dict, row: int, off: int,
                           chunk: int, shards: int):
    """Per-shard slabs for one chunk: shard ``s`` carries kv_heads
    [s*H/shards, (s+1)*H/shards) in the same (2, chunk, layers, heads,
    head_dim) wire format.  Under a TP group each head range lives on
    exactly one device, so every slice pulls only that device's bytes —
    ``np.concatenate(slabs, axis=3)`` reassembles the full slab."""
    import numpy as np
    hkv = cfg.num_kv_heads
    if shards < 1 or hkv % shards:
        raise ValueError(f"shards={shards} must divide kv_heads={hkv}")
    hl = hkv // shards
    out = []
    for s_i in range(shards):
        lo = s_i * hl
        k = np.asarray(cache["k"][:, row, off:off + chunk, lo:lo + hl])
        v = np.asarray(cache["v"][:, row, off:off + chunk, lo:lo + hl])
        out.append(np.stack([k.transpose(1, 0, 2, 3),
                             v.transpose(1, 0, 2, 3)]))
    return out


def cache_with_blocks(cfg: ModelConfig, max_len: int, blocks,
                      shardings: Optional[Dict[str, Any]] = None) -> Dict:
    """Fresh single-row cache with a contiguous run of exported slabs
    already written at positions [0, len(blocks)*chunk).

    Assembled HOST-side and shipped as one device array per k/v: a
    per-block ``.at[].set`` costs a dispatched XLA op (and a first-call
    compile) per block, which at serve-plane block sizes is as slow as
    just recomputing the chunk — this path is O(1) dispatches however
    long the imported run is.  ``shardings`` ({"k": NamedSharding, "v":
    ...}) lands each k/v directly under a TP group's layout: device_put
    splits the host slab so every device receives only its kv_heads
    slice."""
    import numpy as np
    shapes = cache_shapes(cfg, 1, max_len)
    k = np.zeros(shapes["k"].shape, shapes["k"].dtype)
    v = np.zeros(shapes["v"].shape, shapes["v"].dtype)
    if blocks:
        kk = np.concatenate([b[0] for b in blocks])   # (covered, L, H, D)
        vv = np.concatenate([b[1] for b in blocks])
        covered = kk.shape[0]
        k[:, 0, :covered] = kk.transpose(1, 0, 2, 3)
        v[:, 0, :covered] = vv.transpose(1, 0, 2, 3)
    if shardings is not None:
        return {"k": jax.device_put(k, shardings["k"]),
                "v": jax.device_put(v, shardings["v"])}
    return {"k": jnp.asarray(k), "v": jnp.asarray(v)}


def import_kv_block(cfg: ModelConfig, cache: Dict, row: int, off: int,
                    block) -> Dict:
    """Write an exported slab back into cache positions [off, off+chunk)
    of one batch row.  Bit-faithful: the imported KV is byte-identical
    to what the exporting replica computed, so decode from the merged
    cache is token-identical to never having moved."""
    chunk = block.shape[1]
    k = jnp.asarray(block[0].transpose(1, 0, 2, 3),
                    cache["k"].dtype)[:, None]            # (L, 1, c, H, D)
    v = jnp.asarray(block[1].transpose(1, 0, 2, 3), cache["v"].dtype)[:, None]
    return {"k": cache["k"].at[:, row:row + 1, off:off + chunk].set(k),
            "v": cache["v"].at[:, row:row + 1, off:off + chunk].set(v)}


def _cache_tuple(cfg, cache_l):
    return (cache_l["c"], cache_l["r"]) if cfg.mla_kv_lora \
        else (cache_l["k"], cache_l["v"])


def _cache_dict(cfg, tup):
    return ({"c": tup[0], "r": tup[1]} if cfg.mla_kv_lora
            else {"k": tup[0], "v": tup[1]})


def forward_with_cache(params: Params, tokens: jax.Array, cache: Dict,
                       cfg: ModelConfig, cache_index, *,
                       impl: str = "full",
                       decode_kernel: Optional[bool] = None,
                       image_embeds: Optional[jax.Array] = None,
                       chunk: bool = False
                       ) -> Tuple[jax.Array, Dict]:
    """Prefill (S>1) or decode (S==1): returns (last-position logits, cache).

    ``cache_index`` may be a scalar (prefill / lockstep decode) or a (B,)
    array of per-slot cache positions (continuous-batching decode: every
    row writes and attends at its own length).

    ``chunk=True`` marks a fixed-shape *continuation* prefill segment
    (scalar ``cache_index``, possibly > 0): attention spans the whole
    cache under the absolute causal mask, and ALL-position logits
    (B, S, V) are returned so the caller can select the true last prompt
    position when the segment carries right-padding.
    """
    x = L.embed(params["embed"], tokens, cfg)
    if image_embeds is not None:
        x = jnp.concatenate([image_embeds.astype(x.dtype), x], axis=1)
    s = x.shape[1]
    idx = jnp.asarray(cache_index)
    off = idx[:, None] if idx.ndim else idx
    positions = off + jnp.broadcast_to(jnp.arange(s), x.shape[:2])

    def body(carry, xs):
        lp, cl = xs
        out, new_cache = _layer_body(cfg, lp, carry, positions=positions,
                                     impl=impl, cache=_cache_tuple(cfg, cl),
                                     cache_index=idx,
                                     decode_kernel=decode_kernel,
                                     chunk=chunk)
        return out, _cache_dict(cfg, new_cache)

    x, new_caches = jax.lax.scan(body, x, (params["layers"], cache))
    if chunk:
        h = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
        return L.logits_fn(params["embed"], h, cfg), new_caches
    h = L.rms_norm(x[:, -1:], params["ln_f"], cfg.norm_eps)
    logits = L.logits_fn(params["embed"], h, cfg)[:, 0]
    return logits, new_caches
