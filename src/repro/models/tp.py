"""Tensor-parallel replica groups: one ring node = a device sub-mesh.

A ``TPReplicaGroup`` runs an unmodified transformer under ``shard_map``
on a 1-D ("model",) sub-mesh from ``launch.mesh.replica_groups``.  The
sharding map is mesh-transformer-jax style:

  * column-parallel: wq/wk/wv (heads), mlp w1/w3 (ff), expert w1/w3
    (moe_ff), lm_head (vocab), embedding rows (vocab);
  * row-parallel:    wo (heads), mlp w2 (ff), expert w2 (moe_ff) — each
    followed by ONE psum (the ``psum_tp`` hooks in ``models.layers``);
  * KV cache:        k/v sharded on kv_heads, so per-device cache bytes
    drop 1/TP (MLA's compressed c/r caches replicate; only its heads
    shard);
  * MoE:             experts replicate (the router must pick identical
    slots on every device) while the expert ff dim shards — the
    ``TP_RULES`` overrides below.

The trick that keeps the model code unmodified: inside the shard_map
body every array is already the LOCAL shard, so the group calls the
model with a cfg whose head counts are divided by tp — the same
forward code then "just works" on local shapes, and ``tp_context``
activates the psum/axis-index hooks (and turns interior ``shard()``
constraints into no-ops).  Because weight shards are exact row/column
partitions and psum reduces in a deterministic order, decode tokens
are identical to single-device execution.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding import specs as sh
from . import transformer
from .model import Model

# Logical-rule overrides for the 1-axis ("model") group mesh; every
# other DEFAULT_RULES entry resolves naturally (heads/kv_heads/ff/vocab
# -> "model"; batch/embed/moe_embed reference only absent axes and
# filter to replicated).
TP_RULES: Dict[str, Any] = {"experts": None, "moe_ff": "model",
                            "moe_embed": None}

_TP_FAMILIES = ("dense", "moe")


def validate_tp(cfg, tp: int) -> None:
    """Reject configs a ``tp``-way group cannot shard exactly.  Partial
    shards would silently change math; every sharded dim must divide."""
    if tp < 1:
        raise ValueError(f"tp={tp} must be >= 1")
    if cfg.family not in _TP_FAMILIES:
        raise ValueError(
            f"tensor parallelism covers the transformer families "
            f"{_TP_FAMILIES}, not family={cfg.family!r}")

    def div(name: str, val: int) -> None:
        if val % tp:
            raise ValueError(
                f"tp={tp} must divide cfg.{name}={val} exactly "
                f"(a partial shard would change the math)")

    div("num_heads", cfg.num_heads)
    div("vocab", cfg.vocab)
    if not cfg.mla_kv_lora:
        div("num_kv_heads", cfg.num_kv_heads)
    if cfg.moe_experts:
        div("moe_d_ff", cfg.moe_d_ff)
    else:
        div("d_ff", cfg.d_ff)


class TPReplicaGroup:
    """Compiled TP execution plane for one replica group (sub-mesh).

    Owns the resolved param/cache shardings and the jitted shard_map
    programs (prefill, chunked prefill, full-slab decode, bucketized
    slot decode) for ``model`` on ``mesh``.  ``ServeCluster`` keeps one
    instance per group index, so a replica restarted onto the same
    group reuses every compiled executable.
    """

    def __init__(self, model: Model, mesh: Mesh, *, axis: str = "model"):
        if len(mesh.axis_names) != 1 or mesh.axis_names[0] != axis:
            raise ValueError(
                f"replica group mesh must be 1-D over ({axis!r},), got "
                f"{mesh.axis_names}")
        self.model = model
        self.mesh = mesh
        self.axis = axis
        self.tp = mesh.devices.size
        cfg = model.cfg
        validate_tp(cfg, self.tp)
        over: Dict[str, Any] = {
            "num_heads": cfg.num_heads // self.tp,
            # pin head_dim: the default derives it from d_model/num_heads,
            # which would silently grow under the local head count
            "head_dim": cfg.resolved_head_dim,
        }
        if not cfg.mla_kv_lora:
            over["num_kv_heads"] = cfg.num_kv_heads // self.tp
        self.local_model = dataclasses.replace(
            model, cfg=cfg.with_overrides(**over))

        def is_tup(x):
            return isinstance(x, tuple)

        with sh.mesh_context(mesh, TP_RULES):
            self._param_specs = jax.tree.map(
                lambda t: sh.logical_spec(*t), model.param_pspecs(),
                is_leaf=is_tup)
            self._param_shardings = jax.tree.map(
                lambda t: NamedSharding(mesh, sh.logical_spec(*t)),
                model.param_pspecs(), is_leaf=is_tup)
            self._cache_specs = {
                k: sh.logical_spec(*t)
                for k, t in model.cache_pspecs().items()}
        self._cache_shardings = {
            k: NamedSharding(mesh, s) for k, s in self._cache_specs.items()}
        self._fns: Optional[Tuple] = None

    # -- parameters / cache ---------------------------------------------------
    def shard_params(self, params):
        """Lay global params out over the group: each device receives
        only its row/column shard of every weight."""
        return jax.tree.map(
            lambda x, s: jax.device_put(x, s), params,
            self._param_shardings)

    def init_cache(self, batch: int, max_len: int):
        shapes = self.model.cache_shapes(batch, max_len)
        return {
            k: jax.device_put(jnp.zeros(s.shape, s.dtype),
                              self._cache_shardings[k])
            for k, s in shapes.items()}

    def cache_with_blocks(self, max_len: int, blocks):
        """Host slab run -> fresh 1-row cache landed straight under the
        group's kv_heads sharding (each device gets only its slice)."""
        return transformer.cache_with_blocks(
            self.model.cfg, max_len, blocks, shardings=self._cache_shardings)

    def export_kv_block(self, cache, row: int, off: int, chunk: int):
        """Full (shard-concatenated) slab — the prefix cache's
        content-addressed format, importable by any tp degree."""
        return transformer.export_kv_block(self.model.cfg, cache, row, off,
                                           chunk)

    def export_kv_shards(self, cache, row: int, off: int,
                         chunk: int) -> List[np.ndarray]:
        """Per-device slabs (shard s = kv_heads slice held by device s) —
        the per-shard KVB1 handoff wire format."""
        return transformer.export_kv_block_shards(
            self.model.cfg, cache, row, off, chunk, self.tp)

    def per_device_cache_bytes(self, cache) -> int:
        """Bytes one device holds for ``cache`` (1/TP of the global
        cache for sharded k/v) — asserted by the tp bench/tests."""
        return sum(leaf.addressable_shards[0].data.nbytes
                   for leaf in jax.tree.leaves(cache))

    def device_ids(self) -> List[int]:
        return [d.id for d in self.mesh.devices.reshape(-1)]

    # -- compiled programs ----------------------------------------------------
    def fns(self) -> Tuple:
        """(prefill, decode_full, decode_slots, prefill_chunk) — the
        shard_map analogues of ``serve.server._jitted``'s unfused
        programs, built once per group."""
        if self._fns is None:
            self._fns = self._build_fns()
        return self._fns

    def _build_fns(self) -> Tuple:
        lm = self.local_model
        axis, mesh = self.axis, self.mesh
        pP, cP = self._param_specs, self._cache_specs
        logit1 = P(None, axis)          # (B, V): logits stay vocab-sharded
        logit2 = P(None, None, axis)    # (B, S, V) all-position chunk logits

        def rep(n: int) -> P:
            return P(*([None] * n))

        def wrap(f, in_specs, out_specs):
            def inner(*args):
                with sh.tp_context(axis):
                    return f(*args)
            return jax.jit(jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                                         out_specs=out_specs,
                                         check_vma=False))

        prefill = wrap(lambda p, b, c: lm.prefill(p, b, c),
                       (pP, {"tokens": rep(2)}, cP), (logit1, cP))
        prefill_chunk = None
        if lm.supports_chunked_prefill:
            prefill_chunk = wrap(
                lambda p, t, c, i: lm.prefill_chunk(p, t, c, i),
                (pP, rep(2), cP, P()), (logit2, cP))
        decode_full = wrap(
            lambda p, c, t, n: lm.decode_step(p, c, t, n),
            (pP, cP, rep(2), rep(1)), (logit1, cP))

        def slots_body(p, c, t, n, idx):
            # mirrors _jitted.decode_slots exactly (bit-identical decode):
            # gather padded bucket rows, step them, scatter fresh KV back
            sub = jax.tree.map(
                lambda x: jnp.take(x, idx, axis=1, mode="fill",
                                   fill_value=0), c)
            tok = jnp.take(t, idx, axis=0, mode="fill", fill_value=0)
            ln = jnp.take(n, idx, axis=0, mode="fill", fill_value=0)
            logits, new_sub = lm.decode_step(p, sub, tok, ln)
            out = jax.tree.map(
                lambda x, s: x.at[:, idx].set(s, mode="drop"), c, new_sub)
            return logits, out

        decode_slots = wrap(slots_body,
                            (pP, cP, rep(2), rep(1), rep(1)), (logit1, cP))
        return prefill, decode_full, decode_slots, prefill_chunk
