"""Batched serving with D1HT session routing.

Requests carry a session id; the D1HT ring (full routing table, single
local lookup) decides which serving replica owns the session's KV cache.
The Pallas ``ring_lookup`` kernel resolves whole request batches
on-device.  Each replica runs continuous batched decode over its slots:
slot state lives in flat per-slot arrays and every active slot decodes at
its OWN cache position in one jitted call (per-slot lengths flow through
``decode_attention``'s masking), so mixed-length sessions never attend
past their real length and a long session never gates short ones.

Quarantined replicas (spot nodes inside T_q) take no sessions but may
proxy requests — the paper's gateway mechanism (§V); see
``repro.serve.cluster.ServeCluster`` for the churn-aware orchestration
(migration on leave/quarantine, generation-driven restarts).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from functools import lru_cache
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import spans
from repro.core.ring import hash_id
from repro.core.ringstate import RingState
from repro.models import Model
from repro.runtime import Membership


@dataclass
class Request:
    session_id: str
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 16


class SessionRouter:
    """Batched session -> replica resolution over the ring.

    Routes from the Membership's shared ``RingState``: the sorted table
    lives on-device as capacity-padded uint32 (hi, lo) word pairs and is
    re-uploaded only when a membership event bumps the state version —
    never per request batch — and lookups compare full 64-bit IDs (the
    old path truncated to the top 32 bits, which collides at scale).
    """

    def __init__(self, membership: Membership):
        self.membership = membership
        # no event subscription needed: the device table refreshes
        # lazily off the shared state's version
        self.state: RingState = membership.ring_state

    @property
    def uploads(self) -> int:
        """Device-table uploads so far (1 per membership version actually
        routed against — asserted by the serve acceptance test)."""
        return self.state.upload_count

    def route(self, session_ids: List[str]) -> List[int]:
        keys = np.fromiter(
            (session_key(s) for s in session_ids),
            np.uint64, len(session_ids))
        return [int(p) for p in self.state.lookup(keys)]


def session_key(session_id: str) -> int:
    """Ring key of a session (shared by router, placement and cluster)."""
    return hash_id(f"session/{session_id}")


def _decode_bucket(active: int, slots: int) -> int:
    """Pad an active-slot count to the next power of two (capped at the
    slot count): decode batches only ever take log2(slots)+1 distinct
    shapes, so churn in the number of live sessions can never trigger a
    fresh trace per count."""
    b = 1
    while b < active:
        b *= 2
    return min(b, slots)


@lru_cache(maxsize=32)
def _jitted(model: Model) -> Tuple:
    """One jitted (prefill, decode_slots) pair per Model value, shared by
    every replica of that model — a migrated-to replica reuses the
    donor's compiled executables instead of re-tracing (Model is a
    frozen dataclass, so value-equal models hit the same cache line).

    ``decode_slots`` is the bucketized decode round: it gathers the
    (padded) active-slot rows out of the full slab, steps ONLY those
    rows through the model, and scatters the fresh KV back — so round
    cost scales with the active bucket, not the slab width, and the
    out-of-range padding index is dropped on the way back (padded rows
    never corrupt the slab).  ``decode_full`` is the full-house variant
    (bucket == slab width): the gather would be the identity, so it
    steps the slab in place and skips the scatter copy.

    The ``*_fused`` variants additionally run the device-resident
    bucketized ring lookup on the batch's session keys INSIDE the same
    program (the inner jitted wrapper inlines): one decode round =
    route + gather + decode in a single dispatch, returning the
    (hi, lo) owner words next to the tokens.  ``prefill_chunk`` is the
    fixed-shape continuation prefill segment (chunked prefill — every
    chunk of every admit shares one trace), or None for families
    without a chunk path.

    Every decode variant returns the (B,) int32 GREEDY TOKENS, not the
    (B, V) logits: the argmax rides inside the compiled program, so the
    per-round host transfer is B int32 words instead of a full f32
    logits slab (repro-lint RL003 — the readback was the decode loop's
    hidden host sync).  Tensor-parallel groups keep returning logits
    from ``TPReplicaGroup.fns`` (the head stays vocab-sharded there, so
    the argmax needs the global array — see ``decode_round``)."""
    from repro.kernels.ring_lookup.ops import ring_lookup_bucketed

    prefill = jax.jit(model.prefill)

    def _pick(logits):
        with jax.named_scope("pick"):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _step(params, cache, tokens, lengths):
        # per-slot cache positions for transformer families; lockstep
        # max-length for the rest (inactive/padding rows are length 0,
        # so they never raise the lockstep position)
        with jax.named_scope("decode_step"):
            index = lengths if model.supports_per_slot_decode \
                else jnp.max(lengths)
            return model.decode_step(params, cache, tokens, index)

    def _gather(idx, cache, *rows):
        # the active slots of the cache slab (slot axis 1) and of each
        # per-slot array; padding indices read zeros
        with jax.named_scope("slot_gather"):
            sub = jax.tree.map(lambda c: jnp.take(
                c, idx, axis=1, mode="fill", fill_value=0), cache)
            return (sub,) + tuple(jnp.take(r, idx, axis=0, mode="fill",
                                           fill_value=0) for r in rows)

    def _scatter(cache, idx, new_sub):
        with jax.named_scope("kv_scatter"):
            return jax.tree.map(
                lambda c, s: c.at[:, idx].set(s, mode="drop"), cache,
                new_sub)

    def _route(khi, klo, bhi, blo, occ):
        with jax.named_scope("route"):
            return ring_lookup_bucketed(khi, klo, bhi, blo, occ)

    @jax.jit
    def decode_full(params, cache, tokens, lengths):
        logits, new_cache = _step(params, cache, tokens, lengths)
        return _pick(logits), new_cache

    @jax.jit
    def decode_slots(params, cache, tokens, lengths, idx):
        sub, tok, ln = _gather(idx, cache, tokens, lengths)
        logits, new_sub = _step(params, sub, tok, ln)
        return _pick(logits), _scatter(cache, idx, new_sub)

    @jax.jit
    def decode_full_fused(params, cache, tokens, lengths,
                          khi, klo, bhi, blo, occ):
        ohi, olo = _route(khi, klo, bhi, blo, occ)
        logits, new_cache = _step(params, cache, tokens, lengths)
        return _pick(logits), new_cache, ohi, olo

    @jax.jit
    def decode_slots_fused(params, cache, tokens, lengths, idx,
                           khi, klo, bhi, blo, occ):
        sub, tok, ln, qhi, qlo = _gather(idx, cache, tokens, lengths,
                                         khi, klo)
        ohi, olo = _route(qhi, qlo, bhi, blo, occ)
        logits, new_sub = _step(params, sub, tok, ln)
        return _pick(logits), _scatter(cache, idx, new_sub), ohi, olo

    prefill_chunk = jax.jit(model.prefill_chunk) \
        if model.supports_chunked_prefill else None

    return (prefill, decode_full, decode_slots,
            decode_full_fused, decode_slots_fused, prefill_chunk)


class Replica:
    """One serving replica: a vectorized slab of continuous-batching
    decode slots.

    Slot bookkeeping is flat per-slot arrays (``lengths``, ``tokens``,
    ``active``) plus an O(1) free-list — no dict scans (the old admit
    path re-scanned ``sessions.values()`` per admission: O(slots²)).
    ``decode_round`` compacts the active slots into a power-of-two
    bucketized batch and steps only those rows in a single jitted call,
    each at its own cache position (the gathered lengths are the
    per-row cache index, so each slot writes its fresh KV at its own
    length and masks attention there).  The old engine stepped the full
    slab every round — a single straggler session cost as much as a
    full house — and each distinct decode shape risked a fresh trace.
    """

    def __init__(self, model: Model, *, slots: int, max_len: int,
                 generation: int = 0, prefill_chunk: Optional[int] = None,
                 prefix_cache=None, group=None):
        self.model = model
        self.slots = slots
        self.max_len = max_len
        self.generation = generation     # membership generation at creation
        # tensor-parallel replica group (models.tp.TPReplicaGroup) or
        # None for a single-device replica.  With a group, the compiled
        # programs come from the group (shard_map over its sub-mesh), the
        # cache is kv_heads-sharded across its devices, and the fused
        # route→decode variants are skipped (the ring lookup stays
        # host-side for groups).
        self.group = group
        # content-addressed cross-session prompt-prefix cache
        # (repro.dht.data.PrefixCache or None): chunked prefills consult
        # it before computing a chunk and insert what they computed
        self.prefix_cache = prefix_cache \
            if model.supports_kv_blocks else None
        # wall time the last admit_from_blocks spent importing blocks
        # (the cluster splits handoff-transfer from re-prefill with it)
        self.import_us = 0.0
        self.cache = self._init_cache(slots, max_len)
        self.lengths = np.zeros((slots,), np.int32)
        self.tokens = np.zeros((slots, 1), np.int32)
        self.active = np.zeros((slots,), bool)
        # per-slot session ring-key words for the fused route→decode round
        self.key_hi = np.zeros((slots,), np.uint32)
        self.key_lo = np.zeros((slots,), np.uint32)
        self.sessions: Dict[str, int] = {}
        self._free = list(range(slots - 1, -1, -1))   # pop() -> slot 0 first
        # chunked prefill: fixed segment length (None = whole-prompt
        # prefill; ignored for families without a chunk path)
        self.prefill_chunk = prefill_chunk \
            if model.supports_chunked_prefill else None
        # in-flight overlapped prefills: sid -> progress state (slot is
        # reserved but the session is NOT in ``sessions`` until complete,
        # so decode_round never sees a half-filled slot)
        self._pending: Dict[str, dict] = {}
        # owners resolved by the last *fused* decode round: sid -> uint64
        self.routed_owners: Dict[str, int] = {}
        # sids whose overlapped prefill failed (slot already released)
        self.failed_prefills: List[str] = []
        if group is not None:
            (self._prefill, self._decode_full, self._decode_slots,
             self._prefill_chunk) = group.fns()
            self._decode_full_fused = self._decode_slots_fused = None
        else:
            (self._prefill, self._decode_full, self._decode_slots,
             self._decode_full_fused, self._decode_slots_fused,
             self._prefill_chunk) = _jitted(model)

    # -- group-aware cache plumbing (identity for single-device replicas) --
    def _init_cache(self, batch: int, max_len: int):
        if self.group is not None:
            return self.group.init_cache(batch, max_len)
        return self.model.init_cache(batch, max_len)

    def _cache_with_blocks(self, blocks):
        if self.group is not None:
            return self.group.cache_with_blocks(self.max_len, blocks)
        return self.model.cache_with_blocks(self.max_len, blocks)

    def _export_kv_block(self, cache, row: int, off: int, chunk: int):
        if self.group is not None:
            return self.group.export_kv_block(cache, row, off, chunk)
        return self.model.export_kv_block(cache, row, off, chunk)

    @property
    def num_active(self) -> int:
        return len(self.sessions)

    @property
    def num_free(self) -> int:
        return len(self._free)

    def attach_params(self, params) -> None:
        self.params = params

    def admit(self, req: Request) -> int:
        """Prefill a prompt into a free slot (single-sequence batch into a
        fresh slot-shaped cache, then written back slot-granular) and
        return the first generated token.

        Any prefill failure (bad tokens, OOM, a kernel error) rolls the
        slot allocation back: the session entry and the free-list slot
        used to be committed BEFORE prefill ran, so a failed admit left a
        phantom session with ``active=False`` and the next
        ``decode_round`` raised KeyError for every caller."""
        s = len(req.prompt)
        if s >= self.max_len:   # validate BEFORE allocating: a rejected
            # admit must not leak the slot or leave a phantom session
            raise ValueError(f"prompt of {s} tokens >= max_len {self.max_len}")
        fresh = False
        if req.session_id in self.sessions:
            slot = self.sessions[req.session_id]
        elif self._free:
            slot = self._free.pop()
            self.sessions[req.session_id] = slot
            fresh = True
        else:
            raise RuntimeError("replica full")
        try:
            one = self._init_cache(1, self.max_len)
            if self._chunkable(s):
                # fixed-shape chunk loop: every admit of every length
                # reuses ONE compiled segment program (whole-prompt
                # prefill retraces per distinct prompt length — the bulk
                # of the measured per-session migration cost)
                tok, one = self._run_chunks(req.prompt, one)
            else:
                batch = {"tokens": jnp.asarray(req.prompt, jnp.int32)[None, :]}
                logits, one = self._prefill(self.params, batch, one)
                tok = int(jnp.argmax(logits[0]))
            # the commit stays inside the try: with async dispatch a
            # device-side prefill failure (OOM, kernel error) surfaces
            # only HERE, when the result is first materialized
            self._write_slot(one, slot)
            self._commit_slot(req.session_id, slot, s, tok)
        except BaseException:
            if fresh:
                del self.sessions[req.session_id]
                self._free.append(slot)
                self.active[slot] = False
                self.lengths[slot] = 0
                self.tokens[slot, 0] = 0
            raise
        return tok

    # -- chunked / overlapped prefill ---------------------------------------
    def _chunkable(self, s: int) -> bool:
        """Chunk the prefill iff a chunk size is configured, the model
        has a continuation path, and the padded prompt fits the cache."""
        c = self.prefill_chunk
        return bool(c) and self._prefill_chunk is not None \
            and (s + c - 1) // c * c <= self.max_len

    def _run_chunks(self, prompt: np.ndarray, one, *,
                    start: int = 0) -> Tuple[int, object]:
        """Drive the fixed-shape segment program over a prompt from cache
        position ``start`` (0 = whole prompt; > 0 continues over a cache
        whose first ``start`` positions were imported from KV blocks);
        returns (first generated token, filled 1-row cache).

        With a prefix cache attached and ``start == 0``, the longest
        cached token-prefix is imported instead of computed — a hit on a
        shared system prompt skips those chunks' prefill FLOPs entirely
        — and every freshly computed full chunk is offered back."""
        c = self.prefill_chunk
        s = len(prompt)
        if start == 0 and self.prefix_cache is not None:
            covered, blocks = self.prefix_cache.match(prompt)
            if covered:
                # replace the caller's zero cache with one assembled
                # host-side around the imported run (a dispatched set per
                # block would cost as much as recomputing the chunk)
                one = self._cache_with_blocks(blocks)
                start = covered
        padded = (s + c - 1) // c * c
        buf = np.zeros(padded, np.int32)
        buf[:s] = prompt
        logits = None
        for off in range(start, padded, c):
            seg = jnp.asarray(buf[off:off + c], jnp.int32)[None, :]
            logits, one = self._prefill_chunk(self.params, seg, one, off)
            if self.prefix_cache is not None and off + c <= s:
                self.prefix_cache.insert(
                    prompt, off, self._export_kv_block(one, 0, off, c))
        # the prompt's last real token sits at column (s-1) - (padded-c)
        # of the final (right-padded) segment's all-position logits
        tok = int(jnp.argmax(logits[0, (s - 1) - (padded - c)]))
        return tok, one

    def admit_from_blocks(self, req: Request, blocks) -> int:
        """Admit from imported KV blocks: cache positions
        [0, len(blocks)*chunk) come off the wire (a replica-set fetch),
        only the remaining tail of the prompt is re-prefilled.  The
        blocks are bit-identical to what this replica would have
        computed, so the returned token — and every decode after it —
        matches a from-scratch admit exactly.  Degrades to ``admit``
        when no blocks are given; the same rollback discipline applies
        (a failed import or tail prefill leaks no slot)."""
        if not blocks:
            return self.admit(req)
        s = len(req.prompt)
        c = self.prefill_chunk
        if not self._chunkable(s):
            raise ValueError("prompt not chunkable on this replica")
        covered = len(blocks) * c
        if covered > max(((s - 1) // c) * c, 0):
            raise ValueError("blocks cover the final segment: the tail "
                             "must be recomputed to produce logits")
        if s >= self.max_len:
            raise ValueError(f"prompt of {s} tokens >= max_len {self.max_len}")
        fresh = False
        if req.session_id in self.sessions:
            slot = self.sessions[req.session_id]
        elif self._free:
            slot = self._free.pop()
            self.sessions[req.session_id] = slot
            fresh = True
        else:
            raise RuntimeError("replica full")
        try:
            t0 = time.perf_counter_ns()
            with spans.span(spans.KV_IMPORT, session=req.session_id,
                            chunks=len(blocks)):
                one = self._cache_with_blocks(blocks)
                jax.block_until_ready(jax.tree.leaves(one)[0])
            self.import_us = (time.perf_counter_ns() - t0) / 1e3
            tok, one = self._run_chunks(req.prompt, one, start=covered)
            self._write_slot(one, slot)
            self._commit_slot(req.session_id, slot, s, tok)
        except BaseException:
            if fresh:
                del self.sessions[req.session_id]
                self._free.append(slot)
                self.active[slot] = False
                self.lengths[slot] = 0
                self.tokens[slot, 0] = 0
            raise
        return tok

    def export_block(self, session_id: str, j: int) -> np.ndarray:
        """Chunk ``j`` of the session's live cache as a host slab
        (positions [j*chunk, (j+1)*chunk) — the caller guarantees the
        session's length has crossed that boundary)."""
        slot = self.sessions[session_id]
        c = self.prefill_chunk
        return self._export_kv_block(self.cache, slot, j * c, c)

    def export_block_shards(self, session_id: str, j: int) -> List[np.ndarray]:
        """Chunk ``j`` as per-shard slabs — shard s is the kv_heads slice
        device s of the replica group holds (a 1-element list for
        single-device replicas), each independently storable so a group
        export moves every device's slice without first gathering the
        full slab onto one device."""
        slot = self.sessions[session_id]
        c = self.prefill_chunk
        if self.group is not None:
            return self.group.export_kv_shards(self.cache, slot, j * c, c)
        return [self.model.export_kv_block(self.cache, slot, j * c, c)]

    def _commit_slot(self, session_id: str, slot: int, s: int,
                     tok: int) -> None:
        key = np.uint64(session_key(session_id))
        self.key_hi[slot] = np.uint32(key >> np.uint64(32))
        self.key_lo[slot] = np.uint32(key & np.uint64(0xFFFFFFFF))
        self.lengths[slot] = s
        self.tokens[slot, 0] = tok
        self.active[slot] = True

    def begin_admit(self, req: Request) -> Optional[int]:
        """Start an admit that overlaps with decode rounds.

        When the prompt is chunkable the slot is reserved, the prefill
        state parked in ``_pending``, and None is returned — subsequent
        ``decode_round`` calls advance it one fixed-shape chunk at a
        time (``advance_prefills``) until the first token materializes.
        Otherwise this degrades to the synchronous ``admit`` and returns
        its first token directly.  The session enters ``sessions`` only
        on completion, so a half-filled slot is never decoded and a
        chunk failure cannot leave a phantom session."""
        s = len(req.prompt)
        if not self._chunkable(s):
            return self.admit(req)
        if req.session_id in self.sessions or req.session_id in self._pending:
            raise RuntimeError(f"session {req.session_id} already resident")
        if s >= self.max_len:
            raise ValueError(f"prompt of {s} tokens >= max_len {self.max_len}")
        if not self._free:
            raise RuntimeError("replica full")
        slot = self._free.pop()
        c = self.prefill_chunk
        padded = (s + c - 1) // c * c
        buf = np.zeros(padded, np.int32)
        buf[:s] = np.asarray(req.prompt, np.int32)
        st = {
            "slot": slot, "cache": None,
            "prompt": buf, "s": s, "off": 0, "logits": None,
        }
        if self.prefix_cache is not None:
            # overlapped admits hit the cross-session prefix cache too:
            # imported chunks are chunks the duty-cycle never has to
            # advance (inserts stay on the synchronous path only)
            covered, blocks = self.prefix_cache.match(
                np.asarray(req.prompt, np.int32))
            if covered:
                st["cache"] = self._cache_with_blocks(blocks)
                st["off"] = covered
        if st["cache"] is None:
            st["cache"] = self._init_cache(1, self.max_len)
        self._pending[req.session_id] = st
        return None

    @property
    def num_pending(self) -> int:
        return len(self._pending)

    def advance_prefills(self, chunks: int = 1) -> Dict[str, int]:
        """Advance every in-flight overlapped prefill by up to ``chunks``
        fixed-shape segments; returns {sid: first token} for the ones
        that completed.  A failed chunk releases the reserved slot,
        drops the pending state, and records the sid in
        ``failed_prefills`` (instead of raising, so one bad session
        can't discard siblings' completions mid-loop) — the cluster
        re-strands failed sessions for a later re-home."""
        done: Dict[str, int] = {}
        for sid in list(self._pending):
            st = self._pending[sid]
            try:
                c = self.prefill_chunk
                for _ in range(chunks):
                    off = st["off"]
                    seg = jnp.asarray(st["prompt"][off:off + c],
                                      jnp.int32)[None, :]
                    st["logits"], st["cache"] = self._prefill_chunk(
                        self.params, seg, st["cache"], off)
                    st["off"] = off + c
                    if st["off"] >= len(st["prompt"]):
                        break
                if st["off"] < len(st["prompt"]):
                    continue
                padded, s, slot = len(st["prompt"]), st["s"], st["slot"]
                tok = int(jnp.argmax(
                    st["logits"][0, (s - 1) - (padded - c)]))
                self._write_slot(st["cache"], slot)
                self.sessions[sid] = slot
                self._commit_slot(sid, slot, s, tok)
                del self._pending[sid]
                done[sid] = tok
            except Exception:
                slot = st["slot"]
                del self._pending[sid]
                self._free.append(slot)
                self.active[slot] = False
                self.lengths[slot] = 0
                self.tokens[slot, 0] = 0
                self.failed_prefills.append(sid)
        return done

    def _write_slot(self, one_cache, slot: int) -> None:
        def wr(dst, src):
            return dst.at[:, slot:slot + 1].set(src) if dst.ndim >= 2 else dst
        self.cache = jax.tree.map(wr, self.cache, one_cache)

    def decode_round(self, route=None) -> Dict[str, int]:
        """One decode step for all active sessions — each at its own
        cache position.  The active slots are compacted into a batch
        padded to a power-of-two bucket (see ``_decode_bucket``): decode
        work scales with the live session count, and the jit only ever
        sees log2(slots)+1 batch shapes, so admitting or evicting a
        session never costs a recompile.  Padding rows carry an
        out-of-range index: gathers fill them with zeros and the KV
        scatter drops them.

        ``route`` is the device bucket directory (bkt_hi, bkt_lo, occ)
        from ``RingState.device_bucket_table``: when given, the round
        runs the FUSED program — the bucketized owner lookup on the
        batch's session keys rides inside the same dispatch as the
        gather + decode, and the resolved owners land in
        ``routed_owners`` (sid -> uint64 peer id) for the cluster's
        ownership accounting.  One device program per round either way.
        """
        self.routed_owners = {}
        if self.group is not None:
            route = None     # fused ring lookup stays host-side for groups
        if not self.sessions:
            return {}
        act_idx = np.nonzero(self.active)[0].astype(np.int32)
        bucket = _decode_bucket(act_idx.size, self.slots)
        full = bucket == self.slots
        ohi = olo = None
        with spans.span(spans.SERVE_UPLOAD_SLOTS):
            args = [jnp.asarray(self.tokens), jnp.asarray(self.lengths)]
            if full:
                # full house: the gather would be the identity
                # permutation — step the slab directly and skip the
                # scatter-back copy (inactive rows decode garbage at
                # position 0, as the slab engine always did; admit
                # rewrites the whole slot anyway)
                rows = act_idx
            else:
                idx = np.full(bucket, self.slots, np.int32)  # OOB pad
                idx[:act_idx.size] = act_idx
                args.append(jnp.asarray(idx))
                rows = np.arange(act_idx.size)
            if route is not None:
                args += [jnp.asarray(self.key_hi), jnp.asarray(self.key_lo),
                         *route]
        with spans.span(spans.SERVE_DISPATCH):
            if route is not None:
                fn = self._decode_full_fused if full \
                    else self._decode_slots_fused
                out, self.cache, ohi, olo = fn(self.params, self.cache, *args)
            else:
                fn = self._decode_full if full else self._decode_slots
                out, self.cache = fn(self.params, self.cache, *args)
            if self.group is not None:
                # TP groups return vocab-SHARDED logits (models/tp.py
                # keeps the head shard-local): the greedy pick needs the
                # global array, so it runs here instead of inside the
                # group program
                out = jnp.argmax(out, axis=-1).astype(jnp.int32)
        row_of = {int(s): int(r) for s, r in zip(act_idx, rows)}
        # the round's ONE mandatory device->host transfer: B int32
        # tokens (plus the fused path's owner words) in a single
        # device_get — logits never cross the host boundary
        with spans.span(spans.SERVE_DEVICE_GET):
            if ohi is not None:
                # repro-lint: allow(RL003) the one mandatory per-round transfer
                nxt, hi, lo = jax.device_get((out, ohi, olo))
            else:
                # repro-lint: allow(RL003) the one mandatory per-round transfer
                nxt = jax.device_get(out)
        if ohi is not None:
            owners = (hi.astype(np.uint64) << np.uint64(32)) \
                | lo.astype(np.uint64)
            self.routed_owners = {sid: int(owners[row_of[slot]])
                                  for sid, slot in self.sessions.items()}
        nxt = nxt.astype(np.int32, copy=False)
        self.tokens[act_idx, 0] = nxt[rows]
        self.lengths[act_idx] += 1
        return {sid: int(nxt[row_of[slot]])
                for sid, slot in self.sessions.items()}

    def evict(self, session_id: str) -> None:
        """Free the session's slot and zero its row — stale lengths used
        to survive eviction and (under the old global-max decode index)
        inflated every remaining session's decode position."""
        slot = self.sessions.pop(session_id, None)
        if slot is None:
            pend = self._pending.pop(session_id, None)
            if pend is not None:           # abandon an in-flight prefill
                self._free.append(pend["slot"])
            return
        self.active[slot] = False
        self.lengths[slot] = 0
        self.tokens[slot, 0] = 0
        self.key_hi[slot] = 0
        self.key_lo[slot] = 0
        self._free.append(slot)
