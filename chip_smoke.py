#!/usr/bin/env python3
"""Chip smoke test: drive the system's main path once on a TPU and check
every result against an independent oracle.

    python chip_smoke.py                # one chip: phases ring, churn, serve
    python chip_smoke.py --chips 4      # four chips: only the tp=2 serve phase

Phases (each asserts its result and prints one line ``<phase>: {json}``):

  ring   RingState of n = 10^6 and 10^4 random ids; 2^20 keys resolved
         through ``RingState.lookup`` (its own bucketed/flat dispatch) and
         compared with a numpy searchsorted oracle, before and after one
         64-event join/leave/crash batch applied through ``apply_events``.
  churn  the compiled ``edra_tree`` kernel at 2^20 (event, observer)
         pairs against ``ref.tree_math`` in numpy.
  serve  qwen2.5-3b at its published widths (bf16, random weights from
         ``--seed``) behind a ``ServeCluster`` over a 10^4-member ring:
         8 sessions, the busiest owner killed mid-decode so its sessions
         move by KV handoff, first-step logits held against the plain
         jnp decode path.
  tp     (``--chips 4`` only) the same traffic on ``ServeCluster(tp=2)``:
         two 2-chip groups, one killed mid-decode; first-step logits held
         against tp=1 on one chip of the host.

The last line of standard output is one JSON object naming the device.
With no TPU the script exits non-zero and prints no result.  Kernel
tiles are the committed defaults (no autotune cache is read), and JAX's
compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` or ``.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs import get_config  # noqa: E402

MODEL = "qwen2.5-3b"
# bf16's unit roundoff.  First-step logits of two bf16 runs of one model
# (kernel vs plain decode, tp=2 vs tp=1) are held to one unit roundoff
# per layer, summed over the stack, of the reference's largest |logit|:
# the first-order forward error of a residual stack in bf16.
BF16_EPS = 2.0 ** -8


def _emit(phase: str, result: dict) -> None:
    print(f"{phase}: {json.dumps(result, sort_keys=True)}", flush=True)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# ring: RingState.lookup vs a numpy oracle, before and after a churn batch
# ---------------------------------------------------------------------------

def _oracle_owner(ids: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """Successor (first id >= key, wrapping) of every key."""
    return ids[np.searchsorted(ids, keys) % ids.size]


def phase_ring(n: int, queries: int, *, seed: int, events: int = 64) -> dict:
    from repro.core.edra import Event
    from repro.core.ringstate import RingState

    rng = np.random.default_rng(seed)
    ids = np.unique(rng.integers(0, 2**64, n, dtype=np.uint64))
    state = RingState(ids)
    keys = rng.integers(0, 2**64, queries, dtype=np.uint64)
    # boundary keys: exact ids, id + 1, and keys past the largest id
    edge = min(queries // 8, ids.size)
    keys[:edge] = rng.choice(ids, edge, replace=False)
    keys[edge:2 * edge] = rng.choice(ids, edge, replace=False) + np.uint64(1)
    keys[-4:] = np.uint64(2**64 - 1) - np.arange(4, dtype=np.uint64)

    def check(tag: str, live: np.ndarray) -> dict:
        t0 = time.perf_counter()
        got = state.lookup(keys)
        wall = time.perf_counter() - t0
        bad = int(np.count_nonzero(got != _oracle_owner(live, keys)))
        _check(bad == 0, f"ring n={n} {tag}: {bad} of {queries} owners "
                         f"differ from the oracle")
        return {"kernel": state.last_lookup_kernel, "mismatches": bad,
                "lookup_wall_s": wall}

    out = {"n": int(ids.size), "queries": queries,
           "before": check("before churn", ids)}
    # one EDRA batch: a third joins, the rest graceful leaves and crashes
    # (a crash reaches the table as a detected leave)
    joins = np.setdiff1d(rng.integers(0, 2**64, events // 3, dtype=np.uint64),
                         ids)
    gone = rng.choice(ids, events - events // 3, replace=False)
    batch = [Event(int(p), "join") for p in joins] \
        + [Event(int(p), "leave") for p in gone]
    changed = state.apply_events(batch)
    live = np.union1d(np.setdiff1d(ids, gone), joins)
    _check(len(state) == live.size, "ring size after churn")
    out.update({
        "events": {"join": int(joins.size),
                   "leave": int(gone.size - gone.size // 2),
                   "crash": int(gone.size // 2), "slots_changed": changed},
        "after": check("after churn", live),
        "bucket_stats": state.bucket_stats(),
        "full_uploads": state.full_uploads,
        "delta_uploads": state.delta_uploads,
        "upload_bytes": state.upload_bytes,
    })
    return out


# ---------------------------------------------------------------------------
# churn: compiled edra_tree vs ref.tree_math in numpy
# ---------------------------------------------------------------------------

def phase_churn(pairs: int, *, seed: int, n: int = 10**6) -> dict:
    import jax

    from repro.kernels.edra_tree.ops import edra_tree
    from repro.kernels.edra_tree.ref import tree_math

    rng = np.random.default_rng(seed)
    args = (rng.integers(0, n, pairs).astype(np.uint32),         # offset
            np.full(pairs, n, np.uint32),                         # ring size
            rng.integers(0, n, pairs).astype(np.uint32),          # reporter
            rng.uniform(0.0, 10.0, pairs).astype(np.float32),     # t_detect
            rng.integers(0, 2**32, pairs, dtype=np.uint64).astype(np.uint32))
    # Theta = 1 s and delta = 2^-4 s: power-of-two scales keep every
    # multiply exact, so only libm's log separates kernel and oracle
    kw = dict(levels=int(np.ceil(np.log2(n))), theta=1.0, delta_avg=0.0625)
    got = jax.device_get(edra_tree(*map(jax.device_put, args), **kw))
    want = tree_math(np, *args, **kw)
    out = {"pairs": pairs, "n": n}
    for name, g, w in zip(("ttl", "depth", "parent", "sends"),
                          got[1:], want[1:]):
        bad = int(np.count_nonzero(np.asarray(g) != np.asarray(w)))
        _check(bad == 0, f"edra_tree {name}: {bad} of {pairs} differ")
        out[f"{name}_mismatches"] = bad
    ack, ref = np.asarray(got[0], np.float64), np.asarray(want[0], np.float64)
    err = np.abs(ack - ref)
    bad = np.nonzero(err > ACK_RTOL * np.maximum(np.abs(ref), 1))[0]
    good = np.ones(pairs, bool)
    good[bad] = False
    out.update({"ack_max_abs_err": float(err[good].max(initial=0.0)),
                "ack_rel_tol": ACK_RTOL, "ack_off_oracle": int(bad.size)})
    # An ack is a chain of interval-boundary decisions (a ceil).  The
    # chip's log differs from numpy's in its last bits, so a time within
    # that rounding of a boundary can flush one whole interval apart.  A
    # pair off the oracle is accepted as such a tie only if the oracle,
    # rerun with ONE ceil rounded the other way, equals the kernel's ack,
    # and that ceil's argument lies within ACK_RTOL of an integer: the
    # tolerance already allowed on every time.
    sub = tuple(a[bad] for a in args)
    watch = _CeilProbe()
    tree_math(watch, *sub, **kw)
    tie, tie_dist = np.zeros(bad.size, bool), np.zeros(bad.size)
    for call, dist in enumerate(watch.dists):
        near = dist <= ACK_RTOL
        if near.any():
            flip = _CeilProbe(np.where(near, call, -1))
            alt = np.asarray(tree_math(flip, *sub, **kw)[0], np.float64)
            hit = near & ~tie & (np.abs(ack[bad] - alt)
                                 <= ACK_RTOL * np.maximum(np.abs(alt), 1))
            tie |= hit
            tie_dist = np.where(hit, dist, tie_dist)
    _check(bool(tie.all()),
           f"edra_tree ack: {int((~tie).sum())} of {pairs} off the oracle "
           f"and not boundary ties: kernel {ack[bad][~tie][:5]} oracle "
           f"{ref[bad][~tie][:5]}")
    out.update({"boundary_ties": int(tie.sum()),
                "tie_max_boundary_dist": float(tie_dist.max(initial=0.0))})
    return out


ACK_RTOL = 1e-5       # float32 tolerance on an acknowledge time


class _CeilProbe:
    """numpy namespace for ``tree_math`` that watches its ``ceil`` calls.
    Without ``flip`` it records, per call, each element's distance from
    the nearest integer relative to the argument (``dists``); with
    ``flip`` it rounds element i of call ``flip[i]`` the other way."""

    def __init__(self, flip=None):
        self.flip = flip
        self.dists = []

    def __getattr__(self, name):
        return getattr(np, name)

    def ceil(self, x):
        up, near = np.ceil(x), np.rint(x)
        if self.flip is None:
            self.dists.append(np.abs(x - near) / np.maximum(np.abs(x), 1))
        else:
            other = np.where(up == near, near + 1, near)
            up = np.where(self.flip == len(self.dists), other, up)
            self.dists.append(None)
        return up


# ---------------------------------------------------------------------------
# serve: ServeCluster at published widths, busiest owner killed mid-decode
# ---------------------------------------------------------------------------

def _membership(members: int):
    from repro.runtime import Membership

    m = Membership(t_q=60.0, now=lambda: 0.0)
    for i in range(members):
        m.request_join(f"10.{i // 250}.{i % 250}.1", 7000)
    return m


def _requests(cfg, state, count: int, *, seed: int, prompt_lens, new_tokens):
    """``count`` sessions on two ring owners — the busiest holds
    count//2 + 1 — so a kill mid-decode moves several sessions at once.
    Returns (requests, busiest owner)."""
    from repro.serve import Request
    from repro.serve.server import session_key

    act = state.active_ids()
    sids = [f"req-{seed}-{i}" for i in range(64 * act.size // count + 4096)]
    keys = np.fromiter((session_key(s) for s in sids), np.uint64, len(sids))
    owner = _oracle_owner(act, keys)
    uniq, counts = np.unique(owner, return_counts=True)
    order = uniq[np.argsort(-counts, kind="stable")]
    busy = order[0]
    succ = act[(np.searchsorted(act, busy) + 1) % act.size]
    other = next(o for o in order[1:] if o != succ)
    want = {busy: count // 2 + 1, other: count - count // 2 - 1}
    picked = []
    for sid, o in zip(sids, owner):
        if want.get(o, 0) > 0:
            want[o] -= 1
            picked.append(sid)
    rng = np.random.default_rng(seed)
    lo, hi = prompt_lens
    reqs = [Request(sid, rng.integers(0, cfg.vocab, int(rng.integers(lo, hi + 1)),
                                      dtype=np.int32), max_new_tokens=new_tokens)
            for sid in picked]
    return reqs, int(busy)


def _init_params(model, seed: int):
    import jax

    return jax.block_until_ready(model.init(jax.random.PRNGKey(seed)))


def _first_step_logits(model, params, req, *, max_len: int, chunk: int,
                       group=None) -> np.ndarray:
    """Prefill ``req`` into a 1-slot replica, then return the logits of
    its first decode step (the second generated token's)."""
    import jax
    import jax.numpy as jnp

    from repro.serve import Replica

    rep = Replica(model, slots=1, max_len=max_len, prefill_chunk=chunk,
                  group=group)
    rep.attach_params(params)
    rep.admit(req)
    tokens, lengths = jnp.asarray(rep.tokens), jnp.asarray(rep.lengths)
    if group is not None:
        decode = group.fns()[1]
    else:
        decode = jax.jit(model.decode_step)
    logits, _ = decode(params, rep.cache, tokens, lengths)
    return np.asarray(jax.device_get(logits), np.float32)[0]


def _logit_gap(got: np.ndarray, ref: np.ndarray, layers: int) -> dict:
    diff = float(np.abs(got - ref).max())
    bound = layers * BF16_EPS * max(float(np.abs(ref).max()), 1.0)
    return {"max_abs_diff": diff, "bound": bound,
            "argmax_equal": bool(got.argmax() == ref.argmax())}


def _decode_kernel_check(cfg, *, b: int, s: int, seed: int) -> dict:
    """The decode kernel alone at the model's head widths on bf16 inputs,
    against the jnp oracle in float32 at full matmul precision.  The
    lengths include 1 and the block edges, where a mask error shows; the
    bound is two bf16 roundings (of the probabilities and the output)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.decode_attention.kernel import BS
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref

    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
               for shape in ((b, h, hd), (b, s, hkv, hd), (b, s, hkv, hd)))
    edges = np.array([1, BS - 1, BS, BS + 1, s // 2 + 3, s - 1, s, 2])
    length = jnp.asarray(np.clip(np.resize(edges, b), 1, s), jnp.int32)
    got = np.asarray(decode_attention(q, k, v, length), np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(decode_attention_ref(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), length))
    err = float(np.abs(got - want).max())
    bound = 2 * BF16_EPS * float(np.abs(want).max())
    _check(err <= bound, f"decode kernel off its f32 oracle: {err} > {bound}")
    return {"lengths": np.asarray(length).tolist(), "max_abs_err": err,
            "bound": bound}


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_serve(cfg, *, members: int, slots: int, max_len: int,
                count: int, prompt_lens, new_tokens: int, kill_after: int,
                seed: int, chunk: int = 16, decode_kernel=None) -> dict:
    """``decode_kernel`` is passed to ServeCluster: None lets the model
    dispatch pick the Pallas decode kernel (as a user's run does); the
    phase then asserts the kernel is in the decode program it ran."""
    import jax

    from repro.kernels.decode_attention.kernel import NAME as DECODE_KERNEL
    from repro.models import Model
    from repro.serve import ServeCluster
    from repro.serve.server import _jitted

    model = Model(cfg)
    params = _init_params(model, seed)
    m = _membership(members)
    reqs, busy = _requests(cfg, m.ring_state, count, seed=seed,
                           prompt_lens=prompt_lens, new_tokens=new_tokens)
    cluster = ServeCluster(m, model, params, slots=slots, max_len=max_len,
                           prefill_chunk=chunk, decode_kernel=decode_kernel)
    for r in reqs:
        cluster.submit(r)
    moved = sum(rec.owner == busy for rec in cluster.sessions.values())
    for _ in range(kill_after):
        cluster.step()
    m.fail(busy)
    rounds = kill_after + cluster.run()
    st = cluster.stats()
    done = [len(rec.generated) for rec in cluster.sessions.values()]
    _check(all(d == new_tokens for d in done),
           f"sessions incomplete: {done}")
    _check(st["prefill_failures"] == 0, "failed prefills")
    _check(st["handoff_misses"] == 0, "handoff misses")
    _check(st["handoffs"] == moved > 0,
           f"{st['handoffs']} handoffs for {moved} sessions on the victim")
    _check(st["stranded"] == 0, "stranded sessions")
    _check(cluster.fused_rounds > 0, "no fused route->decode round ran")
    _check(cluster.route_divergence == 0,
           f"route divergence {cluster.route_divergence}")

    # the decode program the cluster ran must contain the Pallas kernel
    rep = next(iter(cluster.replicas.values()))
    fused_full = _jitted(cluster.model)[3]
    jaxpr = jax.make_jaxpr(fused_full)(
        rep.params, rep.cache, rep.tokens, rep.lengths, rep.key_hi,
        rep.key_lo, *cluster.state.device_bucket_table())
    kernel = f"name={DECODE_KERNEL}" in str(jaxpr)
    _check(kernel, "decode program runs the jnp attention, not the kernel")
    kernel_vs_oracle = _decode_kernel_check(cfg, b=slots, s=max_len,
                                            seed=seed)

    req = reqs[0]
    got = _first_step_logits(cluster.model, params, req, max_len=max_len,
                             chunk=chunk)
    ref = _first_step_logits(
        dataclasses.replace(cluster.model, decode_use_kernel=False), params,
        req, max_len=max_len, chunk=chunk)
    gap = _logit_gap(got, ref, cfg.num_layers)
    _check(gap["max_abs_diff"] <= gap["bound"],
           f"kernel logits off the plain decode path: {gap}")
    # the token the cluster's fused kernel round produced must be the
    # plain path's argmax wherever the bound cannot reorder the top two
    top2 = np.sort(ref)[-2:]
    gap["ref_top2_margin"] = float(top2[1] - top2[0])
    gap["cluster_token"] = int(cluster.sessions[req.session_id].generated[1])
    gap["cluster_token_is_ref_argmax"] = gap["cluster_token"] == int(
        ref.argmax())
    _check(gap["cluster_token_is_ref_argmax"]
           or gap["ref_top2_margin"] <= 2 * gap["bound"],
           f"cluster's first decode token off the plain path: {gap}")
    return {
        "model": cfg.name, "layers": cfg.num_layers, "dtype": cfg.dtype,
        "members": members, "slots": slots, "max_len": max_len,
        "sessions": len(reqs), "new_tokens": new_tokens, "rounds": rounds,
        "killed_owner_sessions": moved, "decode_kernel_in_program": kernel,
        "decode_kernel_vs_f32_oracle": kernel_vs_oracle,
        "fused_rounds": cluster.fused_rounds,
        "route_divergence": cluster.route_divergence,
        "stats": st, "first_step_logits": gap,
        "peak_bytes_in_use": _peak_bytes(),
    }


# ---------------------------------------------------------------------------
# tp: ServeCluster(tp=2) over four chips, one group killed mid-decode
# ---------------------------------------------------------------------------

def _devices_of(tree) -> list:
    import jax

    return sorted({d.id for leaf in jax.tree.leaves(tree)
                   for d in leaf.devices()})


def phase_tp(cfg, *, tp: int, members: int, slots: int, max_len: int,
             count: int, prompt_lens, new_tokens: int, kill_after: int,
             seed: int, chunk: int = 16) -> dict:
    import jax

    from repro.models import Model
    from repro.serve import ServeCluster

    devices = jax.devices()
    _check(len(devices) >= 2 * tp, f"tp={tp} x 2 groups needs {2 * tp} "
                                   f"devices, found {len(devices)}")
    model = Model(cfg)
    params = _init_params(model, seed)
    m = _membership(members)
    reqs, busy = _requests(cfg, m.ring_state, count, seed=seed,
                           prompt_lens=prompt_lens, new_tokens=new_tokens)
    cluster = ServeCluster(m, model, params, slots=slots, max_len=max_len,
                           prefill_chunk=chunk, tp=tp,
                           mesh=devices[:2 * tp])
    for r in reqs:
        cluster.submit(r)
    # where each group's weights and each replica's KV cache really live
    placement = {}
    for node, rep in cluster.replicas.items():
        gi = cluster._node_group[node]
        placement[gi] = {
            "mesh": [d.id for d in cluster._group_meshes[gi].devices.flat],
            "params": _devices_of(cluster._group_params[gi]),
            "cache": _devices_of(rep.cache)}
    _check(len(placement) == 2, f"sessions use {len(placement)} groups")
    for gi, p in placement.items():
        _check(p["params"] == p["cache"] == sorted(p["mesh"]),
               f"group {gi} state off its mesh: {p}")
    _check(not set(placement[0]["params"]) & set(placement[1]["params"]),
           f"groups share devices: {placement}")
    victim = cluster._node_group[busy]
    moved = sum(rec.owner == busy for rec in cluster.sessions.values())
    for _ in range(kill_after):
        cluster.step()
    cluster.lose_device(placement[victim]["mesh"][0])
    rounds = kill_after + cluster.run()
    st = cluster.stats()
    done = [len(rec.generated) for rec in cluster.sessions.values()]
    _check(all(d == new_tokens for d in done),
           f"sessions incomplete: {done}")
    _check(st["prefill_failures"] == 0, "failed prefills")
    _check(st["handoff_misses"] == 0, "handoff misses")
    _check(st["handoffs"] == moved > 0,
           f"{st['handoffs']} handoffs for {moved} sessions on the victim")
    homes = {cluster._node_group[rec.owner]
             for rec in cluster.sessions.values() if rec.migrations}
    _check(homes and victim not in homes,
           f"migrated sessions landed on groups {homes}")

    survivor = 1 - victim
    req = reqs[0]
    got = _first_step_logits(model, cluster._params_for(survivor), req,
                             max_len=max_len, chunk=chunk,
                             group=cluster._group_obj(survivor))
    ref = _first_step_logits(model, params, req, max_len=max_len,
                             chunk=chunk)
    gap = _logit_gap(got, ref, cfg.num_layers)
    _check(gap["max_abs_diff"] <= gap["bound"],
           f"tp={tp} logits off tp=1: {gap}")
    return {
        "model": cfg.name, "tp": tp, "groups": placement,
        "killed_group": victim, "killed_owner_sessions": moved,
        "migrated_to_groups": sorted(homes), "rounds": rounds,
        "tp1_device": _devices_of(params), "stats": st,
        "first_step_logits_vs_tp1": gap,
    }


# ---------------------------------------------------------------------------

class _CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache reads
    included, so a warm cache shows as a smaller total)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _require_tpu():
    import jax

    try:
        dev = jax.devices()[0]
    except RuntimeError as e:
        sys.exit(f"chip_smoke: JAX found no device: {e}")
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind})")
    return dev


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the tp=2 serve phase on four chips")
    args = ap.parse_args()

    dev = _require_tpu()
    import jax

    from repro.kernels.autotune import CACHE_ENV
    from repro.launch.compile_cache import enable_compile_cache

    # an empty autotune cache: every kernel takes the committed DEFAULTS,
    # so this run's compiles depend only on committed files
    os.environ[CACHE_ENV] = os.devnull
    cache_dir = enable_compile_cache()
    clock = _CompileClock()
    t0 = time.perf_counter()
    cfg = get_config(MODEL)
    serve_kw = dict(members=10**4, slots=8, max_len=2048, count=8,
                    prompt_lens=(128, 512), new_tokens=16, kill_after=4,
                    seed=args.seed)
    if args.chips == 4:
        phases = [("tp", lambda: phase_tp(cfg, tp=2, **serve_kw))]
    else:
        phases = [(f"ring_n{n}",
                   lambda n=n: phase_ring(n, 1 << 20, seed=args.seed))
                  for n in (10**6, 10**4)]
        phases += [("churn", lambda: phase_churn(1 << 20, seed=args.seed)),
                   ("serve", lambda: phase_serve(cfg, **serve_kw))]
    failed = []
    for name, run in phases:
        # a failed phase is reported and the rest still run, so one chip
        # run shows every phase's result
        try:
            _emit(name, run())
        except Exception as e:
            traceback.print_exc()
            print(f"{name}: FAILED {type(e).__name__}: {e}", flush=True)
            failed.append(name)
    _emit("compile", {"backend_compile_s": clock.seconds,
                      "compiles": clock.compiles,
                      "persistent_cache_hits": clock.cache_hits,
                      "cache_dir": cache_dir,
                      "wall_s": time.perf_counter() - t0})
    if failed:
        sys.exit(f"chip_smoke: phases failed: {', '.join(failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
