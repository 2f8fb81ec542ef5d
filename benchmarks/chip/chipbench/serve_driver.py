"""Open-loop chat serving through ``ServeCluster`` on one chip.

The chip holds ``nodes_held`` consecutive nodes of a ring of
``ring_members``; the fused decode round still routes every token
against the whole ring's device table.  Requests arrive as a Poisson
process at the mix's fixed rate; a request draws a session id whose
ring owner is the held node its ``place`` picks (in proportion to the
nodes' arcs, alike for every seed), waits in the generator's queue
for its owner until that replica has a free slot, and is then
submitted (``ServeCluster.submit`` runs its prefill).  ``step`` decodes
one token for every live session.  Times are host-clock times of the
calls that return the tokens; a request's time to first token counts
from when it was due.

``correct``: after the window the cluster is freed and the plain
reference (float32, ``references/<reference>.py``) is run over a sample
of finished requests, drawn from the seed with the longest in it: the
widest gap by which a served token's reference logit lies below the
reference's best must stay under the limit.  With ``control`` the fp8
reference stands in the program's place: the gap is that of the token
it puts first at each position, and the run must come out not correct.
Every owner the fused route returned must be the ring successor of its
key, and no prefill may fail.
"""
from __future__ import annotations

import gc
import tempfile
import time
from collections import deque

import numpy as np

from . import gen, xtrace
from .common import (ROOT, Check, CompileClock, Outcome, load_reference, log,
                     peak_bytes, percentile)

SRC = ROOT / "src"


def _program():
    import sys

    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro.configs.base import ModelConfig
    from repro.models import Model
    from repro.runtime import Membership
    from repro.serve import Request, ServeCluster
    return dict(ModelConfig=ModelConfig, Model=Model, Membership=Membership,
                Request=Request, ServeCluster=ServeCluster)


def model_config(cfg: dict, ModelConfig):
    """The program's config for a Qwen2-layout file."""
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or
        cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        qkv_bias=True, rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"], remat="none")


def program_params(w: dict) -> dict:
    """The benchmark's weights in the program's layout (same arrays)."""
    return {
        "embed": {"embedding": w["embed"]},
        "ln_f": w["ln_f"],
        "layers": {
            "ln1": w["ln1"], "ln2": w["ln2"],
            "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                     "wo": w["wo"], "bq": w["bq"], "bk": w["bk"],
                     "bv": w["bv"]},
            "mlp": {"w1": w["w_gate"], "w3": w["w_up"], "w2": w["w_down"]},
        },
    }


def ring_hosts(members: int):
    return [(f"10.{i // 250}.{i % 250}.1", 7000) for i in range(members)]


def held_pair(active: np.ndarray):
    """The two consecutive nodes whose smaller arc of the key space is
    the largest: the chip's share of the ring, the same for every seed.
    Returns the pair and the first node's share of their keys."""
    arc = (active - np.roll(active, 1)).astype(np.float64)  # uint64 wraps
    i = int(np.argmax(np.minimum(arc, np.roll(arc, -1))))
    j = (i + 1) % active.size
    return [int(active[i]), int(active[j])], float(arc[i] / (arc[i] + arc[j]))


class _Session:
    __slots__ = ("sid", "key", "due", "prompt", "out_len", "first",
                 "last", "seen", "window")

    def __init__(self, sid, key, due, prompt, out_len, window):
        self.sid, self.key, self.due = sid, key, due
        self.prompt, self.out_len, self.window = prompt, out_len, window
        self.first = None
        self.last = None
        self.seen = 0


def run(*, cell, config, traffic, seed, seconds, trace, devices,
        control=False, t0=None, program=None):
    import jax

    P = program or _program()
    successor = load_reference("successor").successor
    t0 = time.perf_counter() if t0 is None else t0
    clock = CompileClock()
    out = Outcome()
    srv = config["serving"]
    ref = load_reference(config["reference"])
    max_len, chunk = srv["max_len"], srv["prefill_chunk"]

    # -- set-up: weights, ring, cluster, every shape the traffic uses ----------
    weights = ref.make_weights(config, seed)
    model = P["Model"](model_config(config, P["ModelConfig"]))
    m = P["Membership"](t_q=60.0, now=lambda: 0.0)
    for host, port in ring_hosts(config["ring_members"]):
        m.request_join(host, port)
    state = m.ring_state
    pool_sids, pool_keys = gen.session_keys(seed, traffic["session_pool"])
    pool_used = np.zeros(pool_keys.size, bool)
    held, share0 = held_pair(state.active_ids())
    cluster = P["ServeCluster"](
        m, model, program_params(weights), slots=srv["slots"],
        max_len=max_len, prefill_chunk=chunk,
        replication=srv["replication"], prefill_duty=srv["prefill_duty"])
    rng = gen.rng_for(seed, 2)
    vocab = config["vocab_size"]
    sessions = {}
    live = {}                                  # sid -> _Session, not done
    ring = state.active_ids().copy()     # the ring does not change in a run
    cand = {"idx": np.nonzero(np.isin(successor(ring, pool_keys),
                                      np.array(held, np.uint64)))[0],
            "cursor": 0}

    def draw_session(node):
        """The next unused pool session owned by ``node``."""
        idx = cand["idx"]
        c = cand["cursor"]
        while c < idx.size and pool_used[idx[c]]:
            c += 1
        cand["cursor"] = c
        while c < idx.size and (pool_used[idx[c]]
                                or owner_of(pool_keys[idx[c]]) != node):
            c += 1
        if c >= idx.size:
            raise RuntimeError("session pool exhausted; raise session_pool")
        j = int(idx[c])
        pool_used[j] = True
        return pool_sids[j], int(pool_keys[j])

    def owner_of(key):
        return int(ring[np.searchsorted(ring, np.uint64(key)) % ring.size])

    def observe(now):
        """Token arrivals since the last call, from each live session's
        record; the returned tokens are what the cluster produced."""
        for s in list(live.values()):
            rec = cluster.sessions.get(s.sid)
            if rec is None:
                continue
            n = len(rec.generated)
            if n > s.seen:
                if s.first is None:
                    s.first = now
                elif s.last is not None and win["open"] <= now < win["close"]:
                    gaps.append(now - s.last)
                    gaps.extend([0.0] * (n - s.seen - 1))
                s.last = now
                s.seen = n
            if rec.done:
                del live[s.sid]

    route_log = []                       # (keys, owners)

    def step():
        cluster.step()
        for rep in cluster.replicas.values():
            if rep.routed_owners:
                sids = list(rep.routed_owners)
                route_log.append((np.fromiter((sessions[s].key for s in sids),
                                              np.uint64, len(sids)),
                                  np.fromiter(rep.routed_owners.values(),
                                              np.uint64, len(sids))))

    gaps = []
    win = {"open": np.inf, "close": np.inf}

    def submit(s):
        req = P["Request"](s.sid, s.prompt, max_new_tokens=s.out_len)
        cluster.submit(req)
        live[s.sid] = s

    # warm-up: every decode bucket up to the slot count, every slot index,
    # the prefill chunk program and the exports (the replicas share their
    # programs: one replica is filled).
    warm = [held[0]] * srv["slots"] + [n for n in held[1:]]
    b = 1
    for i, node in enumerate(warm):
        sid, key = draw_session(node)
        s = _Session(sid, key, 0.0,
                     rng.integers(0, vocab, 1 + (7 * i) % (2 * chunk),
                                  dtype=np.int32), 3 * srv["prefill_duty"], False)
        sessions[sid] = s
        submit(s)
        observe(time.perf_counter())
        if i + 1 == b or i + 1 == len(warm):
            step()
            observe(time.perf_counter())
            b *= 2
    while live:
        step()
        observe(time.perf_counter())
    gc.collect()

    # -- traffic: a ramp to steady state (set-up), then the window -----------
    sched = gen.chat_schedule(traffic, seconds, max_len)
    ramp = traffic["ramp_s"]
    queues = {}
    cursor = 0
    start = time.perf_counter()
    setup_s = None
    win["open"], win["close"] = start + ramp, start + ramp + seconds
    tdir = tempfile.TemporaryDirectory() if trace else None
    trace_state = {"on": False, "done": not trace, "span": None,
                   "ended": -np.inf}
    traced = {"decode_rows": [], "prefill_tokens": []}
    compiles0 = None
    tr_hold = traffic.get("trace_seconds", seconds)
    drain_limit = traffic.get("drain_s", 60.0)
    late = []                         # how late the generator queued
    backlog = []                      # (s into window, waiting, live)
    next_sample = win["open"]

    def in_trace():
        return trace_state["on"]

    while True:
        now = time.perf_counter()
        if setup_s is None and now >= win["open"]:
            setup_s = now - t0
            compiles0 = clock.compiles
        if not trace_state["done"] and not trace_state["on"] \
                and now >= win["open"]:
            jax.profiler.start_trace(tdir.name)
            trace_state["span"] = jax.profiler.TraceAnnotation("bench.window")
            trace_state["span"].__enter__()
            trace_state["on"] = True
        if trace_state["on"] and now >= win["open"] + tr_hold:
            trace_state["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            trace_state["on"] = False
            trace_state["done"] = True
            trace_state["ended"] = time.perf_counter()
        t_rel = now - start
        while cursor < len(sched) and sched[cursor].due <= t_rel \
                and start + sched[cursor].due < win["close"]:
            a = sched[cursor]
            cursor += 1
            sid, key = draw_session(held[0] if a.place < share0 else held[1])
            due = start + a.due
            s = _Session(sid, key, due,
                         rng.integers(0, vocab, a.prompt_len, dtype=np.int32),
                         a.output_len, win["open"] <= due < win["close"])
            sessions[sid] = s
            queues.setdefault(owner_of(key), deque()).append(s)
            if s.window:
                late.append(now - due)
        for node in list(queues):
            q = queues[node]
            while q and cluster._has_capacity(node):
                s = q.popleft()
                with jax.profiler.TraceAnnotation("bench.submit"):
                    submit(s)
                if in_trace():
                    traced["prefill_tokens"].append(len(s.prompt))
                observe(time.perf_counter())
        if live:
            if in_trace():
                traced["decode_rows"].append(
                    [len(sessions[s].prompt) + sessions[s].seen
                     for s in live if sessions[s].seen > 0
                     and cluster._session_resident(cluster.sessions[s])])
            with jax.profiler.TraceAnnotation("bench.step"):
                step()
            observe(time.perf_counter())
        elif cursor < len(sched):
            time.sleep(min(0.002, max(start + sched[cursor].due - now, 0)))
        now = time.perf_counter()
        if win["open"] <= now < win["close"] and now >= next_sample:
            backlog.append((round(now - win["open"], 3),
                            sum(len(q) for q in queues.values()), len(live)))
            next_sample += 1.0
        if now >= win["close"]:
            pending = [s for s in sessions.values()
                       if s.window and s.first is None]
            if not pending or now > win["close"] + drain_limit:
                break
            if not live and not any(queues.values()):
                break
    stop = time.perf_counter()
    if trace_state["on"]:
        trace_state["span"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    in_window = clock.compiles - compiles0
    out.device["memory_peak_bytes"] = peak_bytes(devices)
    st = cluster.stats()

    # -- end-to-end metrics ----------------------------------------------------
    win_req = [s for s in sessions.values() if s.window]
    ttft = [((s.first if s.first is not None else stop) - s.due) * 1e3
            for s in win_req]
    failed = sum(1 for s in win_req if s.first is None)
    ttft_after = [t for t, s in zip(ttft, win_req)
                  if s.due >= trace_state["ended"]]
    out.attempted, out.failed = len(win_req), failed
    out.e2e = {"setup_s": setup_s,
               "itl_p95_ms": percentile([g * 1e3 for g in gaps], 95)}

    # -- what the per-layer readers need ---------------------------------------
    out.layer = {"config": config, "device_kind": devices[0].device_kind,
                 "decode_rows": traced["decode_rows"],
                 "prefill_tokens": traced["prefill_tokens"],
                 "itl_p50_ms": percentile([g * 1e3 for g in gaps], 50)
                 if gaps else None,
                 # the profiler's stop holds the loop for seconds: requests
                 # due before the traced slice has ended are left out
                 "ttft_p95_ms": percentile(ttft_after, 95)
                 if ttft_after else None}
    if trace:
        ev = xtrace.load(xtrace.find_xplane(tdir.name))
        out.trace = xtrace.reduce(ev, xtrace.window_of(ev))
        tdir.cleanup()

    # -- correctness: free the program's state, then the reference -------------
    done = [s for s in sessions.values()
            if not (s.sid in live) and s.sid in cluster.sessions
            and cluster.sessions[s.sid].done and s.due > 0]
    served = {s.sid: list(cluster.sessions[s.sid].generated) for s in done}
    route_bad = sum(int(np.count_nonzero(owners != successor(ring, keys)))
                    for keys, owners in route_log)
    short = sum(1 for s in done if len(served[s.sid]) != s.out_len)
    notes = {"cell": cell["name"], "seed": seed, "held": held,
             "held_share": share0,
             "rate": traffic["rate_per_s"],
             "requests_in_window": len(win_req), "scheduled": len(sched),
             "window_s": win["close"] - win["open"], "run_s": stop - start,
             "compiles_in_window": in_window,
             "compiled_in_window":
                 clock.names[len(clock.names) - in_window:],
             "compile_s_total": clock.seconds,
             "persistent_cache_hits": clock.cache_hits,
             "ttft_ms_p50": percentile(ttft, 50),
             "ttft_ms_top": sorted(((round(t, 1), len(s.prompt))
                                    for t, s in zip(ttft, win_req)),
                                   reverse=True)[:5],
             "itl_ms_p50": percentile([g * 1e3 for g in gaps], 50),
             "itl_ms_q": {q: round(percentile([g * 1e3 for g in gaps], q), 1)
                          for q in (80, 90, 93, 95, 97, 99)} if gaps else {},
             "window_split": [sum(1 for s in win_req
                                  if owner_of(s.key) == n) for n in held],
             "itl_gaps": len(gaps), "stats": st,
             "generator_late_ms_max": max(late, default=0.0) * 1e3,
             "generator_late_ms_p95": percentile(late, 95) * 1e3
             if late else 0.0,
             "backlog": backlog,
             "route_checked": sum(len(k) for k, _ in route_log)}
    del cluster, m, state, live, queues
    gc.collect()
    sample = _sample(done, served, seed, traffic)
    widest, ctrl_widest, tokens_checked = 0.0, 0.0, 0
    for s in sample:
        g, cg = ref.logit_gaps(config, weights, s.prompt, served[s.sid],
                               control=control)
        widest = max(widest, float(g.max()))
        tokens_checked += g.size
        if cg is not None:
            ctrl_widest = max(ctrl_widest, float(cg.max()))
    notes.update({"sampled_requests": len(sample),
                  "sampled_tokens": tokens_checked,
                  "widest_gap": widest, "control": control})
    if control:
        notes["control_widest_gap"] = ctrl_widest
    out.checks = [
        # the control's fp8 tokens stand in the program's place
        Check("served_logit_gap", ctrl_widest if control else widest,
              config["limits"]["served_logit_gap"]),
        Check("route_owner_mismatches", route_bad, 0),
        Check("prefill_failures", st["prefill_failures"], 0),
        Check("short_sessions", short, 0),
        Check("sampled_tokens_short", max(0, traffic["sample_tokens"]
                                          - tokens_checked), 0),
    ]
    out.notes = notes
    log(f"serve: {len(win_req)} requests in the window, {len(gaps)} gaps, "
        f"{in_window} compiles in the window, {len(sample)} requests "
        f"({tokens_checked} tokens) checked")
    return out


def _sample(done, served, seed, traffic):
    """Finished requests for the reference: the longest, then a seeded
    draw, until ``sample_tokens`` served tokens or ``sample_max``
    requests."""
    if not done:
        return []
    rng = gen.rng_for(seed, 3)
    first = max(done, key=lambda s: len(s.prompt) + len(served[s.sid]))
    others = [s for s in done if s is not first]
    others = [others[i] for i in rng.permutation(len(others))]
    picked, toks = [], 0
    for s in [first] + others:
        if toks >= traffic["sample_tokens"] or len(picked) >= traffic["sample_max"]:
            break
        picked.append(s)
        toks += len(served[s.sid])
    return picked
