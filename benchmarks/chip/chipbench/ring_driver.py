"""Closed-loop lookups on the D1HT routing table under EDRA churn.

The window drives ``RingState.lookup`` with batches of keys, one call
outstanding, and applies each EDRA message with
``RingState.apply_events`` when it falls due at the configuration's
cadence (rho messages per Theta, r = 2n/S_avg events per second).
Keys resolved per second over the window are reported under the mix's
``rate_metric``: each mix has a bound of its own.

``correct``: after the window, the live set is replayed message by
message with numpy and every owner the window returned is compared
with the successor of its key in the live set as of the last message
applied before that lookup (the configuration's guarantee).  The limit
is 0 mismatches.  ``control`` applies each message one message late,
which breaks that guarantee.
"""
from __future__ import annotations

import tempfile
import time

import numpy as np

from . import gen, xtrace
from .common import (ROOT, Check, CompileClock, Outcome, load_reference, log,
                     peak_bytes)

SRC = ROOT / "src"


def _program():
    import sys

    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro.core.edra import Event
    from repro.core.ringstate import RingState
    return Event, RingState


def count_mismatches(ids, msgs, records, successor) -> int:
    """Owners that differ from the reference's successor, replaying the
    live set message by message.  ``records``: (messages applied, keys,
    owners) in window order."""
    live, done, bad = ids, 0, 0
    for applied, keys, owners in records:
        while done < applied:
            m = msgs[done]
            live = np.union1d(np.setdiff1d(live, m.leaves), m.joins)
            done += 1
        bad += int(np.count_nonzero(owners != successor(live, keys)))
    return bad


def run(*, cell, config, traffic, seed, seconds, trace, devices,
        control=False, t0=None, program=None):
    import jax

    Event, RingState = program or _program()
    t0 = time.perf_counter() if t0 is None else t0
    clock = CompileClock()
    out = Outcome()
    rng = gen.rng_for(seed)
    ids = gen.ring_ids(config["peers"], rng)
    interval = gen.message_interval(config)
    msgs = gen.churn_messages(config, ids, int(seconds / interval) + 64, rng)
    batches = gen.key_batches(traffic, ids, rng)
    state = RingState(ids)

    def events(m):
        return [Event(int(p), "leave") for p in m.leaves] + \
               [Event(int(p), "join") for p in m.joins]

    # set-up: the batch shape this cell uses, before and after a message
    state.lookup(batches[0])
    state.apply_events(events(msgs[0]))
    state.lookup(batches[-1])
    applied = 1                        # messages due so far
    table_has = 1                      # messages the table holds
    setup_s = time.perf_counter() - t0
    compiles0 = clock.compiles
    upload0 = state.upload_bytes
    records = []
    apply_s = 0.0
    n_events = 0
    tdir = tempfile.TemporaryDirectory() if trace else None
    tracing = False
    traced = {"keys": 0, "calls": 0, "table": 0}
    trace_for = traffic.get("trace_seconds", seconds)
    i = 0
    start = time.perf_counter()
    end = start + seconds
    due = start + interval
    if trace:
        jax.profiler.start_trace(tdir.name)
        tracing = True
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
    now = start
    while now < end:
        while now >= due and applied < len(msgs):
            applied += 1
            # control: the table lags one message behind what is due
            want = applied - 1 if control else applied
            while table_has < want:
                m = msgs[table_has]
                a = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.apply_events"):
                    state.apply_events(events(m))
                apply_s += time.perf_counter() - a
                n_events += m.joins.size + m.leaves.size
                table_has += 1
            due += interval
        keys = batches[i % len(batches)]
        with jax.profiler.TraceAnnotation("bench.lookup"):
            owners = state.lookup(keys)
        records.append((applied, keys, owners))
        if tracing:
            traced["keys"] += keys.size
            traced["calls"] += 1
            traced["table"] += len(state)
        i += 1
        now = time.perf_counter()
        if tracing and now - start >= trace_for:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
    stop = time.perf_counter()
    if tracing:
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    in_window = clock.compiles - compiles0
    out.device["memory_peak_bytes"] = peak_bytes(devices)
    keys_done = sum(r[1].size for r in records)
    out.attempted = keys_done
    out.e2e = {"setup_s": setup_s,
               traffic["rate_metric"]: keys_done / (stop - start)}
    out.layer = {"apply_s": apply_s, "events": n_events,
                 "upload_bytes": state.upload_bytes - upload0,
                 "traced_keys": traced["keys"], "traced_calls": traced["calls"],
                 "traced_table_ids": traced["table"],
                 "device_kind": devices[0].device_kind}
    if trace:
        ev = xtrace.load(xtrace.find_xplane(tdir.name))
        out.trace = xtrace.reduce(ev, xtrace.window_of(ev))
        tdir.cleanup()
    bad = count_mismatches(ids, msgs, records,
                           load_reference(config["reference"]).successor)
    out.failed = bad
    out.checks = [Check("owner_mismatches", bad,
                        config["limits"]["owner_mismatches"])]
    out.notes = {"cell": cell["name"], "seed": seed, "batches": len(records),
                 "keys": keys_done, "messages_applied": table_has - 1,
                 "events_applied": n_events, "window_s": stop - start,
                 "compiles_in_window": in_window,
                 "compiled_in_window":
                     clock.names[len(clock.names) - in_window:],
                 "compile_s_total": clock.seconds,
                 "persistent_cache_hits": clock.cache_hits,
                 "kernel": state.last_lookup_kernel,
                 "bucket_stats": state.bucket_stats(), "control": control}
    log(f"ring: {len(records)} lookups of {batches[0].size} keys in "
        f"{stop - start:.3f} s; {table_has - 1} messages; "
        f"{in_window} compiles in the window")
    return out
