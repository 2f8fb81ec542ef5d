"""Profiler spans of the serve loop and the routing table.

Each span is a ``jax.profiler.TraceAnnotation``: it lands on the
profiler's host clock beside the device's ops, so a trace puts each gap
in which the device idles down to the program span that held it.  The
names below are what a trace reader matches; spans of one request carry
the same ``session`` metadata.

With no profiler recording, a span costs one Python object and a
disabled TraceMe, and metadata is dropped before it is encoded.  The
profiler is looked up only once jax is loaded: a process without it has
no profiler to record into, and there a span is a null context, so
``core/ringstate.py`` stays usable without jax.
"""
from __future__ import annotations

import sys
from contextlib import nullcontext

# replica engine (serve/cluster.py, serve/server.py)
SERVE_STEP = "serve.step"                # one round of ServeCluster.step
SERVE_MIGRATE = "serve.migrate"          # re-homes due this round
SERVE_PREFILL_ADVANCE = "serve.prefill_advance"
SERVE_DECODE_ROUND = "serve.decode_round"
SERVE_UPLOAD_SLOTS = "serve.upload_slots"    # tokens, lengths, idx, keys
SERVE_DISPATCH = "serve.dispatch"            # the jitted decode program
SERVE_DEVICE_GET = "serve.device_get"        # the round's one transfer
SERVE_PUSH_TOKENS = "serve.push_tokens"      # push, eviction, block drops
SERVE_SUBMIT = "serve.submit"
SERVE_ROUTE = "serve.route"
SERVE_ADMIT = "serve.admit"
SERVE_HANDOFF = "serve.handoff"
# KV block store traffic of the serve plane
KV_EXPORT = "kv.export"
KV_FETCH = "kv.fetch"
KV_IMPORT = "kv.import"
# routing table (core/ringstate.py)
RING_APPLY_EVENTS = "ring.apply_events"
RING_LOOKUP = "ring.lookup"
RING_UPLOAD_KEYS = "ring.upload_keys"
RING_TABLE_UPLOAD = "ring.table_upload"  # a device table re-shipped
RING_KERNEL = "ring.kernel"              # dispatch and wait
RING_OWNERS = "ring.owners"              # host gather of owner ids

_profiler = None


def _traceme():
    global _profiler
    if _profiler is None and "jax" in sys.modules:
        from jax import profiler
        _profiler = profiler
    return _profiler


def span(name: str, **meta):
    """A host span; ``meta`` is recorded only while a profiler is on."""
    p = _traceme()
    if p is None:
        return nullcontext()
    if meta and p.TraceAnnotation.is_enabled():
        return p.TraceAnnotation(name, **meta)
    return p.TraceAnnotation(name)


def step_span(name: str, step_num: int):
    """A step span: the profiler groups the trace into numbered steps."""
    p = _traceme()
    if p is None:
        return nullcontext()
    return p.StepTraceAnnotation(name, step_num=step_num)


def note(sp, **meta) -> None:
    """Metadata known only inside the span (counts found on the way)."""
    p = _traceme()
    if meta and p is not None and p.TraceAnnotation.is_enabled():
        sp.set_metadata(**meta)
