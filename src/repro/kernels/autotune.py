"""Tile-size autotuning for the Pallas kernels (ROADMAP item 3).

A small search-and-cache layer over the five kernels' block/tile sizes
(``ring_lookup``/``ring_lookup_bucketed``, ``edra_tree``,
``decode_attention``, ``flash_attention``, ``ssm_scan``):

  * **Keying** — entries are keyed on ``(backend, kernel, shape bucket)``
    where the shape bucket rounds every dimension up to a power of two,
    so one search covers a whole shape class (a churn-driven q=1000 and
    q=1024 lookup share an entry) and the cache stays small.
  * **Persistence** — winners live in a JSON cache file
    (``$REPRO_AUTOTUNE_CACHE`` or ``.autotune/autotune.json`` in the
    checkout, which git ignores); a searched entry is reused by every
    later process on the same backend.  A corrupt or unreadable cache
    file degrades to the defaults — it can never take the kernels down.
  * **Interpret-mode fallback** — on interpret-only backends (CPU tests,
    CI) there is nothing to tune: :func:`tiles_for` returns the
    hand-picked defaults immediately, with no file I/O, and provenance
    reports ``autotune: "defaults"``.

Resolution (:func:`tiles_for`) NEVER searches — it is called from kernel
wrappers at jit-trace time, where timing a candidate would measure a
tracer.  Searching happens only through the explicit host-level entry
points :func:`autotune_kernel` / :func:`autotune_all`, which benchmarks
and the CI ``compiled-smoke`` job invoke before timing.
"""
from __future__ import annotations

import json
import os
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

from .backend import default_interpret

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
CACHE_VERSION = 1

# Hand-picked defaults — the committed tile constants each kernel shipped
# with.  These are the interpret-mode answer and the safety net for a
# missing/corrupt cache, so every kernel must work at these values.
DEFAULTS: Dict[str, Dict[str, int]] = {
    "ring_lookup": {"bq": 1024, "bt": 2048},
    "ring_lookup_bucketed": {"bq": 1024},
    "edra_tree": {"bp": 2048},
    "decode_attention": {"bs": 256},
    "flash_attention": {"bq": 128, "bk": 128},
    "ssm_scan": {"bd": 256},
}

# Sweep space per kernel.  Small on purpose: tile choices interact weakly
# and the cache amortizes the search across processes.
CANDIDATES: Dict[str, List[Dict[str, int]]] = {
    "ring_lookup": [{"bq": bq, "bt": bt}
                    for bq in (256, 512, 1024, 2048)
                    for bt in (1024, 2048, 4096)],
    "ring_lookup_bucketed": [{"bq": bq} for bq in (256, 512, 1024, 2048)],
    "edra_tree": [{"bp": bp} for bp in (512, 1024, 2048, 4096)],
    "decode_attention": [{"bs": bs} for bs in (128, 256, 512)],
    "flash_attention": [{"bq": bq, "bk": bk}
                        for bq in (128, 256) for bk in (128, 256)],
    "ssm_scan": [{"bd": bd} for bd in (128, 256, 512)],
}

KERNELS = tuple(DEFAULTS)

# process-level record of how tiles were resolved (for provenance)
_resolutions: set = set()

# <checkout>/.autotune (this file is <checkout>/src/repro/kernels/autotune.py)
_DEFAULT_CACHE = Path(__file__).resolve().parents[3] / ".autotune" \
    / "autotune.json"


def _is_interpret() -> bool:  # indirection so tests can monkeypatch
    return default_interpret()


def cache_path() -> str:
    return os.environ.get(CACHE_ENV, str(_DEFAULT_CACHE))


def _backend_key() -> str:
    import jax

    dev = jax.devices()[0]
    return f"{jax.default_backend()}:{getattr(dev, 'device_kind', 'unknown')}"


def shape_bucket(**dims: int) -> str:
    """Canonical shape-class key: every dim rounded up to a power of two
    (0/1 stay as-is), fields sorted for stability."""
    parts = []
    for k in sorted(dims):
        v = int(dims[k])
        if v > 1:
            v = 1 << (v - 1).bit_length()
        parts.append(f"{k}{v}")
    return "_".join(parts)


def _entry_key(kernel: str, bucket: str) -> str:
    return f"{_backend_key()}/{kernel}/{bucket}"


def load_cache(path: Optional[str] = None) -> dict:
    """Parsed cache file; a missing, corrupt, or wrong-version file reads
    as empty (defaults win) instead of raising."""
    path = path or cache_path()
    try:
        with open(path) as f:
            data = json.load(f)
        if not isinstance(data, dict) \
                or data.get("version") != CACHE_VERSION \
                or not isinstance(data.get("entries"), dict):
            return {"version": CACHE_VERSION, "entries": {}}
        return data
    except (OSError, ValueError):
        return {"version": CACHE_VERSION, "entries": {}}


def _save_cache(data: dict, path: Optional[str] = None) -> None:
    path = path or cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    # atomic-ish: never leave a torn file for a concurrent reader
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                               prefix=".autotune-")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


# ---------------------------------------------------------------------------
# Per-kernel tile validity (shape constraints the kernels assert on)
# ---------------------------------------------------------------------------

def _tiles_valid(kernel: str, tiles: Dict[str, int], dims: Dict[str, int]) -> bool:
    if kernel == "decode_attention":
        s = dims.get("s")
        return s is None or s % tiles["bs"] == 0
    if kernel == "flash_attention":
        sq, sk = dims.get("sq"), dims.get("sk")
        return (sq is None or sq % tiles["bq"] == 0) and \
            (sk is None or sk % tiles["bk"] == 0)
    if kernel == "ssm_scan":
        din = dims.get("din")
        return din is None or din % tiles["bd"] == 0
    return True


# ---------------------------------------------------------------------------
# Resolution (trace-time safe: cache/defaults only, never a search)
# ---------------------------------------------------------------------------

def tiles_for(kernel: str, **dims: int) -> Dict[str, int]:
    """Tile sizes for one kernel call.

    Interpret-mode backends get the hand-picked defaults immediately (no
    file I/O on the test/CI hot path).  Compiled backends consult the
    persisted cache for this (backend, kernel, shape-bucket) and fall
    back to the defaults on a miss, an invalid entry (tiles that violate
    the call's shape constraints), or a corrupt cache file.
    """
    base = dict(DEFAULTS[kernel])
    if _is_interpret():
        _resolutions.add("defaults")
        return base
    entry = load_cache().get("entries", {}).get(
        _entry_key(kernel, shape_bucket(**dims)))
    if entry and isinstance(entry.get("tiles"), dict):
        tiles = {k: int(v) for k, v in entry["tiles"].items() if k in base}
        if set(tiles) == set(base) and _tiles_valid(kernel, tiles, dims):
            _resolutions.add("cache")
            return tiles
    _resolutions.add("defaults")
    return base


def status_label() -> str:
    """How tiles were resolved so far this process (for provenance):
    ``defaults`` (interpret mode / no cache hits), ``cache`` (every
    resolution hit the cache), or ``mixed``."""
    if not _resolutions or _resolutions == {"defaults"}:
        return "defaults"
    if "searched" in _resolutions:
        return "searched"
    if _resolutions == {"cache"}:
        return "cache"
    return "mixed"


# ---------------------------------------------------------------------------
# Search (host-level only — called by benchmarks / the compiled-smoke job)
# ---------------------------------------------------------------------------

def _time_candidate(fn: Callable[[], object], reps: int) -> float:
    """Best-rep wall seconds with a warmup call (compile + upload)."""
    import jax

    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _default_bench(kernel: str, dims: Dict[str, int]) -> Callable[[dict], float]:
    """Build a ``bench(tiles) -> seconds`` closure on synthetic inputs of
    the requested shape class (lazy kernel imports keep this module
    import-light)."""
    import numpy as np
    import jax.numpy as jnp

    rng = np.random.default_rng(0)

    if kernel == "ring_lookup":
        from .ring_lookup.kernel import ring_lookup_pallas
        keys = jnp.asarray(rng.integers(0, 2**32, dims["q"], dtype=np.uint32))
        table = jnp.asarray(np.sort(rng.integers(
            0, 2**32, dims["n"], dtype=np.uint32)))
        return lambda t: _time_candidate(
            lambda: ring_lookup_pallas(keys, table, interpret=False, **t), 3)
    if kernel == "ring_lookup_bucketed":
        from .ring_lookup.kernel import BW, ring_lookup_bucketed_pallas
        nb = max(dims.get("b", 64), 64)
        khi = jnp.asarray(rng.integers(0, 2**32, dims["q"], dtype=np.uint32))
        klo = jnp.asarray(rng.integers(0, 2**32, dims["q"], dtype=np.uint32))
        bhi = jnp.asarray(rng.integers(0, 2**32, (nb, BW), dtype=np.uint32))
        blo = jnp.asarray(rng.integers(0, 2**32, (nb, BW), dtype=np.uint32))
        occ = jnp.asarray(rng.integers(1, BW - 1, nb, dtype=np.int32))
        return lambda t: _time_candidate(
            lambda: ring_lookup_bucketed_pallas(
                khi, klo, bhi, blo, occ, interpret=False, **t), 3)
    if kernel == "edra_tree":
        from .edra_tree.kernel import edra_tree_pallas
        p = dims["p"]
        off = jnp.asarray(rng.integers(0, 2**20, p, dtype=np.uint32))
        n = jnp.full(p, 2**20, jnp.uint32)
        rep = jnp.asarray(rng.integers(0, 2**20, p, dtype=np.uint32))
        t0 = jnp.asarray(rng.random(p), jnp.float32)
        key = jnp.asarray(rng.integers(0, 2**32, p, dtype=np.uint32))
        return lambda t: _time_candidate(
            lambda: edra_tree_pallas(off, n, rep, t0, key, levels=20,
                                     theta=1.0, delta_avg=0.1,
                                     interpret=False, **t), 3)
    if kernel == "decode_attention":
        from .decode_attention.kernel import decode_attention_pallas
        b, h, hkv, hd, s = (dims.get("b", 8), dims.get("h", 8),
                            dims.get("hkv", 2), dims.get("hd", 128),
                            dims["s"])
        q = jnp.asarray(rng.standard_normal((b, h, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, s, hkv, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, s, hkv, hd)), jnp.float32)
        ln = jnp.full((b,), s, jnp.int32)
        return lambda t: _time_candidate(
            lambda: decode_attention_pallas(q, k, v, ln, interpret=False,
                                            **t), 3)
    if kernel == "flash_attention":
        from .flash_attention.kernel import flash_attention_pallas
        b, h, hd = dims.get("b", 2), dims.get("h", 8), dims.get("hd", 128)
        sq, sk = dims["sq"], dims["sk"]
        q = jnp.asarray(rng.standard_normal((b, sq, h, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, sk, h, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, sk, h, hd)), jnp.float32)
        return lambda t: _time_candidate(
            lambda: flash_attention_pallas(q, k, v, causal=True,
                                           interpret=False, **t), 3)
    if kernel == "ssm_scan":
        from .ssm_scan.kernel import ssm_scan_pallas
        bb, l, din, n = (dims.get("bb", 2), dims.get("l", 256),
                         dims["din"], dims.get("n", 16))
        x = jnp.asarray(rng.standard_normal((bb, l, din)) * .1, jnp.float32)
        dt = jnp.asarray(np.abs(rng.standard_normal((bb, l, din))) * .1,
                         jnp.float32)
        B = jnp.asarray(rng.standard_normal((bb, l, n)) * .5, jnp.float32)
        C = jnp.asarray(rng.standard_normal((bb, l, n)) * .5, jnp.float32)
        A = jnp.asarray(-np.abs(rng.standard_normal((din, n))) - .1,
                        jnp.float32)
        D = jnp.ones((din,), jnp.float32)
        h0 = jnp.zeros((bb, din, n), jnp.float32)
        return lambda t: _time_candidate(
            lambda: ssm_scan_pallas(x, dt, B, C, A, D, h0, interpret=False,
                                    **t), 3)
    raise KeyError(f"unknown kernel {kernel!r}")


def autotune_kernel(kernel: str, dims: Dict[str, int], *,
                    bench: Optional[Callable[[dict], float]] = None,
                    force: bool = False,
                    path: Optional[str] = None) -> Dict[str, int]:
    """Search the candidate tiles for one (kernel, shape bucket) and
    persist the winner.  A cache hit returns WITHOUT re-searching unless
    ``force``; interpret-only backends return the defaults untouched (no
    search is meaningful against the interpreter).  Raises when the
    compiler refuses every candidate: the kernel cannot run here at all,
    and returning the defaults would hide that."""
    if kernel not in DEFAULTS:
        raise KeyError(f"unknown kernel {kernel!r}")
    if _is_interpret():
        _resolutions.add("defaults")
        return dict(DEFAULTS[kernel])
    bucket = shape_bucket(**dims)
    key = _entry_key(kernel, bucket)
    cache = load_cache(path)
    hit = cache["entries"].get(key)
    if hit and not force and isinstance(hit.get("tiles"), dict):
        _resolutions.add("cache")
        return {k: int(v) for k, v in hit["tiles"].items()}
    bench = bench or _default_bench(kernel, dims)
    cands = [c for c in CANDIDATES[kernel] if _tiles_valid(kernel, c, dims)] \
        or [dict(DEFAULTS[kernel])]
    results: List[Tuple[float, Dict[str, int]]] = []
    errors: List[str] = []
    for cand in cands:
        try:
            results.append((float(bench(cand)), cand))
        except Exception as e:  # a tile the backend rejects is just a loss
            errors.append(f"{cand}: {type(e).__name__}: {e}"[:300])
    if not results:
        raise RuntimeError(
            f"{kernel}: the backend refused every candidate tile for "
            f"{dims}:\n" + "\n".join(errors))
    best_s, best = min(results, key=lambda r: r[0])
    import jax

    cache["entries"][key] = {
        "tiles": best, "us": round(best_s * 1e6, 2),
        "candidates": len(results), "jax": jax.__version__,
    }
    _save_cache(cache, path)
    _resolutions.add("searched")
    return dict(best)


# Representative shape classes for a whole-system sweep (the serve and
# churn planes' operating points).
SWEEP_DIMS: Dict[str, List[Dict[str, int]]] = {
    "ring_lookup": [{"q": 4096, "n": 10**6}],
    "ring_lookup_bucketed": [{"q": 4096, "b": 4096}],
    "edra_tree": [{"p": 1 << 18}],
    "decode_attention": [{"s": 1024}],
    "flash_attention": [{"sq": 1024, "sk": 1024}],
    "ssm_scan": [{"din": 1024}],
}


def autotune_all(*, force: bool = False,
                 budget_s: Optional[float] = None) -> Dict[str, dict]:
    """Sweep every kernel's representative shapes (compiled backends
    only; a no-op returning defaults under interpret).  ``budget_s``
    bounds the total wall time — the CI smoke passes ~30 s."""
    t0 = time.perf_counter()
    out: Dict[str, dict] = {}
    for kernel, shapes in SWEEP_DIMS.items():
        for dims in shapes:
            if budget_s is not None \
                    and time.perf_counter() - t0 > budget_s:
                return out
            out[f"{kernel}/{shape_bucket(**dims)}"] = autotune_kernel(
                kernel, dims, force=force)
    return out
