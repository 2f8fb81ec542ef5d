"""Backend autodetection + provenance shared by the Pallas kernel wrappers.

Pallas kernels compile only for TPU; everywhere else (CPU tests, CI,
interactive runs) they must execute in interpreter mode.  Call sites used
to hardcode ``interpret=True``, which silently kept the *interpreted*
kernel on real TPUs too — production paths now resolve the flag from the
actual backend unless the caller pins it explicitly.

This module is also the single source of truth for benchmark provenance:
every BENCH_*.json derives its ``mode``/``backend`` block from
:func:`provenance` / :func:`mode_label` instead of hardcoding a string
that would silently lie on an accelerator runner.
"""
from __future__ import annotations

from functools import lru_cache
from typing import Optional


@lru_cache(maxsize=1)
def default_interpret() -> bool:
    """True iff Pallas kernels must run interpreted (any non-TPU backend)."""
    import jax

    return jax.default_backend() != "tpu"


def resolve_interpret(interpret) -> bool:
    """``None`` -> autodetect; an explicit bool wins."""
    return default_interpret() if interpret is None else bool(interpret)


def mode_label(interpret: Optional[bool] = None) -> str:
    """Execution-mode string for benchmark provenance, derived from the
    interpret flag a benchmark actually ran with (``None`` = autodetect),
    never hardcoded: ``pallas-interpret-cpu`` on a CPU CI runner,
    ``pallas-compiled-tpu`` on a real accelerator."""
    import jax

    kind = "interpret" if resolve_interpret(interpret) else "compiled"
    return f"pallas-{kind}-{jax.default_backend()}"


def provenance(interpret: Optional[bool] = None) -> dict:
    """Measurement provenance block for BENCH_*.json files.

    Records everything a future reader needs to decide whether two
    benchmark files are comparable: execution mode (interpret vs
    compiled — absolute numbers are NEVER comparable across modes, see
    DESIGN.md §10), backend/device identity, and the jax version."""
    import jax

    dev = jax.devices()[0]
    return {
        "mode": mode_label(interpret),
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "device_count": jax.device_count(),
        "jax_version": jax.__version__,
    }


def _device_memory_bytes() -> Optional[int]:
    """Memory capacity of device 0 via ``memory_stats()``, or None when
    a non-TPU backend does not report it (the CPU reports nothing).  A
    TPU always reports its memory, so a failure there raises instead of
    reading as "unknown"."""
    import jax

    dev = jax.devices()[0]
    on_tpu = getattr(dev, "platform", None) == "tpu"
    try:
        stats = dev.memory_stats()
    except Exception:
        if on_tpu:
            raise
        return None
    stats = stats or {}
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if not limit and on_tpu:
        raise RuntimeError(f"TPU {dev} reported no memory limit: {stats}")
    return int(limit) if limit else None


@lru_cache(maxsize=1)
def bucket_budget_bytes() -> int:
    """Upper bound on the bucketized ring-lookup table (DESIGN.md §7).

    RingState stops escalating the directory — and falls back to the
    flat-scan kernel — once the (B, BW) matrix would outgrow this
    budget.  Interpreted backends (CPU tests, CI) only burn host RAM.

    On a compiled backend the matrix lives in device memory (HBM): the
    kernel's wrapper gathers each query's row there and streams only
    row blocks through VMEM.  The budget is 1/16 of the device's
    reported memory (1 GiB on a 16 GiB v5e, where n = 10^6 takes a
    32 MiB directory of 32,768 rows), with a 1 MB floor.  A backend
    that reports no memory gets 8 MB.
    """
    if default_interpret():
        return 256 << 20
    mem = _device_memory_bytes()
    if mem is None:
        return 8 << 20
    return max(mem // 16, 1 << 20)
