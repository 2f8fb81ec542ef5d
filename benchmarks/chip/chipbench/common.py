"""Discovery by name, the device check, and the result line.

A cell in ``BENCHMARK.json`` names a configuration and a traffic mix;
each is a JSON file of its own (``configs/<name>.json``,
``traffic/<name>.json``), and each per-layer metric a reader of its own
(``metrics/<name>.py`` with ``read(run) -> float | None``).  The traffic
file names the driver that runs it (``chipbench/<driver>_driver.py``).
Adding a configuration, a mix, a cell or a metric adds files only.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parents[1]          # benchmarks/chip
ROOT = HERE.parents[1]                              # the checkout


class BenchError(RuntimeError):
    """The run cannot produce a result (no device, a missing file)."""


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def load_named(kind: str, name: str, base: Path = HERE) -> dict:
    """``configs`` or ``traffic`` file of that name."""
    path = base / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def metrics_for(bench: dict, cell: str, section: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(name: str, base: Path = HERE):
    path = base / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no reader {path} for per-layer metric {name}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_reference(name: str, base: Path = HERE):
    """A configuration's plain reference, ``references/<name>.py``."""
    path = base / "references" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no reference {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_ref_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(traffic: dict):
    return importlib.import_module(f"chipbench.{traffic['driver']}_driver")


def require_accelerator(chips: int):
    """The devices of a TPU with at least ``chips`` chips, or exit with
    an error and no result: a CPU run never stands in for the chip."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise BenchError(f"JAX found no device: {e}") from None
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devs[0].platform!r} "
                         f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise BenchError(f"cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed path in the checkout
    (or ``$JAX_COMPILATION_CACHE_DIR``), so only a cell's first run in a
    checkout compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Counts backend compiles (and persistent-cache hits) from JAX's
    monitoring events, with the name of each program compiled."""

    def __init__(self):
        import jax

        self.names: List[str] = []
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def peak_bytes(devices) -> Optional[int]:
    """Peak bytes in use on the fullest chip."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (no interpolation across samples)."""
    v = sorted(values)
    if not v:
        return math.nan
    k = max(int(math.ceil(q / 100.0 * len(v))) - 1, 0)
    return float(v[k])


class Check:
    """One number compared with its limit: ok iff value <= limit."""

    def __init__(self, name: str, value: float, limit: float):
        self.name, self.value, self.limit = name, float(value), float(limit)

    @property
    def ok(self) -> bool:
        return self.value <= self.limit

    def as_dict(self) -> dict:
        return {"value": self.value, "limit": self.limit}


class Outcome:
    """What a driver hands back: end-to-end values, the comparison, and
    what per-layer readers need (``layer`` holds counters and spans,
    ``trace`` the reduced device trace of a ``--trace 1`` run)."""

    def __init__(self):
        self.e2e: Dict[str, float] = {}
        self.attempted = 0
        self.failed = 0
        self.checks: List[Check] = []
        self.layer: Dict[str, Any] = {}
        self.trace = None
        self.device: Dict[str, Any] = {}
        self.notes: Dict[str, Any] = {}

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)
