"""The program's own profiler spans (``serve.*``, ``ring.*``, ``kv.*``)
share the trace with the benchmark's ``bench.*`` spans; the reduction
reads the benchmark's alone, so every metric it computes reads the same
gaps with or without them."""
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import xtrace  # noqa: E402

MS = 1e6  # ns


def test_program_spans_stay_out_of_the_reduction(tmp_path):
    import jax

    from repro.core.edra import Event
    from repro.core.ringstate import RingState

    rng = np.random.default_rng(4)
    ids = np.unique(rng.integers(1, 2**63, 3000, dtype=np.uint64))
    state = RingState(ids)
    keys = rng.integers(0, 2**63, 512, dtype=np.uint64)
    state.lookup(keys)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.apply_events"):
            state.apply_events([Event(int(ids[0]), "leave")])
        with jax.profiler.TraceAnnotation("bench.lookup"):
            state.lookup(keys)
    jax.profiler.stop_trace()
    ev = xtrace.load(xtrace.find_xplane(str(tmp_path)))
    assert {n for n, _, _ in ev.host} == {
        "bench.window", "bench.apply_events", "bench.lookup"}
    # a device that idles through the whole window: every gap is put down
    # to a benchmark span or to none, never to a program span
    lo, hi = xtrace.window_of(ev)
    ev.ops["/device:TPU:0"] = [("%ring_lookup64.1 = s32[1] custom-call()",
                                hi - 0.01 * MS, hi)]
    r = xtrace.reduce(ev, (lo, hi))
    assert set(r.gaps_s) <= {"bench.apply_events", "bench.lookup",
                             "outside bench spans"}
    assert sum(r.gaps_s.values()) + r.busy_s == pytest.approx(r.window_s)
