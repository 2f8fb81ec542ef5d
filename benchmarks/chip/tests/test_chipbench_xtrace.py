"""Trace reduction: busy union, idle share, per-name device time and idle
gaps, on a synthesized trace and on a small trace recorded on the CPU."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import xtrace  # noqa: E402

MS = 1e6  # ns


def _events():
    ev = xtrace.Events()
    ev.ops["/device:TPU:0"] = [
        ("%ring_lookup64.1 = s32[1048576]{0} custom-call(s32[1] %copy)", 0, 40 * MS),
        # overlaps the first op: counts once towards busy time
        ("%copy-start.3 = (u32[8]) copy-start(u32[8] %x)", 30 * MS, 50 * MS),
        ("%fusion.12 = bf16[4] fusion(bf16[4] %a), kind=kLoop", 60 * MS, 70 * MS),
        # runs past the window's end: clipped at 100 ms
        ("%decode_attention.6 = bf16[32,2,8,128] custom-call(s32[32] %b)",
         90 * MS, 120 * MS),
    ]
    ev.modules["/device:TPU:0"] = [
        ("jit_ring_lookup64(13205164600072209036)", 0, 50 * MS),
        ("jit_decode_full_fused(16319155854601405807)", 60 * MS, 120 * MS),
    ]
    ev.host = [
        ("bench.window", 0, 100 * MS),
        ("bench.step", 45 * MS, 100 * MS),
        ("bench.submit", 52 * MS, 58 * MS),
    ]
    return ev


def test_names_are_reduced_to_their_base():
    assert xtrace.op_name("%ring_lookup64.1 = s32[1] custom-call()") == \
        "ring_lookup64"
    assert xtrace.op_name("%decode_attention.6 = bf16[1] custom-call()") == \
        "decode_attention"
    assert xtrace.op_name("%while.2 = (s32[]) while()") == "while"
    assert xtrace.module_name("jit_prefill_chunk(7035607981868301707)") == \
        "jit_prefill_chunk"


def test_union_merges_overlaps():
    assert xtrace.union([(5, 8), (0, 3), (2, 4), (8, 9)]) == [(0, 4), (5, 9)]
    assert xtrace.total(xtrace.union([(0, 10), (2, 3)])) == 10


def test_reduce_busy_idle_and_names():
    ev = _events()
    r = xtrace.reduce(ev, xtrace.window_of(ev))
    assert r.window_s == pytest.approx(0.1)
    # busy: [0, 50] + [60, 70] + [90, 100] = 70 ms
    assert r.busy_s == pytest.approx(0.070)
    assert r.idle_share() == pytest.approx(0.3)
    assert r.op_s["ring_lookup64"] == pytest.approx(0.040)
    assert r.op_s["decode_attention"] == pytest.approx(0.010)
    assert r.ops_matching(lambda n: n == "decode_attention") == \
        pytest.approx(0.010)
    assert r.modules_matching(lambda n: n.startswith("jit_ring_lookup")) == \
        pytest.approx(0.050)
    assert r.module_s["jit_decode_full_fused"] == pytest.approx(0.040)
    # gaps: [50, 60] under bench.submit at its midpoint 55, [70, 90]
    # under bench.step
    assert r.gaps_s["bench.submit"] == pytest.approx(0.010)
    assert r.gaps_s["bench.step"] == pytest.approx(0.020)
    bd = r.breakdown()
    assert bd["device_ops"][0][0] == "ring_lookup64"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_busy_is_the_mean_over_chips():
    ev = _events()
    ev.ops["/device:TPU:1"] = [("%fusion.1 = f32[1] fusion()", 0, 100 * MS)]
    r = xtrace.reduce(ev, (0, 100 * MS))
    assert r.chips == 2
    assert r.busy_s == pytest.approx((0.070 + 0.100) / 2)


def test_a_recorded_cpu_trace_has_host_spans_and_no_device(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = xtrace.load(xtrace.find_xplane(str(tmp_path)))
    names = {n for n, _, _ in ev.host}
    assert {"bench.window", "bench.step"} <= names
    assert not ev.ops                      # no TPU plane on the CPU
    r = xtrace.reduce(ev, xtrace.window_of(ev))
    assert r.chips == 0 and r.idle_share() is None
