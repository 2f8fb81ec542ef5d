"""chip_smoke.py's phases at tiny sizes on the CPU, Pallas kernels in
interpret mode, smoke model preset; and its refusal to run off a TPU."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_smoke_config

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"
SERVE = dict(members=2050, slots=4, max_len=256, count=4,
             prompt_lens=(20, 40), new_tokens=6, kill_after=2, seed=0)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("n, kernel", [(10_000, "bucketed"), (1_000, "flat")])
def test_ring_phase(smoke, n, kernel):
    r = smoke.phase_ring(n, 4096, seed=0)
    for tag in ("before", "after"):
        assert r[tag]["mismatches"] == 0
        assert r[tag]["kernel"] == kernel
    assert sum(r["events"][k] for k in ("join", "leave", "crash")) == 64
    assert r["events"]["slots_changed"] == 64


def test_churn_phase(smoke):
    r = smoke.phase_churn(4096, seed=0, n=40_960)
    assert r["boundary_ties"] == r["ack_off_oracle"]
    assert r["ttl_mismatches"] == r["parent_mismatches"] == 0


def test_serve_phase(smoke):
    # the decode kernel is pinned on: auto dispatch keeps the jnp path
    # off-TPU, and the phase asserts the kernel is in the decode program
    r = smoke.phase_serve(get_smoke_config("qwen2.5-3b"),
                          decode_kernel=True, **SERVE)
    assert r["decode_kernel_in_program"]
    assert r["fused_rounds"] > 0 and r["route_divergence"] == 0
    assert r["stats"]["handoffs"] == r["killed_owner_sessions"] >= 2
    assert r["first_step_logits"]["max_abs_diff"] \
        <= r["first_step_logits"]["bound"]


def test_tp_phase_on_host_devices():
    """tp=2 groups on four host devices, in a child process (the device
    count is fixed when JAX starts)."""
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {str(SCRIPT.parent)!r})\n"
        "import chip_smoke\n"
        "from repro.configs import get_smoke_config\n"
        "r = chip_smoke.phase_tp(get_smoke_config('qwen2.5-3b'), tp=2, "
        f"**{SERVE!r})\n"
        "print('TP:' + json.dumps(r))\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    line = next(ln for ln in out.stdout.splitlines() if ln.startswith("TP:"))
    r = json.loads(line[3:])
    groups = r["groups"]
    assert not set(groups["0"]["params"]) & set(groups["1"]["params"])
    assert r["killed_group"] not in r["migrated_to_groups"]


def test_script_refuses_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, str(SCRIPT)], capture_output=True,
                         text=True, timeout=300, env=env, cwd=SCRIPT.parent)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout
