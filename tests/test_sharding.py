"""Logical sharding rules + EDRA collectives (subprocess for multi-device)."""
import subprocess
import sys

import pytest

from repro.sharding import specs as sh


def test_logical_spec_dedups_axes():
    sh.set_mesh(None)
    sh._STATE.rules = dict(sh.DEFAULT_RULES)
    spec = sh.logical_spec("batch", "seq", "heads")
    # no mesh axis may appear twice in one spec
    flat = []
    for e in spec:
        if e is None:
            continue
        flat.extend([e] if isinstance(e, str) else list(e))
    assert len(flat) == len(set(flat))


def test_rules_filtered_to_mesh_axes():
    sh.set_mesh(None, {"batch": ("pod", "data")})
    assert sh._STATE.rules["batch"] == ("pod", "data")
    sh.set_mesh(None)


def test_shard_noop_without_mesh():
    import jax.numpy as jnp
    sh.set_mesh(None)
    x = jnp.ones((4, 4))
    assert sh.shard(x, "batch", None) is x


def test_unknown_logical_axis_raises():
    """A typo in a spec tuple must fail loudly, not silently resolve to
    'replicated' and de-shard the tensor on every mesh."""
    sh.set_mesh(None)
    sh._STATE.rules = dict(sh.DEFAULT_RULES)
    with pytest.raises(KeyError, match="unknown logical axis"):
        sh.logical_spec("batch", "headz")
    with pytest.raises(KeyError, match="unknown logical axis"):
        sh.logical_spec("vocabs")
    assert sh.logical_spec("batch", None, "heads") is not None


def test_shard_noop_inside_tp_context():
    """Inside a TP shard_map body every array is already a per-device
    shard; a GSPMD constraint there would be ill-typed."""
    import jax
    import jax.numpy as jnp
    mesh = jax.make_mesh((1,), ("model",))
    x = jnp.ones((4, 4))
    try:
        sh.set_mesh(mesh)
        with sh.tp_context("model"):
            assert sh.shard(x, "heads", None) is x
    finally:
        sh.set_mesh(None)


RULES_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
from jax.sharding import NamedSharding
from repro.launch.mesh import make_production_mesh
from repro.sharding import specs as sh

for multi_pod in (False, True):
    mesh = make_production_mesh(multi_pod=multi_pod)
    with sh.mesh_context(mesh):
        for axis in sh.DEFAULT_RULES:
            spec = sh.logical_spec(axis)
            # every resolved mesh axis must exist on THIS mesh (and the
            # sharding must construct — NamedSharding validates names)
            for e in spec:
                for a in ([e] if isinstance(e, str) else list(e or ())):
                    assert a in mesh.axis_names, (multi_pod, axis, a)
            NamedSharding(mesh, spec)
        # absent mesh axes are filtered, present ones kept
        batch = sh.logical_spec("batch")
        assert batch[0] == (("pod", "data") if multi_pod else "data"), batch
print("RULES_OK")
"""


@pytest.mark.slow
def test_default_rules_resolve_on_production_meshes():
    """Every DEFAULT_RULES logical axis resolves to a valid PartitionSpec
    under both production meshes (single-pod 16x16 and multi-pod
    2x16x16), with absent axes ('pod' on single-pod) filtered out."""
    out = subprocess.run(
        [sys.executable, "-c", RULES_SCRIPT],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "PYTHONPATH": "src"})
    assert "RULES_OK" in out.stdout, out.stderr[-2000:]


COLLECTIVE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.sharding.collectives import (edra_allgather, edra_broadcast,
                                        edra_allreduce)
mesh = jax.make_mesh((8,), ("d",))
x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
ag = jax.shard_map(partial(edra_allgather, axis_name="d"), mesh=mesh,
                   in_specs=P("d", None), out_specs=P("d", None, None),
                   check_vma=False)
got = np.asarray(ag(x)).reshape(8, 8, 4)
for i in range(8):
    assert (got[i].squeeze() == np.asarray(x)).all()
for src in (0, 3, 7):
    bc = jax.shard_map(partial(edra_broadcast, axis_name="d", source=src),
                       mesh=mesh, in_specs=P("d", None),
                       out_specs=P("d", None), check_vma=False)
    got = np.asarray(bc(x))
    assert (got == np.tile(np.asarray(x)[src], (8, 1))).all()
ar = jax.shard_map(partial(edra_allreduce, axis_name="d"), mesh=mesh,
                   in_specs=P(None, None), out_specs=P(None, None),
                   check_vma=False)
y = jnp.arange(5 * 3, dtype=jnp.float32).reshape(5, 3)
assert np.allclose(np.asarray(ar(y)), np.asarray(y) * 8)
print("COLLECTIVES_OK")
"""


@pytest.mark.slow
def test_edra_collectives_8dev():
    out = subprocess.run(
        [sys.executable, "-c", COLLECTIVE_SCRIPT],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "PYTHONPATH": "src"})
    assert "COLLECTIVES_OK" in out.stdout, out.stderr[-2000:]


EDRA_GRADSYNC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.sharding.collectives import edra_allreduce

# data-parallel gradient sync via the paper's dissemination tree:
# per-shard grads -> reduce-scatter + EDRA-tree all-gather == psum
mesh = jax.make_mesh((8,), ("data",))
w = jnp.asarray(np.random.default_rng(0).standard_normal((16, 8)),
                jnp.float32)
x = jnp.asarray(np.random.default_rng(1).standard_normal((32, 16)),
                jnp.float32)
y = jnp.asarray(np.random.default_rng(2).standard_normal((32, 8)),
                jnp.float32)

def local_grad(w_, x_, y_):
    # per-shard loss grad (batch shard), then EDRA-tree sync
    g = jax.grad(lambda wt: jnp.mean((x_ @ wt - y_) ** 2))(w_)
    return edra_allreduce(g, "data") / 8.0

sm = jax.shard_map(local_grad, mesh=mesh,
                   in_specs=(P(None, None), P("data", None),
                             P("data", None)),
                   out_specs=P(None, None), check_vma=False)
step = jax.jit(sm)
g_edra = np.asarray(step(w, x, y))
g_ref = np.asarray(jax.grad(lambda wt: jnp.mean((x @ wt - y) ** 2))(w))
assert np.allclose(g_edra, g_ref, atol=1e-5), np.abs(g_edra - g_ref).max()
# schedule check: the EDRA path lowers to ppermute rounds, not all-gather
hlo = jax.jit(sm).lower(w, x, y).compile().as_text()
assert "collective-permute" in hlo
print("EDRA_GRADSYNC_OK")
"""


@pytest.mark.slow
def test_edra_gradient_sync_equals_psum():
    """DP gradient sync through the paper's dissemination tree (DESIGN.md
    §2 level 2) matches the exact data-parallel gradient, and lowers to
    the ppermute recursive-doubling schedule."""
    out = subprocess.run(
        [sys.executable, "-c", EDRA_GRADSYNC_SCRIPT],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "PYTHONPATH": "src"})
    assert "EDRA_GRADSYNC_OK" in out.stdout, out.stderr[-2000:]
