"""Multi-device collective tests — run in a subprocess with 8 host devices so
the main pytest process keeps its single-device view (per the dry-run rules).

``test_qgrad_allreduce_host_mesh`` is the fast in-process regression for the
``jax.shard_map`` entry point, so an import/dispatch break surfaces in the
quick tier, not only in the slow subprocess test."""
import os
import subprocess
import sys

import numpy as np
import pytest


def test_qgrad_allreduce_host_mesh():
    import jax
    from jax.sharding import Mesh
    from repro.parallel.collectives import make_qgrad_allreduce

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1,), ("pod",))
    key = jax.random.PRNGKey(0)
    tree = {"w": jax.random.normal(key, (1, 16)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (1, 4))}
    out = make_qgrad_allreduce(mesh, "pod", 8)(tree, jax.random.fold_in(key, 2))
    for k in tree:
        exp = np.asarray(tree[k]).mean(0)
        got = np.asarray(out[k])[0]
        scale = np.abs(np.asarray(tree[k])).max()
        assert np.abs(got - exp).max() <= scale / 64, k

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.parallel.collectives import make_qgrad_allreduce

mesh = Mesh(np.array(jax.devices()).reshape(8,), ("pod",))
key = jax.random.PRNGKey(0)
tree = {"w": jax.random.normal(key, (8, 16)),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (8, 4))}
ar = make_qgrad_allreduce(mesh, "pod", 8)
out = ar(tree, jax.random.fold_in(key, 2))
for k in tree:
    exp = np.asarray(tree[k]).mean(0)
    got = np.asarray(out[k])[0]
    scale = np.abs(np.asarray(tree[k])).max()
    assert np.abs(got - exp).max() <= scale / 64, k

# elasticity: the same pytree under a 4-device sub-mesh still reduces correctly
mesh4 = Mesh(np.array(jax.devices()[:4]).reshape(4,), ("pod",))
tree4 = {"w": jax.random.normal(key, (4, 16))}
out4 = make_qgrad_allreduce(mesh4, "pod", 8)(tree4, key)
exp4 = np.asarray(tree4["w"]).mean(0)
assert np.abs(np.asarray(out4["w"])[0] - exp4).max() <= float(np.abs(np.asarray(tree4["w"])).max()) / 64

# sharded-batch training sanity: pjit a tiny step over a (2, 4) mesh
from repro.configs import get_smoke_config
from repro.optim import adamw
from repro.train import init_state, make_train_step
from repro.train.steps import build_sharded_train_step
cfg = get_smoke_config("starcoder2_3b")
mesh2 = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
opt = adamw(1e-3)
step, st_sh = build_sharded_train_step(cfg, mesh2, opt, global_batch=8)
state = init_state(cfg, opt, key)
state = jax.device_put(state, st_sh)
batch = {
    "tokens": jnp.zeros((8, 32), jnp.int32),
    "labels": jnp.zeros((8, 32), jnp.int32),
    "memory": None,
}
state, metrics = step(state, batch)
assert jnp.isfinite(metrics["loss"])
print("MULTIDEVICE_OK")
"""


@pytest.mark.slow
def test_quantized_allreduce_and_sharded_step_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, capture_output=True, text=True,
        timeout=420, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert "MULTIDEVICE_OK" in res.stdout
