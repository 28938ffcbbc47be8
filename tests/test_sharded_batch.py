"""Sharded batch serving tests (repro.parallel.batch + qniht_batch_sharded).

Fast tier: single-device ``("batch",)`` meshes exercise the full shard_map
plumbing (specs, padding arithmetic, the early-exit while_loop, BatchServer)
in-process without touching the global device view. The multi-device parity
matrix — packed / Fourier / composed-wavelet operators on a real 8-host-device
mesh, B-not-divisible padding, freeze-rule grouping invariance — runs in a
subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (slow
tier), per the dry-run rule that the main pytest process keeps one device.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import qniht_batch, qniht_batch_sharded
from repro.parallel import BatchServer, make_batch_mesh
from repro.sensing import make_gaussian_problem


def _gaussian_batch(B=6, m=64, n=128, s=6, snr=20.0, key=None):
    key = key if key is not None else jax.random.PRNGKey(0)
    base = make_gaussian_problem(m, n, s, snr, key)
    Y = jnp.stack([
        make_gaussian_problem(m, n, s, snr, jax.random.fold_in(key, b + 1),
                              phi=base.phi).y
        for b in range(B)
    ])
    return base.phi, Y


class TestEarlyExit:
    def test_exact_rule_bit_identical(self):
        """early_exit (tol=0) reproduces the no-early-exit run bit-for-bit,
        trace included (a bitwise fixed point is absorbing)."""
        phi, Y = _gaussian_batch()
        r0 = qniht_batch(phi, Y, 6, 40)
        r1 = qniht_batch(phi, Y, 6, 40, early_exit=True)
        assert bool(jnp.all(r0.x == r1.x))
        for a, b in zip(r0.trace, r1.trace):
            np.testing.assert_array_equal(np.nan_to_num(np.asarray(a)),
                                          np.nan_to_num(np.asarray(b)))

    def test_exact_rule_packed_backend(self):
        phi, Y = _gaussian_batch()
        key = jax.random.PRNGKey(3)
        kw = dict(bits_phi=4, bits_y=8, key=key, requantize="fixed",
                  backend="packed", with_trace=False)
        r0 = qniht_batch(phi, Y, 6, 30, **kw)
        r1 = qniht_batch(phi, Y, 6, 30, early_exit=True, **kw)
        assert bool(jnp.all(r0.x == r1.x))

    def test_freeze_rule_preserves_recovery_quality(self):
        """The freeze rule is a heuristic (a row on a long saddle plateau may
        freeze short of a late support escape), so the guarantee is quality:
        frozen recovery error stays within a whisker of the full run's."""
        key = jax.random.PRNGKey(0)
        base = make_gaussian_problem(64, 128, 6, 20.0, key)
        probs = [make_gaussian_problem(64, 128, 6, 20.0,
                                       jax.random.fold_in(key, b + 1),
                                       phi=base.phi) for b in range(6)]
        Y = jnp.stack([p.y for p in probs])
        X_true = jnp.stack([p.x_true for p in probs])
        r0 = qniht_batch(base.phi, Y, 6, 40, with_trace=False)
        r1 = qniht_batch(base.phi, Y, 6, 40, with_trace=False, early_exit=True,
                         exit_tol=1e-5)

        def errs(r):
            return jnp.linalg.norm(r.x - X_true, axis=-1) / (
                jnp.linalg.norm(X_true, axis=-1) + 1e-30)

        e0, e1 = errs(r0), errs(r1)
        assert float(jnp.max(e1 - e0)) < 0.05
        assert float(jnp.mean(e1)) < float(jnp.mean(e0)) + 0.01

    def test_validation(self):
        phi, Y = _gaussian_batch(B=2)
        key = jax.random.PRNGKey(0)
        with pytest.raises(ValueError, match="pair"):
            qniht_batch(phi, Y, 6, 5, bits_phi=4, bits_y=8, key=key,
                        requantize="pair", early_exit=True)
        with pytest.raises(ValueError, match="exit_tol"):
            qniht_batch(phi, Y, 6, 5, exit_tol=1e-5)


class TestShardedSingleDeviceMesh:
    """The shard_map path on a width-1 mesh — full plumbing, fast tier."""

    def test_parity_and_padding(self):
        phi, Y = _gaussian_batch(B=5)
        r0 = qniht_batch(phi, Y, 6, 30)
        r1 = qniht_batch_sharded(phi, Y, 6, 30, n_devices=1)
        assert r1.x.shape == r0.x.shape
        assert bool(jnp.all(r0.x == r1.x))
        assert bool(jnp.all(r0.trace.mu == r1.trace.mu))

    def test_operator_input(self):
        from repro.core import SubsampledFourierOperator
        from repro.sensing import make_mri_problem

        key = jax.random.PRNGKey(1)
        prob = make_mri_problem(16, 20, 0.5, key, snr_db=None)
        assert isinstance(prob.op, SubsampledFourierOperator)
        Y = jnp.stack([prob.y, prob.y * 0.5])
        r0 = qniht_batch(prob.op, Y, 20, 10, real_signal=True, nonneg=True)
        r1 = qniht_batch_sharded(prob.op, Y, 20, 10, n_devices=1,
                                 real_signal=True, nonneg=True)
        assert bool(jnp.all(r0.x == r1.x))

    def test_rejects_wrong_mesh_axes(self):
        from jax.sharding import Mesh

        phi, Y = _gaussian_batch(B=2)
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
        with pytest.raises(ValueError, match="batch"):
            qniht_batch_sharded(phi, Y, 6, 5, mesh=mesh)

    def test_rejects_1d_y(self):
        phi, Y = _gaussian_batch(B=2)
        with pytest.raises(ValueError, match="B, M"):
            qniht_batch_sharded(phi, Y[0], 6, 5)

    def test_too_many_devices(self):
        with pytest.raises(ValueError, match="device"):
            make_batch_mesh(n_devices=4096)


class TestBatchServer:
    def test_prepack_parity_with_packed_backend(self):
        """Server rows == qniht_batch(backend='packed') rows, same key — the
        pack-once path reproduces the in-loop pack bit-for-bit."""
        phi, Y = _gaussian_batch()
        key = jax.random.PRNGKey(0)
        ref = qniht_batch(phi, Y, 6, 30, bits_phi=4, bits_y=8, key=key,
                          requantize="fixed", backend="packed", with_trace=False)
        srv = BatchServer(phi, 6, 30, bits_phi=4, bits_y=8, key=key,
                          backend="packed")
        got = srv.submit(Y, key)
        assert bool(jnp.all(ref.x == got.x))

    def test_multi_chunk_stream(self):
        phi, Y = _gaussian_batch(B=4)
        srv = BatchServer(phi, 6, 15)
        outs = list(srv.serve([Y, Y * 0.5, Y]))
        assert len(outs) == 3
        assert srv.n_chunks == 3 and srv.n_items == 12
        assert srv.compile_cache_keys == ((4, 64),)
        # same chunk twice → identical results (stateless per chunk)
        assert bool(jnp.all(outs[0].x == outs[2].x))

    def test_server_validates_config(self):
        phi, _ = _gaussian_batch(B=2)
        with pytest.raises(ValueError, match="bits_phi"):
            BatchServer(phi, 6, backend="packed")


_MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import qniht_batch, qniht_batch_sharded
from repro.parallel import BatchServer, make_batch_mesh
from repro.sensing import make_gaussian_problem, make_mri_problem

key = jax.random.PRNGKey(0)
B = 10  # not divisible by 4 or 8 -> padding in play
base = make_gaussian_problem(48, 96, 5, 20.0, key)
Y = jnp.stack([make_gaussian_problem(48, 96, 5, 20.0, jax.random.fold_in(key, b + 1),
                                     phi=base.phi).y for b in range(B)])

# dense f32, 4-device mesh, padded
r0 = qniht_batch(base.phi, Y, 5, 25)
r4 = qniht_batch_sharded(base.phi, Y, 5, 25, n_devices=4)
assert r4.x.shape == r0.x.shape
assert bool(jnp.all(r0.x == r4.x)), "dense parity"
assert bool(jnp.all(r0.trace.mu == r4.trace.mu)), "dense trace parity"

# packed backend: per_tensor and per_block granularities, 8-device mesh
for gran, gs in (("per_tensor", None), ("per_block", 8)):
    kw = dict(bits_phi=4, bits_y=8, key=key, requantize="fixed",
              backend="packed", with_trace=False,
              scale_granularity=gran, group_size=gs)
    a = qniht_batch(base.phi, Y, 5, 25, **kw)
    b = qniht_batch_sharded(base.phi, Y, 5, 25, n_devices=8, **kw)
    assert bool(jnp.all(a.x == b.x)), f"packed {gran} parity"

# matrix-free Fourier and composed-wavelet operators, 8-device mesh
for basis in ("pixel", "haar"):
    prob = make_mri_problem(16, 24, 0.5, key, snr_db=None, sparsity_basis=basis)
    Ym = jnp.stack([prob.y * (1.0 + 0.1 * t) for t in range(6)])
    kw = dict(real_signal=True, nonneg=basis == "pixel", bits_y=8, key=key,
              with_trace=False)
    a = qniht_batch(prob.op, Ym, 24, 12, **kw)
    b = qniht_batch_sharded(prob.op, Ym, 24, 12, n_devices=8, **kw)
    assert bool(jnp.all(a.x == b.x)), f"operator parity ({basis})"

# freeze rule: grouping-invariant (2-device == 8-device == single-device)
t1 = qniht_batch(base.phi, Y, 5, 25, early_exit=True, exit_tol=1e-5,
                 with_trace=False)
t2 = qniht_batch_sharded(base.phi, Y, 5, 25, n_devices=2, exit_tol=1e-5,
                         with_trace=False)
t8 = qniht_batch_sharded(base.phi, Y, 5, 25, n_devices=8, exit_tol=1e-5,
                         with_trace=False)
assert bool(jnp.all(t1.x == t2.x)) and bool(jnp.all(t1.x == t8.x)), "freeze parity"

# multi-chunk server on a 4-device mesh
srv = BatchServer(base.phi, 5, 25, mesh=make_batch_mesh(4))
outs = list(srv.serve([Y, Y]))
assert len(outs) == 2 and srv.n_items == 2 * B
assert bool(jnp.all(outs[0].x == outs[1].x))
assert bool(jnp.all(outs[0].x == qniht_batch(base.phi, Y, 5, 25,
                                             with_trace=False, early_exit=True).x))
print("SHARDED_MULTIDEV_OK")
"""


@pytest.mark.slow
def test_sharded_parity_8dev_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    res = subprocess.run(
        [sys.executable, "-c", _MULTIDEV_SCRIPT], env=env, capture_output=True,
        text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert "SHARDED_MULTIDEV_OK" in res.stdout
