"""Named scopes on the solver's hot regions, and the solver's own count of
the iterations it ran (``IHTResult.iters_run``).

The scopes are trace-time metadata: each must reach the ``op_name`` of the
lowered and the compiled program as a whole ``/``-separated component, which
is what a profiler trace records (as ``tf_op``) and what ``bench/scopes.py``
matches. The count must be ``n_iters`` without early exit and the early-exit
loop's trip count with it, through every entry point that returns a result.
"""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import qniht, qniht_batch, solver_init, solver_segment
from repro.core.operators import (
    ComposedOperator,
    PackedStreamingOperator,
    SubsampledFourierOperator,
    WaveletSynthesisOperator,
)
from repro.parallel import BatchServer, make_batch_mesh
from repro.sensing import make_gaussian_problem

SOLVER_SCOPES = {"mv", "rmv", "threshold", "backtrack"}
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lowered_scopes(text):
    """Path components of every op name in MLIR text with debug info."""
    return {c for p in re.findall(r'loc\("([^"]+)"', text) if "/" in p
            for c in p.split("/")}


def _compiled_scopes(text):
    return {c for p in re.findall(r'op_name="([^"]+)"', text) for c in p.split("/")}


def _packed_solve():
    rng = np.random.default_rng(0)
    phi = jnp.asarray(rng.standard_normal((24, 64)) + 1j * rng.standard_normal((24, 64)),
                      jnp.complex64)
    op = PackedStreamingOperator.pack(phi, 8, jax.random.PRNGKey(0), interpret=True)
    solve = jax.jit(lambda Y: qniht_batch(op, Y, 5, 4, real_signal=True, nonneg=True,
                                          requantize="fixed", early_exit=True,
                                          with_trace=False))
    return solve, jnp.ones((8, 24), jnp.complex64)


def _mri_solve():
    r = 16
    mask = np.zeros((r, r), bool)
    mask.flat[np.random.default_rng(1).choice(r * r, 100, replace=False)] = True
    op = ComposedOperator(SubsampledFourierOperator.from_mask(mask),
                          WaveletSynthesisOperator(r, "haar"))
    solve = jax.jit(lambda Y: qniht_batch(op, Y, 20, 4, real_signal=True,
                                          requantize="fixed", early_exit=True,
                                          with_trace=False))
    return solve, jnp.ones((4, op.shape[0]), jnp.complex64)


@pytest.mark.parametrize("name, build, scopes", [
    ("packed LOFAR", _packed_solve, SOLVER_SCOPES | {"qmm_pad"}),
    ("composed MRI", _mri_solve, SOLVER_SCOPES | {"fft", "sample", "dwt"}),
])
def test_scopes_reach_lowered_and_compiled_op_names(name, build, scopes):
    solve, Y = build()
    lowered = solve.lower(Y)
    assert scopes <= _lowered_scopes(lowered.as_text(debug_info=True)), name
    assert scopes <= _compiled_scopes(lowered.compile().as_text()), name


def test_scopes_nest_backtrack_holds_its_applications():
    solve, Y = _mri_solve()
    names = re.findall(r'op_name="([^"]+)"', solve.lower(Y).compile().as_text())
    inner = [n.split("/") for n in names if "backtrack" in n.split("/")]
    assert any("mv" in c and "dwt" in c for c in inner)
    assert any("threshold" in c for c in inner)


def test_yquant_scope_reaches_lowered_and_compiled_op_names():
    """The per-band observation quantizer runs under the ``yquant`` scope."""
    from repro.sensing import quantize_observations

    r = 16
    mask = np.zeros((r, r), bool)
    mask.flat[np.random.default_rng(2).choice(r * r, 100, replace=False)] = True
    op = SubsampledFourierOperator.from_mask(mask)
    quant = jax.jit(lambda Y: quantize_observations(Y, 8, jax.random.PRNGKey(0),
                                                    granularity="per_band", op=op,
                                                    n_bands=4))
    lowered = quant.lower(jnp.ones((4, op.shape[0]), jnp.complex64))
    assert "yquant" in _lowered_scopes(lowered.as_text(debug_info=True))
    assert "yquant" in _compiled_scopes(lowered.compile().as_text())


def _easy_batch(B=4, m=40, n=80, s=3):
    key = jax.random.PRNGKey(11)
    base = make_gaussian_problem(m, n, s, None, key)
    Y = jnp.stack([make_gaussian_problem(m, n, s, None, jax.random.fold_in(key, b + 1),
                                         phi=base.phi).y for b in range(B)])
    return base.phi, Y


def _trip_count(phi, Y, s, n_iters):
    """The early-exit loop's trip count, counted without it: one iteration
    at a time, up to and including the first after which every row is at a
    bitwise fixed point."""
    state = solver_init(phi, Y, s, n_iters, with_trace=False)
    for k in range(n_iters):
        nxt = solver_segment(phi, state, 1, s=s, with_trace=False)
        if bool(jnp.all(nxt.X == state.X)):
            return k + 1
        state = nxt
    return n_iters


def test_iters_run_is_n_iters_without_early_exit():
    phi, Y = _easy_batch()
    res = qniht_batch(phi, Y, 3, 30, with_trace=False)
    assert res.iters_run.dtype == jnp.int32 and res.iters_run.shape == (4,)
    np.testing.assert_array_equal(np.asarray(res.iters_run), 30)
    one = qniht(phi, Y[0], 3, 30, with_trace=False)
    assert one.iters_run.shape == () and int(one.iters_run) == 30


def test_iters_run_is_the_trip_count_under_early_exit():
    phi, Y = _easy_batch()
    trips = _trip_count(phi, Y, 3, 60)
    assert trips < 60
    res = qniht_batch(phi, Y, 3, 60, early_exit=True, with_trace=False)
    np.testing.assert_array_equal(np.asarray(res.iters_run), trips)
    full = qniht_batch(phi, Y, 3, 60, with_trace=False)
    assert bool(jnp.all(res.x == full.x))


def test_batch_server_returns_iters_run_and_zeros_for_a_drained_chunk(tmp_path):
    phi, Y = _easy_batch()
    trips = _trip_count(phi, Y, 3, 60)
    kw = dict(s=3, n_iters=60, mesh=make_batch_mesh(1), journal_dir=str(tmp_path))
    live = BatchServer(phi, **kw).submit(Y)
    np.testing.assert_array_equal(np.asarray(live.iters_run), trips)
    drained = BatchServer(phi, resume=True, **kw).submit(Y)
    assert bool(jnp.all(drained.x == live.x))
    assert drained.iters_run.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(drained.iters_run), np.zeros(4, np.int32))


_TWO_DEVICES = r"""
import json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp, numpy as np
from repro.core import qniht_batch
from repro.parallel import BatchServer, make_batch_mesh
from repro.parallel.batch import sharded_qniht_run
from repro.sensing import make_gaussian_problem

key = jax.random.PRNGKey(11)
base = make_gaussian_problem(40, 80, 3, None, key)
easy = [make_gaussian_problem(40, 80, 3, None, jax.random.fold_in(key, b + 1),
                              phi=base.phi).y for b in range(3)]
hard = [make_gaussian_problem(40, 80, 12, 10.0, jax.random.fold_in(key, b + 9),
                              phi=base.phi).y for b in range(2)]
# five rows on two shards: the easy three on one, the hard two and a pad row
# on the other
Y = jnp.stack(easy + hard)
kw = dict(s=3, n_iters=60, with_trace=False)
shard1 = jnp.concatenate([Y[3:], jnp.zeros_like(Y[:1])])
own = [int(qniht_batch(base.phi, rows, early_exit=True, requantize="fixed",
                       **kw).iters_run[0]) for rows in (Y[:3], shard1)]
statics = dict(kw, bits_phi=None, bits_y=None, requantize="fixed", backend="dense",
               threshold="topk", c=0.01, shrink_k=2.0, max_backtracks=30,
               real_signal=False, nonneg=False, scale_granularity="per_tensor",
               group_size=None, early_exit=True, exit_tol=0.0, unroll=1)
res = sharded_qniht_run(base.phi, Y, None, mesh=make_batch_mesh(2), **statics)
got = np.asarray(res.iters_run).tolist()
assert got == [own[0]] * 3 + [own[1]] * 2, (got, own)
srv = BatchServer(base.phi, mesh=make_batch_mesh(2), **kw)
assert np.asarray(srv.submit(Y).iters_run).tolist() == got
print("ITERS_RUN_2DEV", json.dumps(own))
"""


def test_iters_run_per_shard_on_two_host_devices():
    """Per-shard early exit: rows report the trip count of the shard that
    solved them, through the sharded runner (padding stripped: five rows on
    two shards) and ``BatchServer.submit``."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", _TWO_DEVICES], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    own = json.loads(res.stdout.strip().split("ITERS_RUN_2DEV ", 1)[1])
    assert own[0] != own[1], "the two shards should exit at different iterations"
