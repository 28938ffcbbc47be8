"""Runtime sanitizer tier: NaN tripwires + compile-count regression tests.

The compile tests pin the serving-path retrace contract from PRs 5-6: a
BatchServer compiles its sharded solve ONCE and then serves same-shape
chunks from cache, and repeated same-shape ``qniht`` calls never retrace.
Every test uses shapes unique within the suite (odd dims) so a cache
already warmed by another test cannot deflate — or inflate — the counts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.sanitize import CompileCounter, sanitize
from repro.core.niht import qniht
from repro.parallel.batch import BatchServer


def _problem(m, n, s, b, seed):
    rng = np.random.default_rng(seed)
    phi = jnp.asarray(rng.standard_normal((m, n), dtype=np.float32) / np.sqrt(m))
    X = np.zeros((b, n), dtype=np.float32)
    for i in range(b):
        X[i, rng.choice(n, size=s, replace=False)] = rng.standard_normal(s)
    Y = jnp.asarray(X, dtype=phi.dtype) @ phi.T
    return phi, Y


# ---------------------------------------------------------------- counter


def test_compile_counter_counts_fresh_and_cached():
    @jax.jit
    def f(v):
        return jnp.tanh(v) * 3.0

    x = jnp.ones((37,), jnp.float32)  # unique shape: forces a fresh compile
    with CompileCounter() as cc:
        f(x).block_until_ready()
        assert cc.compiles == 1
        cc.mark_warm()
        f(x).block_until_ready()
        f(jnp.zeros((37,), jnp.float32)).block_until_ready()  # same shape: cached
    assert cc.compiles == 1
    assert cc.compiles_since_warm == 0
    assert cc.compile_seconds > 0.0
    assert "compiles_after_warmup=0" in cc.summary()


def test_compile_counter_detects_retrace():
    def g(v):
        return v + 1.0

    with CompileCounter() as cc:
        cc.mark_warm()
        # fresh wrapper per call: the exact bug JL006 lints for
        jax.jit(g)(jnp.ones((41,), jnp.float32)).block_until_ready()  # jaxlint: allow=JL006 -- the test IS the retrace bug
        jax.jit(g)(jnp.ones((41,), jnp.float32)).block_until_ready()  # jaxlint: allow=JL006 -- the test IS the retrace bug
    assert cc.compiles_since_warm == 2


# ---------------------------------------------------------------- sanitize


def test_sanitize_trips_on_nan():
    with sanitize():
        with pytest.raises(FloatingPointError):
            jnp.sqrt(jnp.asarray(-1.0)).block_until_ready()


def test_sanitize_trips_on_inf():
    with sanitize():
        with pytest.raises(FloatingPointError):
            (jnp.asarray(1.0, jnp.float32) / jnp.asarray(0.0, jnp.float32)
             ).block_until_ready()


def test_sanitize_trips_on_cached_op_after_local_scope():
    # a jitted jnp op warmed with the checks off (a shape unique in the
    # suite, so no earlier test compiled it), then a thread-local
    # debug_nans(False) scope (JAX opens one in core.jaxpr_as_fun): the
    # tripwire must still fire on the op's cached dispatch path
    a, b = jnp.full((47,), 2.0, jnp.float32), jnp.zeros((47,), jnp.float32)
    (a / b).block_until_ready()
    with jax.debug_nans(False):
        pass
    with sanitize():
        with pytest.raises(FloatingPointError):
            (a / b).block_until_ready()


def test_sanitize_restores_flags():
    before = (jax.config.jax_debug_nans, jax.config.jax_debug_infs)
    with sanitize():
        assert jax.config.jax_debug_nans and jax.config.jax_debug_infs
    assert (jax.config.jax_debug_nans, jax.config.jax_debug_infs) == before
    # restoration must also survive the tripwire firing
    try:
        with sanitize():
            jnp.log(jnp.asarray(0.0)).block_until_ready()
    except FloatingPointError:
        pass
    assert (jax.config.jax_debug_nans, jax.config.jax_debug_infs) == before


def test_sanitize_allows_intentional_nan_transfer():
    # the niht/batch placeholder idiom: NaN built host-side and transferred
    # is a device_put, not an op — must NOT trip the tripwire
    with sanitize():
        buf = jnp.asarray(np.full((5,), np.nan, np.float32))
        assert bool(jnp.all(jnp.isnan(buf)))


def test_sanitize_threads_counter():
    with sanitize(counter=CompileCounter()) as cc:
        assert isinstance(cc, CompileCounter)
        jax.jit(lambda v: v * 2.0)(jnp.ones((43,), jnp.float32))  # jaxlint: allow=JL006 -- one-shot jit, the compile is the point
    assert cc.compiles >= 1


# ------------------------------------------------------- serving contract


def test_batchserver_compiles_once_for_three_same_shape_chunks():
    """Acceptance criterion: 3 same-shape chunks through a BatchServer ->
    exactly 1 backend compile (the sharded solve), chunks 2-3 pure cache."""
    phi, Y = _problem(m=33, n=65, s=3, b=6, seed=7)
    srv = BatchServer(phi, s=3, n_iters=12, n_devices=1, with_trace=True)
    chunks = [Y[:2], Y[2:4], Y[4:6]]
    with sanitize(counter=CompileCounter()) as cc:
        res = srv.submit(chunks[0])
        jax.block_until_ready(res.x)
        assert cc.compiles == 1, (
            f"expected exactly 1 compile for the first chunk, saw {cc.compiles}")
        cc.mark_warm()
        for chunk in chunks[1:]:
            jax.block_until_ready(srv.submit(chunk).x)
    assert cc.compiles == 1, f"retrace on same-shape chunks: {cc.summary()}"
    assert cc.compiles_since_warm == 0
    assert srv.n_chunks == 3


@pytest.mark.parametrize("backend", ["dense", "packed"])
def test_qniht_no_retrace_on_repeated_same_shape_calls(backend):
    # unique shape per backend so neither call can hit another test's cache
    m, n = (35, 67) if backend == "dense" else (39, 69)
    phi, Y = _problem(m=m, n=n, s=3, b=1, seed=11)
    kw = dict(s=3, n_iters=10, bits_phi=8, bits_y=8, backend=backend,
              requantize="fixed", key=jax.random.PRNGKey(0), with_trace=False)
    y2 = jax.block_until_ready(Y[0] * 0.5)  # built outside the counted window
    jax.block_until_ready(qniht(phi, Y[0], **kw).x)  # warm the cache
    with CompileCounter() as cc:
        jax.block_until_ready(qniht(phi, Y[0], **kw).x)
        jax.block_until_ready(qniht(phi, y2, **kw).x)
    assert cc.compiles == 0, f"{backend} qniht retraced: {cc.summary()}"


def test_batchserver_solve_is_nan_clean_under_sanitizer():
    # the serving path end to end with tripwires armed: recovery of an
    # exactly-sparse problem must not manufacture a single NaN
    phi, Y = _problem(m=45, n=89, s=3, b=2, seed=3)
    with sanitize():
        srv = BatchServer(phi, s=3, n_iters=25, n_devices=1, with_trace=True)
        res = srv.submit(Y)
        jax.block_until_ready(res.x)
    assert np.isfinite(np.asarray(res.x)).all()
