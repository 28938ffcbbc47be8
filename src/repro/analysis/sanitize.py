"""Runtime sanitizer: NaN/Inf tripwires + a backend-compile counter.

The static half of this package catches what source *looks like*; this half
asserts what a run actually *did*:

* :func:`sanitize` — context manager flipping ``jax_debug_nans`` /
  ``jax_debug_infs`` on (restoring the previous values on exit), so any NaN
  or Inf produced anywhere — inside jit, inside shard_map, in eager ops —
  raises ``FloatingPointError`` at the producing primitive instead of
  surfacing three layers later as a garbage recovery.

* :class:`CompileCounter` — counts *backend compiles* (actual XLA
  compilations, observed via ``jax.monitoring``'s
  ``/jax/core/compile/backend_compile_duration`` event), not Python-side
  trace entries. ``mark_warm()`` after warm-up lets callers assert the
  serving layer's contract literally: ``compiles_since_warm == 0`` means
  every later chunk reused the executable. Counting compiles rather than
  cache *hits* makes the assertion robust to jit caches pre-warmed by
  earlier tests in the same process.

Used by ``launch/serve.py --sanitize`` / ``launch/recover.py --sanitize``
and the compile-once regression tests (``tests/test_sanitize.py``).

NaN-placeholder caveat: ``jax_debug_nans`` flags NaN at the op that produces
it, so intentional NaN fills (e.g. trace buffers for skipped iterations)
must be built in numpy and transferred (``jnp.asarray(np.full(...))``) —
a transfer is not a computation and does not trip the check. The solver
cores were converted to that idiom in this PR.
"""
from __future__ import annotations

import contextlib

import jax

#: jax.monitoring duration event emitted once per backend (XLA) compilation.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# One module-level listener fan-outs to whichever counters are active:
# jax.monitoring has no unregister API, so registering per-counter would leak.
_ACTIVE: list["CompileCounter"] = []
_REGISTERED = False


def _listener(event: str, duration: float, **kwargs) -> None:
    if event == COMPILE_EVENT:
        for counter in _ACTIVE:
            counter._record(duration)


def _ensure_listener() -> None:
    global _REGISTERED
    if not _REGISTERED:
        jax.monitoring.register_event_duration_secs_listener(_listener)
        _REGISTERED = True


class CompileCounter:
    """Counts backend compiles while active (use as a context manager).

    >>> with CompileCounter() as cc:
    ...     f(x)            # warm-up: compiles
    ...     cc.mark_warm()
    ...     f(x); f(x)      # must hit the cache
    >>> assert cc.compiles_since_warm == 0
    """

    def __init__(self) -> None:
        self.compiles = 0
        self.compile_seconds = 0.0
        self._warm_at: int | None = None

    def __enter__(self) -> "CompileCounter":
        _ensure_listener()
        _ACTIVE.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _ACTIVE.remove(self)

    def _record(self, duration: float) -> None:
        self.compiles += 1
        self.compile_seconds += duration

    def mark_warm(self) -> None:
        """Declare warm-up over; compiles after this point are regressions."""
        self._warm_at = self.compiles

    @property
    def compiles_since_warm(self) -> int:
        return self.compiles - (self._warm_at or 0)

    def summary(self) -> str:
        since = ("n/a" if self._warm_at is None
                 else str(self.compiles_since_warm))
        return (f"compiles={self.compiles} compiles_after_warmup={since} "
                f"compile_s={self.compile_seconds:.2f}")


@contextlib.contextmanager
def sanitize(nans: bool = True, infs: bool = True,
             counter: CompileCounter | None = None):
    """NaN/Inf tripwires + compile counting for the enclosed block.

    Yields the :class:`CompileCounter` (the one passed in, or a fresh one).
    Previous debug-flag values are restored on exit, so nesting and test
    isolation are safe.

    The flags are set thread-locally, not globally: a thread-local setting
    installs the check on jit's C++ dispatch path itself. A global update
    does not reach a thread where JAX has already run a local
    ``debug_nans(False)`` scope (``core.jaxpr_as_fun`` does), so there a
    cached jitted op (``jnp.true_divide``) would return an Inf unchecked.
    """
    own = counter if counter is not None else CompileCounter()
    with jax.debug_nans(bool(nans)), jax.debug_infs(bool(infs)), own:
        yield own
