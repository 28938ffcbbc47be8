"""jaxpr-tier fixtures: one deliberately broken entry per JX rule.

Loaded via ``python -m repro.analysis --tier jaxpr --registry <this file>``
(and by tests/test_jaxpr_tier.py). Every entry here MUST keep producing its
finding — a rule that silently stops firing is worse than no rule. The
module lives under jaxlint_fixtures/ so the AST tier's default walk skips
it.
"""
import numpy as np

from repro.analysis.jaxpr.registry import (EntryPoint, OperatorSpec,
                                           TraceSpec, anchor_of)


def _jx101_narrowing():
    import jax.numpy as jnp

    def fn(x):
        # f32 -> bf16 demotion buried one call deep
        return jnp.tanh(x).astype(jnp.bfloat16).astype(jnp.float32)

    import jax

    return TraceSpec(fn=fn, args=(jax.ShapeDtypeStruct((8,), jnp.float32),),
                     anchor=anchor_of(fn))


def _jx102_weak_output():
    import jax
    import jax.numpy as jnp

    def fn(x):
        # second output is built from a bare python scalar -> weak f32
        return x, jnp.sin(0.5)

    return TraceSpec(fn=fn, args=(jax.ShapeDtypeStruct((8,), jnp.float32),),
                     anchor=anchor_of(fn))


def _jx102_shape_branch():
    import jax
    import jax.numpy as jnp

    def fn(x):
        # Python branch keyed on the abstract shape: every serving shape
        # on one side of the split compiles a different program
        if x.shape[0] > 8:
            return jnp.cumsum(x) * 2.0
        return x + 1.0

    return TraceSpec(fn=fn, args=(jax.ShapeDtypeStruct((8,), jnp.float32),),
                     alt_args=(jax.ShapeDtypeStruct((16,), jnp.float32),),
                     anchor=anchor_of(fn))


def _jx104_callback_in_loop():
    import jax

    def fn(x):
        def body(c, _):
            jax.debug.print("iter {}", c[0])  # host hop per iteration
            return c + 1.0, None

        c, _ = jax.lax.scan(body, x, None, length=4)
        return c

    return TraceSpec(fn=fn, args=(jax.ShapeDtypeStruct((8,), jnp_f32()),),
                     anchor=anchor_of(fn))


def jnp_f32():
    import jax.numpy as jnp

    return jnp.float32


_BIG = np.arange(32768, dtype=np.float32)  # 128 KiB, well over threshold


def _jx105_baked_const():
    import jax
    import jax.numpy as jnp

    def fn(x):
        return x + jnp.asarray(_BIG)[: x.shape[0]]  # closed-over constant

    return TraceSpec(fn=fn, args=(jax.ShapeDtypeStruct((8,), jnp.float32),),
                     anchor=anchor_of(fn))


class BrokenAdjointOperator:
    """rmv maps (m,) -> (m,): the adjoint pairing can never type-check."""

    shape = (16, 32)
    dtype = np.float32

    def mv(self, x):
        import jax.numpy as jnp

        return jnp.zeros(x.shape[:-1] + (self.shape[0],), jnp.float32)

    def rmv(self, r):
        import jax.numpy as jnp

        return jnp.zeros(r.shape, jnp.float32)  # BUG: should be (..., n)


class NarrowingOperator:
    """mv silently demotes the operator dtype c64 -> f32 (drops imag)."""

    shape = (16, 32)
    dtype = np.complex64

    def mv(self, x):
        import jax.numpy as jnp

        return jnp.zeros(x.shape[:-1] + (self.shape[0],), jnp.float32)

    def rmv(self, r):
        import jax.numpy as jnp

        return jnp.zeros(r.shape[:-1] + (self.shape[1],), jnp.complex64)


def _jx106_broken():
    return OperatorSpec(ops=[BrokenAdjointOperator()],
                        anchor=anchor_of(BrokenAdjointOperator),
                        trace_mv=False)


def _jx106_narrowing():
    return OperatorSpec(ops=[NarrowingOperator()],
                        anchor=anchor_of(NarrowingOperator), trace_mv=False)


ENTRIES = [
    EntryPoint("fixture.jx101.narrowing", _jx101_narrowing),
    EntryPoint("fixture.jx102.weak_output", _jx102_weak_output),
    EntryPoint("fixture.jx102.shape_branch", _jx102_shape_branch),
    EntryPoint("fixture.jx104.callback_in_loop", _jx104_callback_in_loop),
    EntryPoint("fixture.jx105.baked_const", _jx105_baked_const),
    EntryPoint("fixture.jx106.broken_adjoint", _jx106_broken),
    EntryPoint("fixture.jx106.narrowing_mv", _jx106_narrowing),
]
