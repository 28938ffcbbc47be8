"""IR rules JX101, JX102 and JX104–JX106 over traced jaxprs and operator
contracts.

These see what the AST tier structurally cannot: the jaxpr is the graph XLA
actually compiles, *after* Python-level indirection (``make_iteration_
operators`` dispatch, pytree flattening, closures) has been resolved. Each
rule walks the closed jaxpr recursively — through pjit calls, scan/while
bodies, custom-call sub-jaxprs — so a narrowing convert eight frames deep in
a packed-backend iteration body is the same finding as one at top level.

JX106 is different in kind: it runs the operator protocol's documented
adjoint contract (mv/rmv shapes and dtypes mutually dual, composition dims
chaining) under ``jax.eval_shape`` — no data, no FLOPs, but a real trace of
both directions.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

JAXPR_RULE_SUMMARIES = {
    "JX101": "IR dtype narrowing: convert_element_type demoting "
             "c128/f64/f32 anywhere in the traced call graph",
    "JX102": "recompile risk: weak_type leaking into entry outputs; "
             "primitive skeleton diverging between two abstract shapes",
    "JX104": "host transfer in hot loop: callback/infeed/outfeed/device_put "
             "primitives inside a while/scan body",
    "JX105": "baked constant: array constant above threshold bytes closed "
             "over into the jaxpr instead of passed as an argument",
    "JX106": "adjoint contract: mv/rmv shapes+dtypes not mutually dual "
             "under eval_shape; ComposedOperator dims not chaining",
}

#: bytes above which a jaxpr constant is "large" (JX105). A (1, N) f32 scale
#: row is ~128 B at serving widths; a baked Φ is tens of KB even at toy shapes.
CONST_THRESHOLD_BYTES = 4096

_HOT_TRANSFER_PRIMS = {"infeed", "outfeed", "device_put", "copy_to_host_async",
                       "debug_print"}


@dataclasses.dataclass
class Issue:
    """One raw rule hit, pre-Finding: the runner owns path/pragma/baseline."""

    rule: str
    message: str
    detail: str  # stable identity fragment (entry-relative, shape-pinned)
    site: Optional[tuple] = None  # (abs file, 1-based line) from source_info


# --------------------------------------------------------------------------
# jaxpr walking


def _sub_jaxprs(eqn):
    """(sub_jaxpr, is_loop_body) pairs reachable from one eqn's params."""
    loop = eqn.primitive.name in ("while", "scan", "fori_loop")
    for val in eqn.params.values():
        for sub in _as_jaxprs(val):
            yield sub, loop


def _as_jaxprs(val):
    if hasattr(val, "jaxpr") and hasattr(val, "consts"):  # ClosedJaxpr
        yield val.jaxpr
    elif hasattr(val, "eqns") and hasattr(val, "invars"):  # open Jaxpr
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _as_jaxprs(v)


def iter_eqns(jaxpr, in_loop=False):
    """Yield (eqn, in_loop) over ``jaxpr`` and every reachable sub-jaxpr."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        for sub, is_loop in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, in_loop or is_loop)


def iter_closed(closed):
    """Yield every ClosedJaxpr reachable from ``closed`` (itself included)."""
    seen = set()

    def walk(cj):
        if id(cj) in seen:
            return
        seen.add(id(cj))
        yield cj
        for eqn in cj.jaxpr.eqns:
            for val in eqn.params.values():
                vals = val if isinstance(val, (list, tuple)) else [val]
                for v in vals:
                    if hasattr(v, "jaxpr") and hasattr(v, "consts"):
                        yield from walk(v)

    yield from walk(closed)


def eqn_site(eqn):
    """(file, line) of the user frame that traced ``eqn``, or None.

    ``jax._src.source_info_util`` is private API — probe defensively and
    degrade to the entry anchor rather than crash the analyzer on a jax
    upgrade.
    """
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info)
        if frame is not None:
            return (frame.file_name, frame.start_line)
    except Exception:
        pass
    return None


def _skeleton(closed):
    """The trace's primitive-name sequence — its compile-relevant shape."""
    return tuple(eqn.primitive.name for eqn, _ in iter_eqns(closed.jaxpr))


# --------------------------------------------------------------------------
# JX101 — dtype narrowing in the IR


def _is_inexact(dt) -> bool:
    # jnp's lattice, not np's: bfloat16/float8 are ml_dtypes extension types
    # that np.issubdtype does not classify as inexact
    import jax.numpy as jnp

    return jnp.issubdtype(jnp.dtype(dt), jnp.inexact)


def check_jx101_narrowing(name, closed):
    import numpy as np

    out = []
    for eqn, _ in iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        old = getattr(eqn.invars[0].aval, "dtype", None)
        new = eqn.params.get("new_dtype")
        if old is None or new is None:
            continue
        if not (_is_inexact(old) and _is_inexact(new)):
            continue  # quantize/dequantize int hops are the product, not a bug
        if np.dtype(new).itemsize >= np.dtype(old).itemsize:
            continue
        out.append(Issue(
            "JX101",
            f"traced graph of `{name}` demotes {np.dtype(old).name} -> "
            f"{np.dtype(new).name} via convert_element_type",
            f"{name} :: convert {np.dtype(old).name}->{np.dtype(new).name}",
            site=eqn_site(eqn)))
    return out


# --------------------------------------------------------------------------
# JX102 — recompile-risk surface


def check_jx102_recompile(name, closed, alt_closed):
    out = []
    for i, var in enumerate(closed.jaxpr.outvars):
        aval = getattr(var, "aval", None)
        if aval is None or not getattr(aval, "weak_type", False):
            continue
        if not _is_inexact(getattr(aval, "dtype", "int32")):
            continue
        out.append(Issue(
            "JX102",
            f"`{name}` output[{i}] is weak-typed "
            f"({aval.dtype}) — mixing it with strong-typed "
            "arrays re-specializes downstream jits per call site",
            f"{name} :: weak_type output[{i}]"))
    if alt_closed is not None:
        sk_a, sk_b = _skeleton(closed), _skeleton(alt_closed)
        if sk_a != sk_b:
            div = next((j for j, (a, b) in enumerate(zip(sk_a, sk_b))
                        if a != b), min(len(sk_a), len(sk_b)))
            out.append(Issue(
                "JX102",
                f"`{name}` traces to a different primitive skeleton at a "
                f"second abstract shape ({len(sk_a)} vs {len(sk_b)} eqns, "
                f"first divergence at eqn {div}) — a Python branch keys on "
                "shape, so every serving shape pays a fresh XLA compile",
                f"{name} :: shape-dependent skeleton"))
    return out


# --------------------------------------------------------------------------
# JX104 — host↔device traffic inside the hot loop


def check_jx104_hot_transfer(name, closed):
    out = []
    for eqn, in_loop in iter_eqns(closed.jaxpr):
        if not in_loop:
            continue
        prim = eqn.primitive.name
        if prim in _HOT_TRANSFER_PRIMS or "callback" in prim:
            out.append(Issue(
                "JX104",
                f"`{name}`: `{prim}` primitive inside a while/scan body — a "
                "host round-trip (or device re-placement) every solver "
                "iteration serializes the loop on transfer latency",
                f"{name} :: {prim} in loop",
                site=eqn_site(eqn)))
    return out


# --------------------------------------------------------------------------
# JX105 — large constants baked into the jaxpr


def check_jx105_baked_const(name, closed,
                            threshold=CONST_THRESHOLD_BYTES):
    import numpy as np

    out = []
    seen = set()
    for cj in iter_closed(closed):
        for var, const in zip(cj.jaxpr.constvars, cj.consts):
            if id(const) in seen:
                continue
            seen.add(id(const))
            # jaxpr consts are typed literals that carry shape and dtype but
            # no nbytes
            dt = np.dtype(getattr(const, "dtype", "uint8"))
            shape = list(getattr(const, "shape", ()))
            nbytes = int(np.prod(shape, dtype=np.int64)) * dt.itemsize
            dt = dt.name
            if nbytes <= threshold:
                continue
            out.append(Issue(
                "JX105",
                f"`{name}` bakes a {nbytes}-byte constant ({dt}{shape}) "
                "into the jaxpr — it is re-hashed on every compile-cache "
                "lookup and silently pinned to trace-time values; pass it "
                "as an argument instead",
                f"{name} :: const {dt}{shape}"))
    return out


# --------------------------------------------------------------------------
# JX106 — adjoint-contract verification (eval_shape, no data)


def _eval_shape(fn, *args):
    import jax

    return jax.eval_shape(fn, *args)


def check_jx106_adjoint_contract(name, op, batch=4):
    """Statically verify mv/rmv duality for one operator instance.

    The contract (docs/operator-protocol): ``mv`` maps ``(..., n) ->
    (..., m)`` and ``rmv`` maps ``(..., m) -> (..., n)``, both preserving the
    operator dtype, batching over leading axes — exactly what the solver's
    ⟨mv(x), r⟩ == ⟨x, rmv(r)⟩ adjoint identity needs shape-wise.
    """
    import jax
    import numpy as np

    out = []

    def _issue(msg, frag):
        out.append(Issue("JX106", f"`{name}`: {msg}", f"{name} :: {frag}"))

    try:
        m, n = op.shape
        dt = np.dtype(op.dtype)
    except Exception as e:  # noqa: BLE001 - any protocol break is the finding
        out.append(Issue("JX106", f"`{name}`: shape/dtype protocol failed: "
                         f"{type(e).__name__}: {e}", f"{name} :: protocol"))
        return out

    checks = [
        ("mv", op.mv, (n,), (m,)),
        ("rmv", op.rmv, (m,), (n,)),
        ("mv batched", op.mv, (batch, n), (batch, m)),
        ("rmv batched", op.rmv, (batch, m), (batch, n)),
    ]
    for label, fn, in_shape, want_shape in checks:
        try:
            res = _eval_shape(fn, jax.ShapeDtypeStruct(in_shape, dt))
        except Exception as e:  # noqa: BLE001 - the trace failure IS the finding
            _issue(f"{label} failed to trace on {dt.name}{list(in_shape)}: "
                   f"{type(e).__name__}: {e}", f"{label} trace")
            continue
        if tuple(res.shape) != want_shape:
            _issue(f"{label} maps {list(in_shape)} -> {list(res.shape)}, "
                   f"contract requires {list(want_shape)} — adjoint pairing "
                   "⟨mv(x), r⟩ == ⟨x, rmv(r)⟩ cannot hold",
                   f"{label} shape")
        if np.dtype(res.dtype) != dt:
            _issue(f"{label} changes dtype {dt.name} -> "
                   f"{np.dtype(res.dtype).name} — mv/rmv must be mutually "
                   "dual in dtype or the inner products live in different "
                   "precisions", f"{label} dtype")

    outer = getattr(op, "outer", None)
    inner = getattr(op, "inner", None)
    if outer is not None and inner is not None:
        try:
            if outer.shape[1] != inner.shape[0]:
                _issue(f"composition does not chain: outer takes "
                       f"{outer.shape[1]}, inner produces {inner.shape[0]}",
                       "compose chain")
            if tuple(op.shape) != (outer.shape[0], inner.shape[1]):
                _issue(f"composed shape {list(op.shape)} != "
                       f"[{outer.shape[0]}, {inner.shape[1]}] from factors",
                       "compose shape")
        except Exception as e:  # noqa: BLE001
            _issue(f"composition introspection failed: {e}", "compose")
    return out


IR_RULES = {
    "JX101": check_jx101_narrowing,
    "JX104": check_jx104_hot_transfer,
    "JX105": check_jx105_baked_const,
}
