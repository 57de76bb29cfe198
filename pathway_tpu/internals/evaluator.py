"""Expression compiler: ColumnExpression tree -> Python closure.

reference: python/pathway/internals/graph_runner/expression_evaluator.py:211
(RowwiseEvaluator lowering the AST to engine expressions) + the row-wise
interpreter src/engine/expression.rs.  Here the lowering target is a Python
closure ``fn(ctx) -> value``; the caller supplies a resolver mapping
ColumnReference nodes to accessors over its row context.

Error semantics follow the reference (src/engine/error.rs): if any operand is
``ERROR`` the result is ``ERROR``; exceptions raise unless the run was started
with ``terminate_on_error=False`` in which case they produce ``ERROR`` rows.
"""

from __future__ import annotations

from typing import Any, Callable

from . import expression as expr_mod
from .value import ERROR, Json, Pointer
from .keys import ref_scalar
from . import dtype as dt
from ..testing import faults

__all__ = ["compile_expression", "compile_vector_expression", "EvalContext"]


class EvalContext:
    """Runtime switches shared across compiled closures."""

    terminate_on_error: bool = True

    @classmethod
    def handle(cls, exc: Exception, kind: str = "eval", operator: str = ""):
        if cls.terminate_on_error:
            raise exc
        from .errors import register_error

        retries = getattr(exc, "retries_exhausted", None)
        suffix = "" if retries is None else f" (after {retries} retries)"
        register_error(
            f"{type(exc).__name__}: {exc}{suffix}", kind=kind, operator=operator
        )
        return ERROR


def compile_expression(
    e: expr_mod.ColumnExpression,
    resolve_ref: Callable[[expr_mod.ColumnReference], Callable[[Any], Any]],
) -> Callable[[Any], Any]:
    """Compile ``e`` into ``fn(ctx) -> value``."""

    def rec(node: expr_mod.ColumnExpression) -> Callable[[Any], Any]:
        return compile_expression(node, resolve_ref)

    if isinstance(e, expr_mod.ColumnConstExpression):
        v = e._value
        return lambda ctx: v

    if isinstance(e, expr_mod.ColumnReference):
        return resolve_ref(e)

    if isinstance(e, expr_mod.ColumnBinaryOpExpression):
        lf, rf = rec(e.left), rec(e.right)
        impl = expr_mod.binary_op_impl(e.op)
        # branch on the operator once at compile time, not per row
        if e.op == "==":

            def run_eq(ctx):
                a = lf(ctx)
                if a is ERROR:
                    return ERROR
                b = rf(ctx)
                return ERROR if b is ERROR else a == b

            return run_eq
        if e.op == "!=":

            def run_ne(ctx):
                a = lf(ctx)
                if a is ERROR:
                    return ERROR
                b = rf(ctx)
                return ERROR if b is ERROR else a != b

            return run_ne

        def run_binary(ctx):
            a = lf(ctx)
            if a is ERROR:
                return ERROR
            b = rf(ctx)
            if b is ERROR:
                return ERROR
            if a is None or b is None:
                return None
            try:
                return impl(a, b)
            except Exception as exc:
                return EvalContext.handle(exc)

        return run_binary

    if isinstance(e, expr_mod.ColumnUnaryOpExpression):
        f = rec(e.expr)
        op = e.op

        def run_unary(ctx):
            v = f(ctx)
            if v is ERROR:
                return ERROR
            if v is None:
                return None
            try:
                if op == "-":
                    return -v
                if op == "~":
                    return not v if isinstance(v, bool) else ~v
                if op == "abs":
                    return abs(v)
            except Exception as exc:
                return EvalContext.handle(exc)
            raise ValueError(f"unknown unary op {op}")

        return run_unary

    if isinstance(e, (expr_mod.ApplyExpression,)):
        # Async applies are lifted out of an operator's expressions where
        # the operator can gather them (runtime.py ``AsyncSlots``: select
        # and filter through AsyncMapNode, the external index a whole
        # flush at once); one that is reached here, inside a join or a
        # reduce, runs alone on an event loop of its own.
        arg_fns = [rec(a) for a in e.args]
        kwarg_fns = {k: rec(v) for k, v in e.kwargs.items()}
        fun = e.fun
        propagate_none = e.propagate_none
        is_async = isinstance(e, expr_mod.AsyncApplyExpression)

        def run_apply(ctx):
            args = [f(ctx) for f in arg_fns]
            kwargs = {k: f(ctx) for k, f in kwarg_fns.items()}
            if any(a is ERROR for a in args) or any(v is ERROR for v in kwargs.values()):
                return ERROR
            if propagate_none and (
                any(a is None for a in args) or any(v is None for v in kwargs.values())
            ):
                return None
            try:
                if faults.enabled:
                    faults.perturb("udf")
                if is_async:
                    import asyncio

                    return asyncio.run(fun(*args, **kwargs))
                return fun(*args, **kwargs)
            except Exception as exc:
                return EvalContext.handle(exc, kind="udf")

        return run_apply

    if isinstance(e, expr_mod.CastExpression):
        f = rec(e.expr)
        target = e.return_type

        def run_cast(ctx):
            v = f(ctx)
            if v is ERROR:
                return ERROR
            if v is None:
                return None
            try:
                return _cast(v, target)
            except Exception as exc:
                return EvalContext.handle(exc)

        return run_cast

    if isinstance(e, expr_mod.ConvertExpression):
        f = rec(e.expr)
        target = e.return_type
        unwrap = e.unwrap

        def run_convert(ctx):
            v = f(ctx)
            if v is ERROR:
                return ERROR
            if v is None:
                return None
            if isinstance(v, Json):
                res = {
                    dt.INT: v.as_int,
                    dt.FLOAT: v.as_float,
                    dt.STR: v.as_str,
                    dt.BOOL: v.as_bool,
                }[target]()
            else:
                res = _cast(v, target)
            if res is None and unwrap:
                return EvalContext.handle(ValueError(f"cannot convert {v!r}"))
            return res

        return run_convert

    if isinstance(e, expr_mod.DeclareTypeExpression):
        return rec(e.expr)

    if isinstance(e, expr_mod.CoalesceExpression):
        fns = [rec(a) for a in e.args]

        def run_coalesce(ctx):
            for f in fns:
                v = f(ctx)
                if v is not None:
                    return v
            return None

        return run_coalesce

    if isinstance(e, expr_mod.RequireExpression):
        vf = rec(e.val)
        fns = [rec(a) for a in e.args]

        def run_require(ctx):
            for f in fns:
                if f(ctx) is None:
                    return None
            return vf(ctx)

        return run_require

    if isinstance(e, expr_mod.IfElseExpression):
        cf, tf, ef = rec(e.if_), rec(e.then), rec(e.else_)

        def run_ifelse(ctx):
            c = cf(ctx)
            if c is ERROR:
                return ERROR
            return tf(ctx) if c else ef(ctx)

        return run_ifelse

    if isinstance(e, expr_mod.IsNotNoneExpression):
        f = rec(e.expr)
        return lambda ctx: f(ctx) is not None

    if isinstance(e, expr_mod.IsNoneExpression):
        f = rec(e.expr)
        return lambda ctx: f(ctx) is None

    if isinstance(e, expr_mod.MakeTupleExpression):
        fns = [rec(a) for a in e.args]
        return lambda ctx: tuple(f(ctx) for f in fns)

    if isinstance(e, expr_mod.GetExpression):
        of, idxf, df = rec(e.obj), rec(e.index), rec(e.default)
        checked = e.check_if_exists

        def run_get(ctx):
            obj = of(ctx)
            if obj is ERROR:
                return ERROR
            idx = idxf(ctx)
            try:
                if isinstance(obj, Json):
                    inner = obj.value
                    res = inner[idx]
                    return Json(res)
                return obj[idx]
            except (KeyError, IndexError, TypeError) as exc:
                if checked:
                    return df(ctx)
                return EvalContext.handle(exc)

        return run_get

    if isinstance(e, expr_mod.MethodCallExpression):
        fns = [rec(a) for a in e.args]
        fun = e.fun
        propagate_none = e.propagate_none

        def run_method(ctx):
            args = [f(ctx) for f in fns]
            if any(a is ERROR for a in args):
                return ERROR
            if propagate_none and args and args[0] is None:
                return None
            try:
                return fun(*args)
            except Exception as exc:
                return EvalContext.handle(exc)

        return run_method

    if isinstance(e, expr_mod.UnwrapExpression):
        f = rec(e.expr)

        def run_unwrap(ctx):
            v = f(ctx)
            if v is None:
                return EvalContext.handle(ValueError("unwrap() on None"))
            return v

        return run_unwrap

    if isinstance(e, expr_mod.FillErrorExpression):
        f, rf = rec(e.expr), rec(e.replacement)

        def run_fill(ctx):
            try:
                v = f(ctx)
            except Exception:
                return rf(ctx)
            if v is ERROR:
                return rf(ctx)
            return v

        return run_fill

    if isinstance(e, expr_mod.PointerExpression):
        fns = [rec(a) for a in e.args]
        inst_fn = rec(e.instance) if e.instance is not None else None
        optional = e.optional

        def run_pointer(ctx):
            vals = [f(ctx) for f in fns]
            if any(v is ERROR for v in vals):
                return ERROR
            if optional and any(v is None for v in vals):
                return None
            key = ref_scalar(*vals)
            if inst_fn is not None:
                inst_key = ref_scalar(inst_fn(ctx))
                key = key.with_shard(inst_key.value >> (128 - Pointer.SHARD_BITS))
            return key

        return run_pointer

    if isinstance(e, expr_mod.ReducerExpression):
        raise TypeError(
            "reducer expression used outside of reduce() context"
        )

    # unknown node kinds (internal slot references etc.) resolve like refs
    try:
        return resolve_ref(e)  # type: ignore[arg-type]
    except Exception:
        pass
    raise TypeError(f"cannot compile expression of type {type(e).__name__}")


def _cast(v: Any, target: dt.DType) -> Any:
    target = dt.unoptionalize(target)
    if target is dt.INT:
        return int(v)
    if target is dt.FLOAT:
        return float(v)
    if target is dt.BOOL:
        return bool(v)
    if target is dt.STR:
        if isinstance(v, bool):
            return "True" if v else "False"
        return str(v)
    if target is dt.BYTES:
        return v.encode() if isinstance(v, str) else bytes(v)
    if target is dt.JSON:
        return v if isinstance(v, Json) else Json(v)
    return v


# ---------------------------------------------------------------------------
# columnar (batch) compilation — the TPU-first engine direction: evaluate a
# whole micro-batch of rows as numpy column arrays instead of per-row
# closures.  reference parity note: the Rust engine evaluates per row over
# i64/f64 (src/engine/expression.rs); this path keeps those numeric
# semantics (int64 arithmetic) and falls back to the row path whenever a
# batch contains anything non-numeric (None/ERROR/strings → object dtype).
# ---------------------------------------------------------------------------

#: binary ops safe to vectorize: no zero-divide (numpy warns + returns
#: inf/nan where the row path raises/routes ERROR), no Python-only
#: semantics
_VECTOR_BIN_OPS: dict | None = None

#: runtime magnitude bound for int columns on the vector path: with
#: |inputs| < 2^31 the compile-time bit-growth analysis below guarantees
#: no intermediate exceeds int64, so numpy can never silently wrap where
#: the row path's Python bignums would keep going
VECTOR_INT_BOUND = 1 << 31


def _vector_bin_ops():
    global _VECTOR_BIN_OPS
    if _VECTOR_BIN_OPS is None:
        import operator

        _VECTOR_BIN_OPS = {
            "+": operator.add,
            "-": operator.sub,
            "*": operator.mul,
            "<": operator.lt,
            "<=": operator.le,
            ">": operator.gt,
            ">=": operator.ge,
            "==": operator.eq,
            "!=": operator.ne,
            "&": operator.and_,
            "|": operator.or_,
            "^": operator.xor,
        }
    return _VECTOR_BIN_OPS


#: worst-case result bit width assumed for an int column reference
#: (enforced at runtime by _materialize_cols against VECTOR_INT_BOUND)
_REF_BITS = 31
#: int64 headroom the analysis must stay within (sign bit reserved)
_MAX_BITS = 62


def compile_vector_expression(
    e: expr_mod.ColumnExpression,
    slot_of_ref,
) -> Callable | None:
    """Compile ``e`` into ``fn(cols) -> ndarray`` over numpy column arrays,
    or return None when the expression isn't vectorizable.

    ``slot_of_ref(ref) -> int | None`` maps a ColumnReference (or internal
    slot expression) to its input-column index.  Integer expressions carry
    a compile-time worst-case bit-width (inputs bounded by
    ``VECTOR_INT_BOUND`` at runtime); anything that could exceed int64
    stays on the row path, so wraparound can never diverge from the
    Python-int row semantics.
    """
    import operator

    numeric = (dt.INT, dt.FLOAT, dt.BOOL)

    def rec(node):
        """Returns (fn, kind, bits) or None; kind in {'int','float','bool'}."""
        if isinstance(node, expr_mod.ColumnConstExpression):
            v = node._value
            if type(v) is bool:
                return (lambda cols: v), "bool", 1
            if type(v) is int:
                return (lambda cols: v), "int", max(v.bit_length(), 1)
            if type(v) is float:
                return (lambda cols: v), "float", 0
            return None
        if isinstance(node, expr_mod.ColumnBinaryOpExpression):
            impl = _vector_bin_ops().get(node.op)
            if impl is None:
                # division-family ops are safe when the divisor is a
                # non-zero constant (no zero-divide can occur, so numpy
                # and the row path agree)
                if node.op in ("//", "%", "/") and isinstance(
                    node.right, expr_mod.ColumnConstExpression
                ):
                    d = node.right._value
                    if type(d) in (int, float) and d != 0:
                        left = rec(node.left)
                        if left is None:
                            return None
                        lf, lkind, lbits = left
                        impl2 = {
                            "//": operator.floordiv,
                            "%": operator.mod,
                            "/": operator.truediv,
                        }[node.op]
                        if node.op == "/" or lkind == "float":
                            kind, bits = "float", 0
                        elif node.op == "%":
                            kind = "int"
                            bits = (
                                abs(d).bit_length() if type(d) is int else lbits
                            )
                        else:
                            kind, bits = "int", lbits
                        if kind == "int" and bits > _MAX_BITS:
                            return None
                        return (lambda cols: impl2(lf(cols), d)), kind, bits
                return None
            left, right = rec(node.left), rec(node.right)
            if left is None or right is None:
                return None
            lf, lkind, lbits = left
            rf, rkind, rbits = right
            if node.op in ("<", "<=", ">", ">=", "==", "!="):
                kind, bits = "bool", 1
            elif node.op in ("&", "|", "^"):
                kind = "bool" if lkind == rkind == "bool" else "int"
                bits = max(lbits, rbits)
            elif "float" in (lkind, rkind):
                kind, bits = "float", 0
            elif node.op == "*":
                kind, bits = "int", lbits + rbits
            else:  # + -
                kind, bits = "int", max(lbits, rbits) + 1
            if kind == "int" and bits > _MAX_BITS:
                return None
            return (lambda cols: impl(lf(cols), rf(cols))), kind, bits
        if isinstance(node, expr_mod.ColumnUnaryOpExpression):
            inner = rec(node.expr)
            if inner is None:
                return None
            f, kind, bits = inner
            if node.op == "-":
                if kind == "bool":
                    # numpy forbids - on bool arrays; the row path returns
                    # -True == -1 — keep that on the row path
                    return None
                return (lambda cols: -f(cols)), kind, bits
            if node.op == "~" and kind in ("bool", "int"):
                return (lambda cols: ~f(cols)), kind, bits + 1
            return None
        # column references / internal slots: only non-optional numerics —
        # an Optional column may carry None, which the object-dtype guard
        # catches anyway, but excluding it here avoids wasted conversions
        slot = slot_of_ref(node)
        if slot is None:
            return None
        d = getattr(node, "_dtype", None)
        if d not in numeric:
            return None
        kind = {dt.INT: "int", dt.FLOAT: "float", dt.BOOL: "bool"}[d]
        bits = _REF_BITS if kind == "int" else (1 if kind == "bool" else 0)
        return (lambda cols: cols[slot]), kind, bits

    if getattr(e, "_dtype", None) not in numeric:
        return None
    compiled = rec(e)
    return None if compiled is None else compiled[0]


def _collect_slots(e, slot_of_ref) -> dict:
    """Slots referenced by ``e`` mapped to their declared dtype."""
    out: dict = {}

    def walk(node):
        slot = slot_of_ref(node)
        if slot is not None:
            out[slot] = getattr(node, "_dtype", None)
            return
        for d in getattr(node, "_deps", lambda: ())() or ():
            walk(d)

    walk(e)
    return out


def _materialize_cols(rows, slots, int_slots=()):
    """Column arrays for ``slots``; None if any column is non-numeric
    (object dtype: None/ERROR/strings present in the batch) or an int
    column exceeds the wraparound-safety bound the compile-time analysis
    assumed.  A declared-INT column whose batch happens to be all Python
    bools (bool subclasses int, so the row path accepts them) widens to
    int64 so arithmetic stays numeric — numpy bool ops are logical
    (True+True == True) and unary ``-`` raises."""
    import numpy as np

    cols = {}
    for s in slots:
        vals = [r[s] for r in rows]
        arr = np.asarray(vals)
        if arr.dtype == object:
            return None
        if arr.dtype.kind == "b" and s in int_slots:
            arr = arr.astype(np.int64)
        if arr.dtype.kind in "iu" and (
            arr.max(initial=0) >= VECTOR_INT_BOUND
            or arr.min(initial=0) <= -VECTOR_INT_BOUND
        ):
            # kind 'u': a batch of all-huge positive ints coerces to
            # uint64 and would otherwise bypass the wraparound bound
            return None
        if arr.dtype.kind == "f" and not _float_col_exact(arr, vals):
            # float64 coerced from huge Python ints (declared-INT column
            # mixing magnitudes, or optional numerics): values beyond
            # 2**53 already lost precision vs the exact bigint row path
            return None
        cols[s] = arr
    return cols


#: largest magnitude exactly representable in float64 — int-sourced
#: values beyond this lose precision when numpy coerces a mixed batch
FLOAT_EXACT_BOUND = 1 << 53


def _float_col_exact(arr, vals) -> bool:
    """True iff coercing ``vals`` to the float64 array ``arr`` was
    value-preserving.  Vectorized precheck: if every magnitude is below
    2**53 the coercion of any int source was exact; only when huge (or
    NaN) values are present do we scan source types."""
    import numpy as np

    if bool((np.abs(arr) < FLOAT_EXACT_BOUND).all()):
        return True
    return all(isinstance(v, float) for v in vals)


def build_vector_select(exprs, slot_of_ref):
    """``fn(rows) -> list[tuple] | None`` evaluating a whole select batch
    over numpy columns; returns None at build time unless every output
    column is a pass-through reference or a vectorizable expression (and
    at least one actually computes)."""
    fns = []
    pass_slots = {}
    for i, e in enumerate(exprs):
        slot = slot_of_ref(e)
        if slot is not None:
            pass_slots[i] = slot
            fns.append(None)
            continue
        f = compile_vector_expression(e, slot_of_ref)
        if f is None:
            return None
        fns.append(f)
    if all(f is None for f in fns):
        return None  # pure projection — build_projection_entries covers it

    slot_dtypes: dict = {}
    for f, e in zip(fns, exprs):
        if f is not None:
            slot_dtypes.update(_collect_slots(e, slot_of_ref))
    compute_slots = sorted(slot_dtypes)
    int_slots = frozenset(
        s for s, d in slot_dtypes.items() if d is dt.INT
    )

    def run(rows):
        cols = _materialize_cols(rows, compute_slots, int_slots)
        if cols is None:
            return None
        n = len(rows)
        out_cols = []
        for i, f in enumerate(fns):
            if f is None:
                s = pass_slots[i]
                out_cols.append([r[s] for r in rows])
            else:
                res = f(cols)
                # const-only expressions yield Python scalars — broadcast
                out_cols.append(
                    res.tolist() if hasattr(res, "tolist") else [res] * n
                )
        # C-level transpose into row tuples
        return list(zip(*out_cols))

    return run


def build_projection_entries(exprs, slot_of_ref):
    """Entry-level fast path for pure-projection selects:
    ``fn(entries) -> list[Entry]`` rebuilding ``(key, out_row, diff)`` in a
    single comprehension — no numpy, no intermediate row lists.  Returns
    None unless every output column is a plain slot reference."""
    import operator as _op

    if not exprs:
        return None  # id-only select — row path emits empty tuples
    slots = []
    for e in exprs:
        s = slot_of_ref(e)
        if s is None:
            return None
        slots.append(s)
    # three column sweeps + one C-level zip beat a single row-tuple
    # comprehension by ~20% at big batch sizes
    if len(slots) == 1:
        s0 = slots[0]

        def run_single(entries):
            return list(
                zip(
                    [e[0] for e in entries],
                    [(e[1][s0],) for e in entries],
                    [e[2] for e in entries],
                )
            )

        return run_single
    getter = _op.itemgetter(*slots)

    def run_multi(entries):
        return list(
            zip(
                [e[0] for e in entries],
                [getter(e[1]) for e in entries],
                [e[2] for e in entries],
            )
        )

    return run_multi


def build_vector_filter(cond, slot_of_ref):
    """``fn(rows) -> list[bool] | None`` evaluating a filter predicate
    over numpy columns; None at build time if not vectorizable."""
    f = compile_vector_expression(cond, slot_of_ref)
    if f is None:
        return None
    slot_dtypes = _collect_slots(cond, slot_of_ref)
    slots = sorted(slot_dtypes)
    if not slots:
        return None
    int_slots = frozenset(s for s, d in slot_dtypes.items() if d is dt.INT)

    def run(rows):
        cols = _materialize_cols(rows, slots, int_slots)
        if cols is None:
            return None
        return f(cols).tolist()

    return run
