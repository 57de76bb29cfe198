"""pathway_tpu — a TPU-native incremental stream/batch data-processing
framework with a live LLM/RAG toolkit.

A ground-up rebuild of the capabilities of the reference Pathway framework
(Python + Rust/timely-differential, /root/reference) designed TPU-first:

* host plane: a lean micro-batch incremental dataflow engine
  (``internals/engine.py``) keeping the reference's semantics — keyed diff
  streams, per-timestamp consistency, as-of-now serving joins;
* device plane: JAX/XLA/Pallas — jit-compiled embedders/rerankers
  (``models/``), HBM-resident vector indexes with Pallas top-k kernels
  (``ops/``), multi-chip sharding via ``jax.sharding`` meshes
  (``parallel/``).

Import as ``import pathway_tpu as pw`` — the public surface mirrors
``import pathway as pw`` (reference: python/pathway/__init__.py).
"""

from __future__ import annotations

from typing import Any

from .internals import dtype as dt
from .internals.value import (
    Json,
    Pointer,
    DateTimeNaive,
    DateTimeUtc,
    Duration,
    ERROR,
    PENDING,
)
from .internals.keys import ref_scalar, unsafe_make_pointer
from .internals.schema import (
    Schema,
    SchemaProperties,
    column_definition,
    schema_from_csv,
    schema_from_types,
    schema_from_dict,
    schema_from_pandas,
    schema_builder,
)
from .internals.pyobject import PyObjectWrapper, wrap_py_object
from .internals.custom_reducers import BaseCustomAccumulator
from .internals.expression import (
    ApplyExpression,
    AsyncApplyExpression,
    CastExpression,
    CoalesceExpression,
    ColumnExpression,
    ColumnReference,
    DeclareTypeExpression,
    FillErrorExpression,
    IfElseExpression,
    MakeTupleExpression,
    RequireExpression,
    UnwrapExpression,
    smart_wrap,
)
from .internals.thisclass import this, left, right
from .internals.table import Table, TableLike, groupby
from .internals.table_slice import TableSlice
from .internals.groupbys import GroupedTable
from .internals.joins import (
    JoinMode,
    JoinResult,
    OuterJoinResult,
    join,
    join_inner,
    join_left,
    join_outer,
    join_right,
)
from .internals import reducers
from .internals import udfs
from .internals.udfs import UDF, UDFAsync, UDFSync, udf, udf_async
from .internals.interactive import LiveTable, enable_interactive_mode
from .internals.row_transformer import (
    ClassArg,
    input_attribute,
    input_method,
    method,
    output_attribute,
    transformer,
)
from .internals.run import run, run_all, MonitoringLevel
from .internals.config import set_license_key, set_monitoring_config
from .internals.graph import G as global_graph
from .internals.iterate import iterate, iterate_universe

__version__ = "0.1.0"

Type = dt  # pw.Type-ish access to dtypes

# reference type-name parity (python/pathway/__init__.py): anything
# joinable is a TableLike here; grouped joins reduce through GroupedTable
Joinable = TableLike
GroupedJoinResult = GroupedTable


# ---------------------------------------------------------------------------
# free functions (reference: python/pathway/__init__.py exports)
# ---------------------------------------------------------------------------


def apply(fun, *args, **kwargs) -> ColumnExpression:
    """Row-wise application, result type inferred from annotations
    (reference: internals/common.py apply).

    Example:

    >>> import pathway_tpu as pw
    >>> t = pw.debug.table_from_markdown('''
    ... a | b
    ... 2 | 3
    ... 5 | 1
    ... ''')
    >>> pw.debug.compute_and_print(
    ...     t.select(m=pw.apply(max, t.a, t.b)), include_id=False)
    m
    3
    5
    """
    import inspect

    try:
        hints = inspect.get_annotations(fun, eval_str=True)
    except Exception:
        hints = getattr(fun, "__annotations__", {})
    return_type = hints.get("return", Any)
    return ApplyExpression(fun, return_type, *args, **kwargs)


def apply_with_type(fun, ret_type, *args, **kwargs) -> ColumnExpression:
    return ApplyExpression(fun, ret_type, *args, **kwargs)


def apply_async(fun, *args, **kwargs) -> ColumnExpression:
    import inspect

    from .internals.udfs import coerce_async

    try:
        hints = inspect.get_annotations(fun, eval_str=True)
    except Exception:
        hints = getattr(fun, "__annotations__", {})
    return_type = hints.get("return", Any)
    return AsyncApplyExpression(coerce_async(fun), return_type, *args, **kwargs)


def cast(target_type, expr) -> ColumnExpression:
    return CastExpression(target_type, smart_wrap(expr))


def declare_type(target_type, expr) -> ColumnExpression:
    return DeclareTypeExpression(target_type, smart_wrap(expr))


def coalesce(*args) -> ColumnExpression:
    """First non-None argument (reference: pw.coalesce).

    Example:

    >>> import pathway_tpu as pw
    >>> t = pw.debug.table_from_markdown('''
    ... a    | b
    ...      | 7
    ... 2    | 9
    ... ''')
    >>> pw.debug.compute_and_print(
    ...     t.select(v=pw.coalesce(t.a, t.b)), include_id=False)
    v
    2
    7
    """
    return CoalesceExpression(*args)


def require(val, *args) -> ColumnExpression:
    return RequireExpression(val, *args)


def if_else(if_clause, then_clause, else_clause) -> ColumnExpression:
    """Conditional expression (reference: pw.if_else).

    Example:

    >>> import pathway_tpu as pw
    >>> t = pw.debug.table_from_markdown('''
    ... v
    ... 3
    ... 8
    ... ''')
    >>> r = t.select(size=pw.if_else(t.v > 5, "big", "small"))
    >>> pw.debug.compute_and_print(r, include_id=False)
    size
    big
    small
    """
    return IfElseExpression(if_clause, then_clause, else_clause)


def make_tuple(*args) -> ColumnExpression:
    """Pack expressions into one tuple cell (reference: pw.make_tuple).

    Example:

    >>> import pathway_tpu as pw
    >>> t = pw.debug.table_from_markdown('''
    ... a | b
    ... 1 | x
    ... ''')
    >>> pw.debug.compute_and_print(
    ...     t.select(pair=pw.make_tuple(t.a, t.b)), include_id=False)
    pair
    (1, 'x')
    """
    return MakeTupleExpression(*args)


def unwrap(expr) -> ColumnExpression:
    return UnwrapExpression(smart_wrap(expr))


def fill_error(expr, replacement) -> ColumnExpression:
    return FillErrorExpression(smart_wrap(expr), replacement)


def assert_table_has_schema(
    table: Table,
    schema,
    *,
    allow_superset: bool = True,
    ignore_primary_keys: bool = True,
    allow_subtype: bool = True,
) -> None:
    """reference: internals/asserts.py"""
    from .internals.schema import is_subschema

    if allow_superset:
        ok = is_subschema(table.schema, schema)
    else:
        ok = is_subschema(table.schema, schema) and is_subschema(schema, table.schema)
    if ok and not allow_subtype:
        cols = table.schema.columns()
        ok = all(
            n in cols and cols[n].dtype == c.dtype
            for n, c in schema.columns().items()
        )
    if not ok:
        raise AssertionError(
            f"table schema {table.schema!r} does not match expected {schema!r}"
        )


class universes:
    """reference: python/pathway/universes.py"""

    @staticmethod
    def promise_are_equal(*tables: Table) -> None:
        for t in tables[1:]:
            tables[0]._universe.promise_equal(t._universe)

    @staticmethod
    def promise_is_subset_of(t1: Table, t2: Table) -> None:
        t1._universe.promise_subset_of(t2._universe)

    @staticmethod
    def promise_are_pairwise_disjoint(*tables: Table) -> None:
        pass


# ---------------------------------------------------------------------------
# lazy submodules
# ---------------------------------------------------------------------------

_LAZY_SUBMODULES = {
    "io",
    "debug",
    "demo",
    "stdlib",
    "indexing",
    "temporal",
    "ml",
    "graphs",
    "stateful",
    "statistical",
    "ordered",
    "utils",
    "xpacks",
    "persistence",
    "ops",
    "models",
    "parallel",
    "cli",
    "viz",
    "asynchronous",
}


def sql(query: str, **tables):
    """SQL over tables (reference: pw.sql, internals/sql.py — sqlglot
    there, a native parser here)."""
    from .internals.sql import sql as _sql

    return _sql(query, **tables)


def global_error_log():
    """Table of row-level evaluation errors collected when running with
    ``terminate_on_error=False`` (reference: internals/errors.py +
    graph.rs:958 error_log)."""
    from .internals.errors import global_error_log as _gel

    return _gel()


def local_error_log():
    """``with pw.local_error_log() as log:`` — errors of operators built
    inside the block land in ``log`` (reference: internals/errors.py:12)."""
    from .internals.errors import local_error_log as _lel

    return _lel()


def set_dead_letter_sink(sink):
    """Register a callable receiving every dead-lettered record
    (``{"payload", "reason", "source", "time"}``): poison connector
    payloads routed via ``ConnectorSubject.dead_letter`` /
    ``on_error="dead_letter"`` land here in addition to the global error
    log, so operators can persist them for replay."""
    from .internals.errors import set_dead_letter_sink as _sdls

    _sdls(sink)


def table_transformer(
    func=None,
    *,
    allow_superset=True,
    ignore_primary_keys=True,
    allow_subtype=True,
    locals=None,
):
    """Decorator checking ``pw.Table[SomeSchema]`` annotations of the
    wrapped function's arguments and return value at call time
    (reference: internals/common.py:533)."""
    import functools
    import typing

    def _flag(mapping, key):
        return mapping.get(key, True) if isinstance(mapping, dict) else mapping

    def _check(value, annotation, key):
        schema = None
        args = typing.get_args(annotation)
        if args and isinstance(args[0], type) and hasattr(args[0], "__columns__"):
            schema = args[0]
        if schema is not None and isinstance(value, Table):
            assert_table_has_schema(
                value,
                schema,
                allow_superset=_flag(allow_superset, key),
                ignore_primary_keys=_flag(ignore_primary_keys, key),
                allow_subtype=_flag(allow_subtype, key),
            )

    def decorate(fn):
        try:
            hints = typing.get_type_hints(fn, localns=locals)
        except Exception:
            hints = {}

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            import inspect

            bound = inspect.signature(fn).bind(*args, **kwargs)
            for pname, pvalue in bound.arguments.items():
                if pname in hints:
                    _check(pvalue, hints[pname], pname)
            result = fn(*args, **kwargs)
            if "return" in hints:
                _check(result, hints["return"], "return")
            return result

        return wrapper

    return decorate if func is None else decorate(func)


def load_yaml(stream):
    """Load a declarative ``!pw`` app template
    (reference: internals/yaml_loader.py:74)."""
    from .internals.yaml_loader import load_yaml as _load

    return _load(stream)


def pandas_transformer(output_schema, output_universe=None):
    """reference: stdlib/utils/pandas_transformer.py:15 (re-exported at
    top level like the reference's ``pw.pandas_transformer``)."""
    from .stdlib.utils.pandas_transformer import (
        pandas_transformer as _impl,
    )

    return _impl(output_schema, output_universe)


def __getattr__(name: str):
    import importlib

    if name in _LAZY_SUBMODULES:
        # "utils" stays the top-level package (it delegates the stdlib
        # helper names via its own __getattr__) — binding stdlib.utils
        # here would fight the attribute the import system sets when
        # pathway_tpu.utils.* is imported, losing whichever came second
        if name in ("indexing", "temporal", "ml", "graphs", "stateful", "statistical", "ordered", "viz"):
            mod = importlib.import_module(f".stdlib.{name}", __name__)
        else:
            mod = importlib.import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    if name == "AsyncTransformer":
        from .stdlib.utils.async_transformer import AsyncTransformer

        globals()[name] = AsyncTransformer
        return AsyncTransformer
    if name in ("IntervalJoinResult", "WindowJoinResult", "AsofJoinResult"):
        temporal = importlib.import_module(".stdlib.temporal", __name__)
        value = getattr(temporal, name)
        globals()[name] = value
        return value
    if name == "PersistenceMode":
        from .persistence import PersistenceMode

        globals()[name] = PersistenceMode
        return PersistenceMode
    if name == "window":
        # reference __all__ lists ``window`` (temporal window constructors);
        # expose the temporal window namespace under the name
        temporal = importlib.import_module(".stdlib.temporal", __name__)
        import types

        ns = types.SimpleNamespace(
            Window=temporal.Window,
            tumbling=temporal.tumbling,
            sliding=temporal.sliding,
            session=temporal.session,
            intervals_over=temporal.intervals_over,
        )
        globals()[name] = ns
        return ns
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "Table",
    "TableLike",
    "Schema",
    "Json",
    "Pointer",
    "DateTimeNaive",
    "DateTimeUtc",
    "Duration",
    "ColumnExpression",
    "ColumnReference",
    "GroupedTable",
    "JoinMode",
    "JoinResult",
    "MonitoringLevel",
    "UDF",
    "udf",
    "udfs",
    "reducers",
    "this",
    "left",
    "right",
    "apply",
    "apply_with_type",
    "apply_async",
    "cast",
    "declare_type",
    "coalesce",
    "require",
    "if_else",
    "make_tuple",
    "unwrap",
    "fill_error",
    "iterate",
    "iterate_universe",
    "run",
    "pandas_transformer",
    "run_all",
    "set_license_key",
    "set_monitoring_config",
    "groupby",
    "column_definition",
    "schema_from_types",
    "schema_from_dict",
    "schema_from_pandas",
    "schema_builder",
    "assert_table_has_schema",
    "universes",
    "unsafe_make_pointer",
    "load_yaml",
    "global_error_log",
    "local_error_log",
    "set_dead_letter_sink",
    "sql",
    "TableSlice",
    "SchemaProperties",
    "schema_from_csv",
    "PyObjectWrapper",
    "wrap_py_object",
    "BaseCustomAccumulator",
    "table_transformer",
    "Joinable",
    "GroupedJoinResult",
    "OuterJoinResult",
    "join",
    "join_inner",
    "join_left",
    "join_right",
    "join_outer",
    "udf_async",
    "UDFAsync",
    "UDFSync",
    "LiveTable",
    "enable_interactive_mode",
    "AsyncTransformer",
    "IntervalJoinResult",
    "WindowJoinResult",
    "AsofJoinResult",
    "PersistenceMode",
    "window",
    "viz",
    "asynchronous",
    "ClassArg",
    "input_attribute",
    "input_method",
    "method",
    "output_attribute",
    "transformer",
]
