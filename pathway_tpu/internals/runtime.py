"""GraphRunner: lower the parse graph onto micro-batch engine nodes.

reference: python/pathway/internals/graph_runner/__init__.py:36 (GraphRunner),
storage_graph.py (column layout), expression_evaluator.py (lowering) — all
collapsed into one pass here since the runtime is in-process Python instead
of a PyO3-bridged Rust engine.
"""

from __future__ import annotations

from typing import Any, Callable, Iterable

from ..testing import faults
from . import dtype as dt
from .engine import (
    AsyncMapNode,
    ConcatNode,
    DeduplicateNode,
    Engine,
    GroupByNode,
    JoinNode,
    Node,
    OutputNode,
    RowwiseNode,
    SemiJoinNode,
    SourceNode,
    UpdateCellsNode,
    UpdateRowsNode,
    ZipNode,
)
from .evaluator import EvalContext, compile_expression
from .expression import (
    AsyncApplyExpression,
    ColumnConstExpression,
    ColumnExpression,
    ColumnReference,
    FullyAsyncApplyExpression,
    IdExpression,
    ApplyExpression,
)
from .graph import G, Operator
from .groupbys import _GroupColExpression, _ReducerSlotExpression
from .joins import JoinMode
from .keys import derive_subkey, ref_pair, ref_pointer, ref_scalar
from .value import ERROR, Pointer

__all__ = ["GraphRunner", "build_engine"]


class _SlotExpression(ColumnExpression):
    """Reference to a precomputed async-result slot appended to the row."""

    def __init__(self, flat_idx: int, dtype: dt.DType):
        super().__init__()
        self.flat_idx = flat_idx
        self._slot_dtype = dtype

    def _compute_dtype(self) -> dt.DType:
        return self._slot_dtype


def _contains_nondeterministic(e: ColumnExpression) -> bool:
    if isinstance(e, ApplyExpression) and not e.deterministic:
        return True
    return any(_contains_nondeterministic(d) for d in e._deps())


class AsyncSlots:
    """The async applies of an operator's expressions, lifted out of them.

    Each :class:`AsyncApplyExpression` (found once, by identity) becomes a
    slot: ``extend`` evaluates one row's slots and appends their results to
    the row, and ``substitute`` rewrites an expression to read slot ``i``
    at ``base_width + i`` of such a row, so that what is left of it
    compiles synchronously.  ``select``/``filter`` run ``extend`` through
    an :class:`AsyncMapNode`; the external index
    (stdlib/indexing/lowering.py) through ``extend_all``, every row of a
    flush in one gather on the process's persistent loop."""

    def __init__(
        self,
        exprs: Iterable[ColumnExpression],
        resolve: Callable,
        base_width: int,
        op_name: str,
    ):
        self.slots: list[AsyncApplyExpression] = []

        def collect(e: ColumnExpression) -> None:
            if isinstance(e, AsyncApplyExpression):
                if not any(e is s for s in self.slots):
                    self.slots.append(e)
                return
            for d in e._deps():
                collect(d)

        for e in exprs:
            collect(e)
        self.base_width = base_width
        self.op_name = op_name
        #: any fully_async slot makes a select pipelined (results land one
        #: engine step later; device work overlaps host ingest)
        self.pipelined = any(
            isinstance(s, FullyAsyncApplyExpression) for s in self.slots
        )
        self.deterministic = all(s.deterministic for s in self.slots)
        #: rows in flight at once: the tightest ``capacity`` of the slots
        caps = [c for c in (getattr(s, "capacity", None) for s in self.slots) if c]
        self.capacity: int | None = min(caps) if caps else None
        self._slot_fns = [
            (
                s.fun,
                [compile_expression(a, resolve) for a in s.args],
                {k: compile_expression(v, resolve) for k, v in s.kwargs.items()},
                s.propagate_none,
            )
            for s in self.slots
        ]

    def __len__(self) -> int:
        return len(self.slots)

    def substitute(self, e: ColumnExpression) -> ColumnExpression:
        def subst(node: ColumnExpression) -> ColumnExpression | None:
            for i, s in enumerate(self.slots):
                if node is s:
                    return _SlotExpression(self.base_width + i, s.return_type)
            return None

        return e._substitute(subst)

    async def extend(self, ctx: tuple) -> tuple:
        """``(key, values)`` -> ``(key, values + slot results)``."""
        key, values = ctx
        results = []
        for fun, arg_fns, kwarg_fns, propagate_none in self._slot_fns:
            args = [f(ctx) for f in arg_fns]
            kwargs = {k: f(ctx) for k, f in kwarg_fns.items()}
            if any(a is ERROR for a in args) or any(
                v is ERROR for v in kwargs.values()
            ):
                results.append(ERROR)
                continue
            if propagate_none and any(a is None for a in args):
                results.append(None)
                continue
            # failure domain: an async UDF whose retries are exhausted
            # must not tear down the engine loop — under
            # terminate_on_error=False its own row carries ERROR and the
            # failure lands in the global error log
            try:
                if faults.enabled:
                    faults.perturb("udf")
                results.append(await fun(*args, **kwargs))
            except Exception as exc:  # noqa: BLE001 — routed
                results.append(
                    EvalContext.handle(exc, kind="udf", operator=self.op_name)
                )
        return (key, tuple(values) + tuple(results))

    def extend_all(self, ctxs: list[tuple]) -> list[tuple]:
        """``extend`` for every row at once, blocking: all of them are
        pending (at most ``capacity`` in flight) before any is awaited."""
        from .aio import gather_bounded, submit

        return submit(gather_bounded(self.extend, ctxs, self.capacity)).result()


class _TableLayout:
    """Flat row layout over the operator's input tables."""

    def __init__(self, tables: list):
        self.tables = tables
        self.offsets: dict[int, int] = {}
        off = 0
        for t in tables:
            self.offsets[id(t)] = off
            off += len(t.column_names())
        self.width = off
        self.col_idx: dict[int, dict[str, int]] = {
            id(t): {n: i for i, n in enumerate(t.column_names())} for t in tables
        }

    def slot_of(self, node) -> int | None:
        """Flat column index for a plain reference node, else None (used
        by the columnar fast path; ``.id`` is not a slot)."""
        if isinstance(node, _SlotExpression):
            return node.flat_idx
        if isinstance(node, ColumnReference) and node.name != "id":
            off = self.offsets.get(id(node.table))
            if off is None:
                return None
            idx = self.col_idx[id(node.table)].get(node.name)
            return None if idx is None else off + idx
        return None

    def resolver(self):
        def resolve(ref: ColumnReference) -> Callable:
            if isinstance(ref, _SlotExpression):
                idx = ref.flat_idx
                return lambda ctx: ctx[1][idx]
            if ref.name == "id":
                return lambda ctx: ctx[0]
            t = ref.table
            if id(t) not in self.offsets:
                raise ValueError(
                    f"expression references table not among operator inputs: "
                    f"{ref!r} (did you mean to join/ix?)"
                )
            idx = self.offsets[id(t)] + self.col_idx[id(t)][ref.name]
            return lambda ctx: ctx[1][idx]

        return resolve


class GraphRunner:
    """Builds an Engine from the parse graph, tree-shaken from outputs."""

    def __init__(self):
        self.engine = Engine()
        self.table_node: dict[int, Node] = {}  # id(table) -> producing node
        self.source_nodes: list[tuple[SourceNode, Operator]] = []

    # ---- public ----
    def build(self, output_requests: list[tuple[Any, OutputNode]]) -> Engine:
        from .config import get_pathway_config
        from .flight_recorder import span

        with span("graph.lower", "runtime") as timed:
            self.engine.set_threads(get_pathway_config().threads)
            ops = G.relevant_operators([t._operator for t, _ in output_requests])
            for op in ops:
                self._lower(op)
            for table, out_node in output_requests:
                self.engine.add(out_node)
                self._node_of(table).downstream.append((out_node, 0))
            self._feed_static_sources()
            timed.set(operators=len(ops), nodes=len(self.engine.nodes))
        return self.engine

    def _feed_static_sources(self):
        for src, op in self.source_nodes:
            subject = op.params.get("subject")
            if subject is not None and getattr(subject, "_mode", None) == "static":
                subject._run_static(src)
                continue
            rows = op.params.get("rows")
            if rows is not None:
                entries = [(key, row, 1) for key, row in rows]
                src.push(0, entries)
            stream = op.params.get("stream")
            if stream is not None:
                # contract: stream is {time: [(key, values, diff)]} — built
                # grouped at parse time so feeding is one push per time
                for t, ent in stream.items():
                    src.push(t, ent)

    # ---- helpers ----
    def _node_of(self, table) -> Node:
        return self.table_node[id(table)]

    def _register(self, op: Operator, node: Node) -> None:
        for out_table in op.outputs:
            self.table_node[id(out_table)] = node

    def _connect_inputs(self, op: Operator, node: Node) -> None:
        for port, t in enumerate(op.inputs):
            self._node_of(t).downstream.append((node, port))

    # ---- lowering dispatch ----
    def _lower(self, op: Operator) -> None:
        handler = getattr(self, f"_lower_{op.kind}", None)
        if handler is None:
            raise NotImplementedError(f"no lowering for operator kind {op.kind!r}")
        n0 = len(self.engine.nodes)
        handler(op)
        if op.error_logs:
            # evaluation errors in this operator's nodes route to the
            # local logs active when it was built (errors.local_error_log)
            for node in self.engine.nodes[n0:]:
                node.error_logs = op.error_logs

    def _lower_input(self, op: Operator) -> None:
        src = SourceNode(name=f"input#{op.id}")
        self.engine.add(src)
        self.source_nodes.append((src, op))
        subject = op.params.get("subject")
        if subject is not None:
            subject._attach(src, self.engine)
        self._register(op, src)

    # rowwise family -------------------------------------------------------
    def _rowwise_pipeline(
        self,
        op: Operator,
        exprs: dict[str, ColumnExpression],
        final_builder: Callable[[list[Callable], _TableLayout], Node],
    ) -> None:
        """Shared select/filter pipeline: [zip] -> [async map] -> final node."""
        inputs = op.inputs
        layout = _TableLayout(inputs)
        upstream: Node | None = None

        if len(inputs) > 1:
            zip_node = ZipNode(
                len(inputs),
                fn=lambda key, rows: tuple(v for r in rows for v in r),
                name=f"zip#{op.id}",
            )
            # recovery-plane keyspace: op ids are deterministic per
            # program (graph build order) — the streaming driver restores
            # the per-key port slots under OPERATOR_PERSISTING
            zip_node.persistent_id = f"zip#{op.id}"
            self.engine.add(zip_node)
            self._connect_inputs(op, zip_node)
            upstream = zip_node
        slots = AsyncSlots(
            exprs.values(), layout.resolver(), layout.width, f"async#{op.id}"
        )
        if slots:
            # AsyncMapNode operates on rows; we need key in ctx, so wrap rows
            wrap_in = RowwiseNode(
                lambda key, row, diff: [(key, ((key, row),), diff)],
                name=f"asyncwrap#{op.id}",
            )
            self.engine.add(wrap_in)
            if upstream is None:
                self._connect_inputs(op, wrap_in)
            else:
                upstream.downstream.append((wrap_in, 0))
            amap = AsyncMapNode(
                lambda row: slots.extend(row[0]),
                capacity=slots.capacity,
                pipelined=slots.pipelined,
                name=f"async#{op.id}",
            )
            # recovery-plane coverage: the node's only cross-step state is
            # its retraction memo — when every slot UDF is deterministic a
            # post-restart retraction recomputes the identical value, so
            # an empty memo is safe and OPERATOR_PERSISTING may cover the
            # graph (non-deterministic slots keep the refusal)
            amap._slots_deterministic = slots.deterministic
            self.engine.add(amap)
            wrap_in.downstream.append((amap, 0))
            unwrap = RowwiseNode(
                lambda key, row, diff: [(key, row[1], diff)],
                name=f"asyncunwrap#{op.id}",
            )
            self.engine.add(unwrap)
            amap.downstream.append((unwrap, 0))
            upstream = unwrap
            exprs = {n: slots.substitute(e) for n, e in exprs.items()}

        resolve = layout.resolver()
        fns = [compile_expression(e, resolve) for e in exprs.values()]
        final = final_builder(fns, layout)
        self.engine.add(final)
        if upstream is None:
            self._connect_inputs(op, final)
        else:
            upstream.downstream.append((final, 0))
        self._register(op, final)

    def _lower_rowwise(self, op: Operator) -> None:
        exprs = op.params["exprs"]
        memoize = any(_contains_nondeterministic(e) for e in exprs.values())

        def builder(fns, layout):
            # arity-specialized row constructors: select is the hottest
            # node and a genexpr-into-tuple per row costs ~2x a direct
            # call tuple at small widths
            if len(fns) == 1:
                (f0,) = fns

                def fn(key, row, diff):
                    return [(key, (f0((key, row)),), diff)]

            elif len(fns) == 2:
                f0, f1 = fns

                def fn(key, row, diff):
                    ctx = (key, row)
                    return [(key, (f0(ctx), f1(ctx)), diff)]

            elif len(fns) == 3:
                f0, f1, f2 = fns

                def fn(key, row, diff):
                    ctx = (key, row)
                    return [(key, (f0(ctx), f1(ctx), f2(ctx)), diff)]

            elif len(fns) == 4:
                f0, f1, f2, f3 = fns

                def fn(key, row, diff):
                    ctx = (key, row)
                    return [(key, (f0(ctx), f1(ctx), f2(ctx), f3(ctx)), diff)]

            else:

                def fn(key, row, diff):
                    ctx = (key, row)
                    return [(key, tuple([f(ctx) for f in fns]), diff)]

            node = RowwiseNode(fn, memoize=memoize, name=f"select#{op.id}")
            if not memoize:
                from .evaluator import (
                    build_projection_entries,
                    build_vector_select,
                )

                # columnar fast paths: pure projections rebuild entries in
                # one comprehension; computed selects evaluate big batches
                # as numpy columns (engine.py RowwiseNode.flush), falling
                # back per batch when non-numeric values appear
                node.vector_entries_fn = build_projection_entries(
                    list(exprs.values()), layout.slot_of
                )
                if node.vector_entries_fn is None:
                    node.vector_fn = build_vector_select(
                        list(exprs.values()), layout.slot_of
                    )
            return node

        self._rowwise_pipeline(op, exprs, builder)

    def _lower_filter(self, op: Operator) -> None:
        cond = op.params["condition"]
        primary = op.inputs[0]
        width = len(primary.column_names())

        def builder(fns, layout):
            cond_fn = fns[0]
            op_name = f"filter#{op.id}"

            def fn(key, row, diff):
                c = cond_fn((key, row))
                if c is ERROR:
                    # reference semantics (src/engine/error.rs): an ERROR
                    # condition drops the row and logs it — ERROR is truthy
                    # in Python, so without this guard poisoned rows would
                    # silently PASS the filter
                    if diff > 0:
                        from .errors import register_error

                        register_error(
                            "filter condition evaluated to ERROR; row dropped",
                            kind="filter",
                            operator=op_name,
                        )
                    return []
                if c:
                    return [(key, row[:width], diff)]  # row is a tuple; slice is too
                return []

            node = RowwiseNode(fn, name=f"filter#{op.id}")
            from .evaluator import build_vector_filter

            node.vector_mask = build_vector_filter(cond, layout.slot_of)
            node.filter_width = width
            return node

        self._rowwise_pipeline(op, {"__cond__": cond}, builder)

    def _lower_flatten(self, op: Operator) -> None:
        primary = op.inputs[0]
        names = primary.column_names()
        col_idx = names.index(op.params["column"])
        origin = op.params.get("origin_id") is not None

        op_name = f"flatten#{op.id}"

        def fn(key, row, diff):
            seq = row[col_idx]
            if seq is None:
                return []
            if seq is ERROR:
                # a poisoned sequence (e.g. failed parse UDF under
                # terminate_on_error=False) flattens to nothing, loudly
                if diff > 0:
                    from .errors import register_error

                    register_error(
                        "flatten input is ERROR; row dropped",
                        kind="eval",
                        operator=op_name,
                    )
                return []
            out = []
            for i, v in enumerate(_iter_flat(seq)):
                new_row = list(row)
                new_row[col_idx] = v
                if origin:
                    new_row.append(key)
                out.append((derive_subkey(key, i), tuple(new_row), diff))
            return out

        node = RowwiseNode(fn, name=f"flatten#{op.id}")
        self.engine.add(node)
        self._connect_inputs(op, node)
        self._register(op, node)

    def _lower_reindex(self, op: Operator) -> None:
        exprs = op.params["exprs"]
        instance = op.params.get("instance")
        raw = op.params.get("raw", False)
        layout = _TableLayout(op.inputs)
        resolve = layout.resolver()
        fns = [compile_expression(e, resolve) for e in exprs]
        inst_fn = compile_expression(instance, resolve) if instance is not None else None

        def fn(key, row, diff):
            ctx = (key, row)
            vals = [f(ctx) for f in fns]
            if raw:
                new_key = vals[0]
            else:
                new_key = ref_pointer(vals, inst_fn(ctx) if inst_fn else None)
            return [(new_key, row, diff)]

        node = RowwiseNode(fn, name=f"reindex#{op.id}")
        self.engine.add(node)
        self._connect_inputs(op, node)
        self._register(op, node)

    # stateful -------------------------------------------------------------
    def _lower_groupby(self, op: Operator) -> None:
        table = op.inputs[0]
        layout = _TableLayout([table])
        resolve = layout.resolver()
        grouping = op.params["grouping"]
        reducers = op.params["reducers"]
        out_exprs = op.params["out_exprs"]
        set_id = op.params.get("set_id", False)

        g_fns = [compile_expression(g, resolve) for g in grouping]
        red_arg_fns = [
            [compile_expression(a, resolve) for a in r.args] for r in reducers
        ]
        instance = op.params.get("instance")
        inst_fn = compile_expression(instance, resolve) if instance is not None else None
        sort_by = op.params.get("sort_by")
        sort_fn = compile_expression(sort_by, resolve) if sort_by is not None else None

        def out_resolve(ref):
            if isinstance(ref, _GroupColExpression):
                slot = ref.slot
                return lambda ctx: ctx[0][slot]
            if isinstance(ref, _ReducerSlotExpression):
                slot = ref.slot
                return lambda ctx: ctx[1][slot]
            raise ValueError(f"unexpected reference in reduce output: {ref!r}")

        out_fns = [compile_expression(e, out_resolve) for e in out_exprs.values()]

        def group_fn(key, row):
            ctx = (key, row)
            return tuple([f(ctx) for f in g_fns])

        def args_fn(key, row):
            ctx = (key, row)
            return tuple(
                [tuple([f(ctx) for f in arg_fns]) for arg_fns in red_arg_fns]
            )

        def out_fn(gvals, rvals):
            ctx = (gvals, rvals)
            return tuple(f(ctx) for f in out_fns)

        def key_fn(gvals, instance_val):
            if set_id:
                return gvals[0]
            return ref_pointer(gvals, instance_val)

        node = GroupByNode(
            group_fn=group_fn,
            instance_fn=(lambda key, row: inst_fn((key, row))) if inst_fn else None,
            args_fn=args_fn,
            out_fn=out_fn,
            key_fn=key_fn,
            reducers=[r.reducer for r in reducers],
            sort_by_fn=(lambda key, row: sort_fn((key, row))) if sort_fn else None,
            name=f"groupby#{op.id}",
            persistent_id=op.params.get("persistent_id"),
        )
        # columnar ingest gate: plain column projections (or scalar
        # constants, e.g. count()'s Const(0) placeholder arg) throughout,
        # no per-row key/seq sensitivity (GroupByNode._ingest_vector)
        def vec_arg(a):
            s = layout.slot_of(a)
            if s is not None:
                return s
            if isinstance(a, ColumnConstExpression) and type(a._value) in (
                int, float, bool, str, type(None)
            ):
                return ("const", a._value)
            return None

        group_slots = [layout.slot_of(g) for g in grouping]
        red_arg_slots = [[vec_arg(a) for a in r.args] for r in reducers]
        if (
            inst_fn is None
            and sort_fn is None
            and all(s is not None for s in group_slots)
            and all(s is not None for sl in red_arg_slots for s in sl)
            and all(r.reducer.vector_safe for r in reducers)
            and not any(r.reducer.distinguish_by_key for r in reducers)
        ):
            node.vector_spec = (group_slots, red_arg_slots)
        self.engine.add(node)
        self._connect_inputs(op, node)
        self._register(op, node)

    def _lower_join(self, op: Operator) -> None:
        left, right = op.inputs
        mode: JoinMode = op.params["mode"]
        on = op.params["on"]
        out_exprs = op.params["out_exprs"]
        id_expr = op.params.get("id_expr")

        llayout = _TableLayout([left])
        rlayout = _TableLayout([right])
        lfns = [compile_expression(le, llayout.resolver()) for le, _ in on]
        rfns = [compile_expression(re, rlayout.resolver()) for _, re in on]

        lcols = {n: i for i, n in enumerate(left.column_names())}
        rcols = {n: i for i, n in enumerate(right.column_names())}

        def join_resolve(ref: ColumnReference):
            if ref.name == "id":
                if ref.table is left:
                    return lambda ctx: ctx[0]
                if ref.table is right:
                    return lambda ctx: ctx[2]
                raise ValueError("id reference to table outside join")
            if ref.table is left:
                idx = lcols[ref.name]
                return lambda ctx: (ctx[1][idx] if ctx[1] is not None else None)
            if ref.table is right:
                idx = rcols[ref.name]
                return lambda ctx: (ctx[3][idx] if ctx[3] is not None else None)
            raise ValueError(
                f"join select references table that is neither side: {ref!r}"
            )

        out_fns = [compile_expression(e, join_resolve) for e in out_exprs.values()]

        def out_fn(lkey, lrow, rkey, rrow):
            ctx = (lkey, lrow, rkey, rrow)
            return tuple(f(ctx) for f in out_fns)

        if id_expr is not None:
            if isinstance(id_expr, IdExpression) and id_expr.table is left:
                out_key_fn = lambda lkey, lrow, rkey, rrow: lkey
            elif isinstance(id_expr, IdExpression) and id_expr.table is right:
                out_key_fn = lambda lkey, lrow, rkey, rrow: rkey
            else:
                id_fn = compile_expression(id_expr, join_resolve)
                out_key_fn = lambda lkey, lrow, rkey, rrow: id_fn(
                    (lkey, lrow, rkey, rrow)
                )
        else:
            out_key_fn = lambda lkey, lrow, rkey, rrow: ref_pair(lkey, rkey)

        node = JoinNode(
            left_key_fn=lambda key, row: tuple(f((key, row)) for f in lfns),
            right_key_fn=lambda key, row: tuple(f((key, row)) for f in rfns),
            out_fn=out_fn,
            out_key_fn=out_key_fn,
            left_outer=mode in (JoinMode.LEFT, JoinMode.OUTER),
            right_outer=mode in (JoinMode.RIGHT, JoinMode.OUTER),
            exact_match=op.params.get("exact_match", False),
            name=f"join#{op.id}",
        )
        # single-column equi-join: probe with the raw cell instead of a
        # frozen 1-tuple (JoinNode._process fast loop)
        if len(on) == 1:
            ls = llayout.slot_of(on[0][0])
            rs = rlayout.slot_of(on[0][1])
            if ls is not None and rs is not None:
                node.left_key_slot = ls
                node.right_key_slot = rs
        # plain-reference join select: code-generate the output-row
        # constructor once (a tuple display of subscripts) instead of a
        # per-row genexpr over compiled closures
        fast_out = self._join_fast_out(
            out_exprs, left, right, lcols, rcols,
            none_checks=mode is not JoinMode.INNER,
        )
        if fast_out is not None:
            node.out_fn = fast_out
        self.engine.add(node)
        self._connect_inputs(op, node)
        self._register(op, node)

    @staticmethod
    def _join_fast_out(out_exprs, left, right, lcols, rcols, none_checks):
        parts = []
        for e in out_exprs.values():
            if not isinstance(e, ColumnReference):
                return None
            if e.name == "id":
                if e.table is left:
                    parts.append("lkey")
                elif e.table is right:
                    parts.append("rkey")
                else:
                    return None
            elif e.table is left and e.name in lcols:
                idx = lcols[e.name]
                parts.append(
                    f"(lrow[{idx}] if lrow is not None else None)"
                    if none_checks
                    else f"lrow[{idx}]"
                )
            elif e.table is right and e.name in rcols:
                idx = rcols[e.name]
                parts.append(
                    f"(rrow[{idx}] if rrow is not None else None)"
                    if none_checks
                    else f"rrow[{idx}]"
                )
            else:
                return None
        if not parts:
            return None
        body = ", ".join(parts) + ("," if len(parts) == 1 else "")
        return eval(f"lambda lkey, lrow, rkey, rrow: ({body})")

    def _lower_ix(self, op: Operator) -> None:
        context_t, source_t = op.inputs
        optional = op.params["optional"]
        ptr = op.params["ptr"]
        layout = _TableLayout([context_t])
        ptr_fn = compile_expression(ptr, layout.resolver())
        n_cols = len(source_t.column_names())

        def out_fn(lkey, lrow, rkey, rrow):
            if rrow is None:
                if not optional:
                    raise KeyError(
                        f"ix: no row with key referenced by {ptr!r}"
                    )
                return tuple([None] * n_cols)
            return tuple(rrow)

        node = JoinNode(
            left_key_fn=lambda key, row: ptr_fn((key, row)),
            right_key_fn=lambda key, row: key,
            out_fn=out_fn,
            out_key_fn=lambda lkey, lrow, rkey, rrow: lkey,
            left_outer=True,  # always emit context rows; missing handled above
            right_outer=False,
            name=f"ix#{op.id}",
        )
        self.engine.add(node)
        self._connect_inputs(op, node)
        self._register(op, node)

    def _lower_concat(self, op: Operator) -> None:
        # align each input's columns to the output order
        names = op.outputs[0].column_names()
        node = ConcatNode(len(op.inputs), reindex=op.params["reindex"], name=f"concat#{op.id}")
        self.engine.add(node)
        for port, t in enumerate(op.inputs):
            proj = self._projection(t, names, f"concatproj#{op.id}.{port}")
            self._node_of(t).downstream.append((proj, 0))
            proj.downstream.append((node, port))
        self._register(op, node)

    def _projection(self, table, names: list[str], name: str) -> Node:
        src_names = table.column_names()
        if src_names == names:
            idxs = None
        else:
            idxs = [src_names.index(n) for n in names]
        if idxs is None:
            fn = lambda key, row, diff: [(key, row, diff)]
        else:
            fn = lambda key, row, diff: [(key, tuple(row[i] for i in idxs), diff)]
        node = RowwiseNode(fn, name=name)
        self.engine.add(node)
        return node

    def _lower_update_rows(self, op: Operator) -> None:
        names = op.outputs[0].column_names()
        node = UpdateRowsNode(name=f"update_rows#{op.id}")
        self.engine.add(node)
        for port, t in enumerate(op.inputs):
            proj = self._projection(t, names, f"urproj#{op.id}.{port}")
            self._node_of(t).downstream.append((proj, 0))
            proj.downstream.append((node, port))
        self._register(op, node)

    def _lower_update_cells(self, op: Operator) -> None:
        node = UpdateCellsNode(op.params["positions"], name=f"update_cells#{op.id}")
        self.engine.add(node)
        self._connect_inputs(op, node)
        self._register(op, node)

    def _lower_semijoin(self, op: Operator) -> None:
        right_key = op.params.get("right_key")
        if right_key is not None:
            rlayout = _TableLayout([op.inputs[1]])
            rk_fn_c = compile_expression(right_key, rlayout.resolver())
            right_key_fn = lambda key, row: rk_fn_c((key, row))
        else:
            right_key_fn = lambda key, row: key
        node = SemiJoinNode(
            mask_key_fn=lambda key, row: key,
            right_key_fn=right_key_fn,
            mode=op.params["mode"],
            name=f"semijoin#{op.id}",
        )
        self.engine.add(node)
        self._connect_inputs(op, node)
        self._register(op, node)

    def _lower_with_universe_of(self, op: Operator) -> None:
        node = SemiJoinNode(
            mask_key_fn=lambda key, row: key,
            right_key_fn=lambda key, row: key,
            mode="intersect",
            name=f"with_universe_of#{op.id}",
        )
        self.engine.add(node)
        self._connect_inputs(op, node)
        self._register(op, node)

    def _lower_deduplicate(self, op: Operator) -> None:
        table = op.inputs[0]
        layout = _TableLayout([table])
        resolve = layout.resolver()
        value_fn_c = compile_expression(op.params["value"], resolve)
        instance = op.params.get("instance")
        inst_fn_c = compile_expression(instance, resolve) if instance is not None else None
        acceptor = op.params["acceptor"]
        node = DeduplicateNode(
            instance_fn=(lambda key, row: inst_fn_c((key, row))) if inst_fn_c else (lambda key, row: ()),
            value_fn=lambda key, row: value_fn_c((key, row)),
            acceptor=acceptor,
            name=f"dedup#{op.id}",
            persistent_id=op.params.get("persistent_id"),
        )
        self.engine.add(node)
        self._connect_inputs(op, node)
        self._register(op, node)

    def _lower_external_index(self, op: Operator) -> None:
        from ..stdlib.indexing.lowering import lower_external_index

        lower_external_index(self, op)

    def _lower_iterate(self, op: Operator) -> None:
        from .iterate import lower_iterate

        lower_iterate(self, op)

    def _lower_sort(self, op: Operator) -> None:
        from ..stdlib.indexing.lowering import lower_sort

        lower_sort(self, op)

    def _lower_asof_now_join(self, op: Operator) -> None:
        from ..stdlib.temporal._asof_now_join import lower_asof_now_join

        lower_asof_now_join(self, op)

    def _lower_window_behavior(self, op: Operator) -> None:
        from ..stdlib.temporal._behavior_node import lower_window_behavior

        lower_window_behavior(self, op)

    def _lower_row_transformer(self, op: Operator) -> None:
        from .row_transformer import lower_row_transformer

        lower_row_transformer(self, op)


def _iter_flat(seq):
    import numpy as np

    if isinstance(seq, np.ndarray):
        return list(seq)
    if isinstance(seq, str):
        return list(seq)
    if isinstance(seq, (tuple, list)):
        return seq
    from .value import Json

    if isinstance(seq, Json):
        inner = seq.value
        return [Json(v) for v in inner]
    raise TypeError(f"cannot flatten value of type {type(seq)}")


def build_engine(output_requests) -> Engine:
    return GraphRunner().build(output_requests)
