"""The index node hands its data expression a whole flush: every update row
of a timestamp is pending in the embedder before any is awaited
(``ExternalIndexNode._collect_updates`` over ``AsyncSlots.extend_all``), so
that a flush rides one device tick and not one a document.

The index under the node is a recording stand-in (``add_batch``/``remove``/
``search``), so keys, vectors, metadata and payloads compare exactly; the
encoders are tiny and run on the CPU.
"""

from __future__ import annotations

import asyncio
import threading
from dataclasses import dataclass

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu import debug as pwd
from pathway_tpu.internals import udfs
from pathway_tpu.stdlib.indexing.data_index import _build_index_operator
from pathway_tpu.stdlib.indexing.retrievers import InnerIndexFactory
from pathway_tpu.xpacks.llm._utils import AsyncMicroBatcher
from pathway_tpu.xpacks.llm.embedders import BaseEmbedder

DIM = 8


def fake_vector(text: str) -> np.ndarray:
    seed = int.from_bytes(text.encode()[:8].ljust(8, b"\0"), "little") % 2**32
    return np.random.default_rng(seed).normal(size=DIM).astype(np.float32)


class RecordingIndex:
    """What the node asks of an inner index, kept as plain dictionaries; a
    search answers every live key, so a reply carries every payload."""

    def __init__(self):
        self.rows: dict = {}
        self.add_calls: list[list] = []
        self.removed: list = []

    def add_batch(self, keys, datas, metas):
        self.add_calls.append(list(keys))
        for key, data, meta in zip(keys, datas, metas):
            self.rows[key] = (data, meta)

    def remove(self, key):
        self.removed.append(key)
        self.rows.pop(key, None)

    def search(self, queries):
        return [[(key, 1.0) for key in self.rows] for _ in queries]


@dataclass
class RecordingFactory(InnerIndexFactory):
    built: RecordingIndex | None = None

    def build_inner_index(self):
        self.built = RecordingIndex()
        return self.built


class BatchedEmbedder(BaseEmbedder):
    """An async-UDF embedder over an ``AsyncMicroBatcher``, as
    ``SentenceTransformerEmbedder`` is: ``calls`` keeps what each call of the
    batcher's ``batch_fn`` was handed; a text in ``fail_on`` raises in its
    own call."""

    def __init__(self, fail_on=(), capacity=None, use_scheduler=None):
        super().__init__(
            executor=udfs.async_executor(capacity=capacity), deterministic=True
        )
        self.fail_on = set(fail_on)
        self.calls: list[list[str]] = []
        self.in_flight = 0
        self.in_flight_max = 0
        self._batcher = AsyncMicroBatcher(self._batch, use_scheduler=use_scheduler)

    def _batch(self, texts):
        self.calls.append(list(texts))
        return [fake_vector(t) for t in texts]

    async def __wrapped__(self, input: str, **kwargs) -> np.ndarray:
        self.in_flight += 1
        self.in_flight_max = max(self.in_flight_max, self.in_flight)
        try:
            if input in self.fail_on:
                raise ValueError(f"cannot embed {input!r}")
            await asyncio.sleep(0)  # let every admitted call start
            return await self._batcher.call(input)
        finally:
            self.in_flight -= 1

    def get_embedding_dimension(self, **kwargs) -> int:
        return DIM


TEXTS = ["alpha", "beta gamma", "delta", "epsilon zeta eta", "theta", "iota kappa"]


def docs_table(rows: list[tuple[int, str, int, int]]):
    """``(id, text, time, diff)`` rows as a streamed table with a ``meta``
    column derived from the text."""
    lines = ["    | text | __time__ | __diff__"] + [
        f"{i} | {text.replace(' ', '_')} | {t} | {d}" for i, text, t, d in rows
    ]
    docs = pwd.table_from_markdown("\n".join(lines))
    return docs.select(
        text=pw.apply_with_type(lambda s: s.replace("_", " "), str, pw.this.text),
        meta=pw.apply_with_type(
            lambda s: pw.Json({"words": len(s.split("_"))}), pw.Json, pw.this.text
        ),
    )


def run_index(docs, index_data, *, terminate_on_error=True):
    """Lower one external index over ``docs`` with one late query and run
    it; returns the factory (its recording index), the replies' payloads and
    the error log's rows."""
    queries = pwd.table_from_markdown(
        """
        | q | __time__
    90  | x | 1000
    """
    )
    factory = RecordingFactory()
    raw = _build_index_operator(
        docs, queries, factory, index_data, queries.q,
        index_metadata=docs.meta, k=100,
    )
    replies: list = []
    errors: list = []
    pw.io.subscribe(
        raw,
        on_change=lambda k, row, tm, add: replies.append(row["_pw_index_reply"])
        if add else None,
    )
    pw.io.subscribe(
        pw.global_error_log(),
        on_change=lambda k, row, tm, add: errors.append(row) if add else None,
    )
    pw.run(terminate_on_error=terminate_on_error)
    (reply,) = replies
    payloads = {key: payload for key, _score, payload in reply}
    return factory, payloads, errors


def reference_rows(rows):
    """The same documents through a synchronous ``index_data`` (evaluated
    row by row): what the batched evaluation has to equal."""
    from pathway_tpu.internals.graph import G

    G.clear()  # the graph under test has run; this is a pipeline of its own
    docs = docs_table(rows)
    vec = pw.apply_with_type(fake_vector, np.ndarray, docs.text)
    factory, payloads, _ = run_index(docs, vec)
    return factory.built, payloads


def same_index(got: RecordingIndex, want: RecordingIndex):
    assert got.rows.keys() == want.rows.keys()
    for key, (data, meta) in want.rows.items():
        np.testing.assert_array_equal(got.rows[key][0], data)
        assert got.rows[key][1] == meta


@pytest.fixture
def fresh_runtime():
    from pathway_tpu.runtime import get_runtime, reset_runtime

    reset_runtime()
    runtime = get_runtime()
    # a wide admission window: "one tick" must not hinge on a loaded test
    # machine getting a flush's submits in within the default 5 ms
    runtime.max_wait_ms = 50.0
    yield runtime
    reset_runtime()


@pytest.mark.parametrize("n", [1, 3, len(TEXTS)])
def test_a_flush_is_one_batch_call_and_one_tick(n, fresh_runtime):
    rows = [(i + 1, TEXTS[i], 2, 1) for i in range(n)]
    docs = docs_table(rows)
    embedder = BatchedEmbedder()
    before = fresh_runtime.stats()["ticks_total"]
    factory, payloads, _ = run_index(docs, embedder(docs.text))
    assert [sorted(call) for call in embedder.calls] == [sorted(TEXTS[:n])]
    assert fresh_runtime.stats()["ticks_total"] - before == 1
    assert len(factory.built.add_calls) == 1
    want, want_payloads = reference_rows(rows)
    same_index(factory.built, want)
    assert payloads == want_payloads and len(payloads) == n


def test_flushes_of_two_timestamps_are_two_ticks(fresh_runtime):
    rows = [(1, "alpha", 2, 1), (2, "delta", 2, 1), (3, "theta", 4, 1)]
    docs = docs_table(rows)
    embedder = BatchedEmbedder()
    factory, payloads, _ = run_index(docs, embedder(docs.text))
    assert [sorted(c) for c in embedder.calls] == [["alpha", "delta"], ["theta"]]
    assert fresh_runtime.stats()["ticks_total"] == 2
    same_index(factory.built, reference_rows(rows)[0])


def test_per_loop_batcher_also_sees_one_call():
    """Without the runtime (``use_scheduler=False``) the batcher collects
    per scheduling round of the loop: one round now holds the flush."""
    rows = [(i + 1, t, 2, 1) for i, t in enumerate(TEXTS)]
    docs = docs_table(rows)
    embedder = BatchedEmbedder(use_scheduler=False)
    factory, _, _ = run_index(docs, embedder(docs.text))
    assert [sorted(c) for c in embedder.calls] == [sorted(TEXTS)]
    same_index(factory.built, reference_rows(rows)[0])


def test_one_failing_row_is_error_alone(fresh_runtime):
    # "delta" fails in its own call; it is added at 2 and retracted at 4
    rows = [(1, "alpha", 2, 1), (2, "delta", 2, 1), (3, "theta", 2, 1),
            (2, "delta", 4, -1), (4, "beta gamma", 4, 1)]
    docs = docs_table(rows)
    embedder = BatchedEmbedder(fail_on={"delta"})
    factory, payloads, errors = run_index(
        docs, embedder(docs.text), terminate_on_error=False
    )
    good = [r for r in rows if r[1] != "delta"]
    want, want_payloads = reference_rows(good)
    same_index(factory.built, want)
    assert payloads == want_payloads
    # the retraction computed the same ERROR and was skipped: nothing removed
    assert factory.built.removed == []
    excluded = [e for e in errors if "excluded from index" in e["message"]]
    assert len(excluded) == 1 and excluded[0]["kind"] == "index"
    udf = [e for e in errors if e["kind"] == "udf"]
    assert len(udf) == 2 and all("cannot embed" in e["message"] for e in udf)
    # the rows that shared the failing row's flush were embedded together
    assert sorted(embedder.calls[0]) == ["alpha", "theta"]


def test_a_failing_row_stops_the_run_when_errors_terminate(fresh_runtime):
    docs = docs_table([(1, "alpha", 2, 1), (2, "delta", 2, 1)])
    embedder = BatchedEmbedder(fail_on={"delta"})
    with pytest.raises(ValueError, match="cannot embed"):
        run_index(docs, embedder(docs.text))


def test_the_final_entry_of_a_key_decides(fresh_runtime):
    # add, remove, add of key 1 in ONE timestamp; key 2 added then removed
    rows = [(1, "alpha", 2, 1), (1, "alpha", 2, -1), (1, "beta gamma", 2, 1),
            (2, "delta", 2, 1), (2, "delta", 2, -1), (3, "theta", 2, 1)]
    docs = docs_table(rows)
    embedder = BatchedEmbedder()
    factory, payloads, _ = run_index(docs, embedder(docs.text))
    want, want_payloads = reference_rows(rows)
    same_index(factory.built, want)
    assert payloads == want_payloads
    texts = sorted(p[0] for p in payloads.values())
    assert texts == ["beta gamma", "theta"]
    # every update row, retractions too, was evaluated in the one batch
    assert len(embedder.calls) == 1


def test_none_propagates_without_a_call(fresh_runtime):
    docs = docs_table([(1, "alpha", 2, 1), (2, "delta", 2, 1)])
    docs = docs.select(
        pw.this.meta,
        text=pw.apply_with_type(
            lambda s: None if s == "delta" else s, str | None, pw.this.text
        ),
    )
    embedder = BatchedEmbedder()
    embedder.propagate_none = True
    factory, _, _ = run_index(docs, embedder(docs.text))
    assert embedder.calls == [["alpha"]]
    assert sorted(
        ("none" if d is None else "vec") for d, _ in factory.built.rows.values()
    ) == ["none", "vec"]


@pytest.mark.parametrize("kind", ["bm25_text", "vector_column"])
def test_a_synchronous_index_data_takes_the_per_row_path(kind, monkeypatch):
    from pathway_tpu.stdlib.indexing.lowering import ExternalIndexNode

    seen: list = []
    original = ExternalIndexNode._collect_updates

    def spy(self, updates, last, payloads):
        seen.append(len(self.doc_slots))
        return original(self, updates, last, payloads)

    monkeypatch.setattr(ExternalIndexNode, "_collect_updates", spy)
    rows = [(i + 1, t, 2, 1) for i, t in enumerate(TEXTS[:3])]
    docs = docs_table(rows)
    data = (
        docs.text if kind == "bm25_text"
        else pw.apply_with_type(fake_vector, np.ndarray, docs.text)
    )
    factory, payloads, _ = run_index(docs, data)
    assert seen == [0]  # no async apply: nothing lifted, no loop touched
    assert len(factory.built.rows) == len(payloads) == 3
    if kind == "bm25_text":
        assert sorted(d for d, _ in factory.built.rows.values()) == sorted(TEXTS[:3])


@pytest.mark.parametrize("capacity", [1, 2])
def test_capacity_bounds_the_calls_in_flight(capacity):
    rows = [(i + 1, t, 2, 1) for i, t in enumerate(TEXTS)]
    docs = docs_table(rows)
    embedder = BatchedEmbedder(capacity=capacity, use_scheduler=False)
    factory, _, _ = run_index(docs, embedder(docs.text))
    assert embedder.in_flight_max == capacity
    assert sorted(t for call in embedder.calls for t in call) == sorted(TEXTS)
    same_index(factory.built, reference_rows(rows)[0])


def test_no_event_loop_is_made_per_row(monkeypatch, fresh_runtime):
    """``asyncio.run`` is gone from the document path AND the query path
    of the index node: both gather on the process's persistent loop."""

    def refuse(*_a, **_kw):
        raise AssertionError("asyncio.run on the index node's path")

    monkeypatch.setattr(asyncio, "run", refuse)
    rows = [(i + 1, t, 2, 1) for i, t in enumerate(TEXTS[:4])]
    docs = docs_table(rows)
    queries = pwd.table_from_markdown(
        """
        | q     | __time__
    90  | alpha | 1000
    91  | theta | 1000
    """
    )
    embedder = BatchedEmbedder()
    factory = RecordingFactory()
    raw = _build_index_operator(
        docs, queries, factory, embedder(docs.text), embedder(queries.q),
        index_metadata=docs.meta, k=2,
    )
    loops: set = set()
    inner = embedder._batch

    def batch(texts):
        loops.add(threading.current_thread().name)
        return inner(texts)

    embedder._batcher.batch_fn = batch
    seen: list = []
    pw.io.subscribe(raw, on_change=lambda k, row, tm, add: seen.append(row))
    pw.run()
    assert len(seen) == 2
    # the documents in one call, the two queries of the timestamp in another
    assert [sorted(c) for c in embedder.calls] == [sorted(TEXTS[:4]), ["alpha", "theta"]]
    assert loops == {"pw-tick"}


# -- real encoders on the CPU ---------------------------------------------------


MIXED = [
    "a",
    "one two three four five six seven eight nine ten eleven twelve",
    "short text",
    " ".join(f"w{i}" for i in range(40)),
    "mid length row of seven words here",
]


def encoder_flush(enc, texts):
    """``texts`` through ``SentenceTransformerEmbedder(encoder=enc)`` as one
    flush of the index node; returns the vectors in the order of ``texts``."""
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    rows = [(i + 1, t, 2, 1) for i, t in enumerate(texts)]
    docs = docs_table(rows)
    embedder = SentenceTransformerEmbedder(encoder=enc)
    factory, payloads, _ = run_index(docs, embedder(docs.text))
    by_text = {payloads[key][0]: data for key, (data, _m) in factory.built.rows.items()}
    return np.stack([by_text[t] for t in texts])


def small_encoder():
    import jax.numpy as jnp

    from pathway_tpu.models.encoder import EncoderConfig, SentenceEncoder

    cfg = EncoderConfig(vocab_size=512, hidden_dim=32, num_layers=2, num_heads=2,
                        mlp_dim=64, max_len=64, dtype=jnp.float32)
    return SentenceEncoder(cfg=cfg, max_length=64)


def count_launches(enc, attr: str = "_apply") -> list[tuple]:
    """The shapes of ``ids`` in every launch of ``enc``'s dense forward
    (``_apply``) or packed one (``_apply_ragged``) from here on."""
    launches: list[tuple] = []
    apply = getattr(enc, attr)

    def counting(params, ids, *rest, **kw):
        launches.append(tuple(ids.shape))
        return apply(params, ids, *rest, **kw)

    setattr(enc, attr, counting)
    return launches


def test_a_small_flush_launches_the_programs_of_lone_rows(fresh_runtime):
    """The files of one scan go out one row a launch inside their one tick:
    the same programs as each row alone, so the vectors are equal exactly
    and nothing compiles."""
    from pathway_tpu.internals.flight_recorder import compile_stats, ingest_stats

    enc = small_encoder()
    alone = np.stack([enc.encode([t])[0] for t in MIXED])
    launches = count_launches(enc)
    compiled = dict(compile_stats())
    ticks = fresh_runtime.stats()["ticks_total"]
    got = encoder_flush(enc, MIXED)
    assert fresh_runtime.stats()["ticks_total"] - ticks == 1
    assert len(launches) == len(MIXED) and {rows for rows, _ in launches} == {1}
    assert dict(compile_stats()) == compiled
    np.testing.assert_array_equal(got, alone)
    assert ingest_stats()["docs_total"] >= len(MIXED)


def test_a_bulk_flush_takes_the_row_buckets(fresh_runtime):
    """Thirty-five rows of one sequence bucket (a server started over a
    corpus) launch at 32 rows, and the three left over alone.  Within 1e-5
    of each row alone, not exactly: a launch of another row count is
    another compiled program, whose float32 reductions may be ordered
    otherwise."""
    from pathway_tpu.models.encoder import TAIL_ROWS

    enc = small_encoder()
    texts = [f"w{i} w{i + 1} w{i + 2}" for i in range(TAIL_ROWS + 3)]
    alone = np.stack([enc.encode([t])[0] for t in texts])
    launches = count_launches(enc)
    got = encoder_flush(enc, texts)
    assert sorted(rows for rows, _ in launches) == [1, 1, 1, TAIL_ROWS]
    assert fresh_runtime.stats()["ticks_total"] == 1
    np.testing.assert_allclose(got, alone, atol=1e-5, rtol=0)


def tiny_moe_encoder(**over):
    import jax

    from pathway_tpu.models import causal_moe_embedder as cme
    from pathway_tpu.models.encoder import SentenceEncoder

    cfg = cme.CausalMoeEmbedderConfig(
        vocab_size=512, hidden_dim=32, head_dim=8, num_kv_heads=2,
        layer_types=("full", "window"), heads_per_layer=(4, 4),
        mlp_types=("dense", "sparse"), window=8, dense_mlp_dim=64,
        num_experts=4, top_k=2, expert_dim=16, shared_expert_dim=16,
        max_len=64, seq_buckets=(8, 16, 32, 64), q_block=16, **over,
    )
    params = cme.init_params(cfg, jax.random.PRNGKey(0))
    return SentenceEncoder(cfg=cfg, max_length=64, params=params)


def test_language_model_embedder_launches_a_flush_as_one_packed_program(fresh_runtime):
    """The config's default: the files of one scan share ONE launch of a
    token-bucket program that the first dispatch's warm-up already minted,
    and no dense program exists."""
    from pathway_tpu.internals.flight_recorder import compile_stats

    enc = tiny_moe_encoder(token_buckets=(32, 64, 128))
    assert enc.cfg.attention_impl == "ragged" and enc.cfg.packed_row_buckets == (32,)
    alone = np.stack([enc.encode([t])[0] for t in MIXED])  # warms every token bucket
    dense, packed = count_launches(enc), count_launches(enc, "_apply_ragged")
    compiled = dict(compile_stats())
    ticks = fresh_runtime.stats()["ticks_total"]
    got = encoder_flush(enc, MIXED)
    assert fresh_runtime.stats()["ticks_total"] - ticks == 1
    assert dense == [] and len(packed) == 1 and packed[0][0] in enc.cfg.token_buckets
    assert dict(compile_stats()) == compiled
    # another program than a lone row's (a larger token bucket): float32 sums
    # in another order, the same products
    np.testing.assert_allclose(got, alone, atol=1e-5, rtol=0)


def test_language_model_embedder_launches_one_row_programs(fresh_runtime):
    """The dense dispatch of the same config (``attention_impl="xla"``)."""
    from pathway_tpu.internals.flight_recorder import compile_stats
    from pathway_tpu.models.encoder import BATCH_BUCKETS, EncoderConfig, packed_plan

    enc = tiny_moe_encoder(attention_impl="xla")
    assert enc.cfg.batch_buckets == (1,) and EncoderConfig().batch_buckets == BATCH_BUCKETS
    alone = np.stack([enc.encode([t])[0] for t in MIXED])  # compiles (1, seq) only
    launches = count_launches(enc)
    compiled = dict(compile_stats())
    ticks = fresh_runtime.stats()["ticks_total"]
    got = encoder_flush(enc, MIXED)
    assert fresh_runtime.stats()["ticks_total"] - ticks == 1
    # N one-row launches of shapes a lone document already compiled
    assert len(launches) == len(MIXED) and {rows for rows, _ in launches} == {1}
    assert len({seq for _, seq in launches}) > 1  # mixed lengths, several buckets
    assert dict(compile_stats()) == compiled
    np.testing.assert_array_equal(got, alone)  # the same programs ran
    # the config's row buckets hold for a bulk group too: forty rows of one
    # sequence bucket are forty launches, where a BERT takes 32 and 8
    plan = packed_plan([5] * 40, 64, seq_buckets=enc.cfg.seq_buckets,
                       batch_buckets=enc.cfg.batch_buckets)
    assert [(seq, bb, len(rows)) for seq, bb, rows in plan] == [(8, 1, 1)] * 40
    assert [bb for _, bb, _ in packed_plan([5] * 40, 64)] == [32, 8]
