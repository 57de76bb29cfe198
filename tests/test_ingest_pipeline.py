"""PR 5: packed ingest pipeline — per-bucket dispatch parity, token-budget
batching, overlap pipeline, device-resident embed→upsert, tokenizer cache."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models.encoder import (
    BATCH_BUCKETS,
    EncoderConfig,
    SentenceEncoder,
    bucketed_dispatch,
    pad_chunk,
    packed_plan,
    packed_prepare,
)

SMALL = EncoderConfig(
    vocab_size=1024, hidden_dim=32, num_layers=2, num_heads=4, mlp_dim=64,
    max_len=128, dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def enc():
    return SentenceEncoder(cfg=SMALL, max_length=128)


def _mixed_texts(n, seed=0, max_words=110):
    rng = np.random.default_rng(seed)
    return [
        " ".join(f"w{rng.integers(0, 50)}" for _ in range(int(k)))
        for k in rng.integers(1, max_words, size=n)
    ]


# ---------------------------------------------------------------------------
# per-bucket packed dispatch
# ---------------------------------------------------------------------------


def test_packed_bit_exact_vs_manual_bucket_dispatch(enc):
    """Rows of one seq bucket dispatched by the packed path must be
    BIT-exact with a hand-built pad_chunk dispatch at the same (bb, seq)
    shape — the packed path adds no numerics of its own, including the
    pad-row pooling-mask convention (pad rows get mask[0]=1, no 0/0)."""
    texts = _mixed_texts(10, seed=3, max_words=25)  # all land in seq 32
    ids, mask = enc.tokenizer.encode_batch(texts, max_length=128)
    fwd = lambda i, m: enc._apply(enc.params, i, m)  # noqa: E731
    out_packed = bucketed_dispatch(fwd, ids, mask, 128, vocab_size=1024)
    pids, pmask, _ = pad_chunk(ids[:, :32], mask[:, :32], 32, 32, ids_dtype=np.uint16)
    manual = np.asarray(fwd(jnp.asarray(pids), jnp.asarray(pmask)), np.float32)
    np.testing.assert_array_equal(out_packed, manual[:10])
    assert np.isfinite(out_packed).all()


def test_order_restoration_under_shuffled_lengths(enc):
    """Mixed lengths arrive interleaved; results must come back in
    submission order (each row equal to encoding it alone)."""
    texts = _mixed_texts(23, seed=7)
    batch = enc.encode(texts)
    for i in [0, 5, 11, 22]:
        np.testing.assert_allclose(
            batch[i], enc.encode([texts[i]])[0], atol=1e-5
        )


def test_packed_plan_groups_and_token_budget():
    lengths = [10, 200, 30, 33, 10, 64]
    plan = packed_plan(lengths, 256)
    # per-row buckets: 10→32, 200→256, 30→32, 33→64, 10→32, 64→64
    by_seq = {seq: list(rows) for seq, _bb, rows in plan}
    assert sorted(by_seq) == [32, 64, 256]
    assert by_seq[32] == [0, 2, 4] and by_seq[64] == [3, 5] and by_seq[256] == [1]
    # token budget caps bb*seq per launch
    plan_b = packed_plan([100] * 64, 128, max_tokens=128 * 8)
    assert all(bb <= 8 for _s, bb, _r in plan_b)
    assert sum(len(r) for _s, _bb, r in plan_b) == 64
    # plans only ever use grid shapes → compiled-executable set is bounded
    for _seq, bb, _rows in plan + plan_b:
        assert bb in BATCH_BUCKETS


def test_packed_prepare_padding_stats():
    lengths = np.array([4, 4, 4, 4])
    ids = np.zeros((4, 64), np.int32)
    mask = np.zeros((4, 64), np.int32)
    ids[:, :4] = 7
    mask[:, :4] = 1
    prepared, stats = packed_prepare(ids, mask, 64, vocab_size=1024)
    assert stats["real_tokens"] == 16
    # 4 rows → batch bucket 4, seq bucket 32: padded = 4*32
    assert stats["padded_tokens"] == 4 * 32
    assert len(prepared) == 1


def test_compile_set_flat_across_mixed_length_batches(enc):
    """Heterogeneous corpora must reuse the compiled grid: two different
    length mixes drawn from the same buckets add zero compilations."""
    from pathway_tpu.internals.flight_recorder import compile_stats

    fwd = lambda i, m: enc._apply(enc.params, i, m)  # noqa: E731
    batches = []
    for seed in (1, 2, 3, 4):
        texts = _mixed_texts(20, seed=seed)
        batches.append(enc.tokenizer.encode_batch(texts, max_length=128))
    # first pass warms whatever grid shapes these mixes hit...
    for ids, mask in batches:
        bucketed_dispatch(fwd, ids, mask, 128, vocab_size=1024)
    before = compile_stats().get("encoder.forward", 0)
    # ...after which ANY reordering/repetition of heterogeneous-length
    # traffic re-uses the compiled set: zero new compilations
    for ids, mask in batches + batches[::-1]:
        bucketed_dispatch(fwd, ids, mask, 128, vocab_size=1024)
    assert compile_stats().get("encoder.forward", 0) == before


# ---------------------------------------------------------------------------
# token-budget flush (AsyncMicroBatcher)
# ---------------------------------------------------------------------------


def test_async_micro_batcher_token_budget_flush():
    from pathway_tpu.xpacks.llm._utils import AsyncMicroBatcher

    calls: list[list[str]] = []

    def batch_fn(items):
        calls.append(list(items))
        return items

    batcher = AsyncMicroBatcher(
        batch_fn, max_batch=100, use_scheduler=False, max_tokens=10
    )

    async def run():
        # 4 docs x 4 estimated tokens (2 words + CLS/SEP): the budget of
        # 10 flushes after the 3rd, the 4th rides the round-end flush
        return await asyncio.gather(*[batcher.call("a b") for _ in range(4)])

    results = asyncio.run(run())
    assert results == ["a b"] * 4
    assert [len(c) for c in calls] == [3, 1]


def test_scheduler_budget_chunks():
    from pathway_tpu.runtime import WorkGroup, budget_chunks as _budget_chunks
    from pathway_tpu.xpacks.llm._utils import AsyncMicroBatcher

    class Item:
        def __init__(self, payload):
            self.payload = payload

    # a WorkGroup without token attrs chunks by count only
    group = WorkGroup("g", lambda xs: xs, max_batch=2)
    chunks = _budget_chunks(group, [Item(i) for i in range(5)])
    assert [len(c) for c in chunks] == [2, 2, 1]
    # a batcher-as-group with a budget chunks by token mass too
    batcher = AsyncMicroBatcher(
        lambda xs: xs, max_batch=10, use_scheduler=False, max_tokens=8
    )
    items = [Item("one two"), Item("three four"), Item("five six")]
    chunks = _budget_chunks(batcher, items)  # 4 tokens each, budget 8
    assert [len(c) for c in chunks] == [2, 1]
    assert all(len(c) >= 1 for c in chunks)


# ---------------------------------------------------------------------------
# overlap pipeline
# ---------------------------------------------------------------------------


def test_pipeline_embeddings_match_encode(enc):
    from pathway_tpu.xpacks.llm._ingest import IngestPipeline

    texts = _mixed_texts(17, seed=11)
    with IngestPipeline(enc) as pipe:
        futs = [pipe.submit(texts[i : i + 5]) for i in range(0, 17, 5)]
        out = np.concatenate([f.result(timeout=60) for f in futs])
    # sub-batches land in smaller batch buckets than one big encode —
    # same values up to XLA's per-shape vectorization (~1e-7 on CPU)
    np.testing.assert_allclose(out, enc.encode(texts), atol=1e-5)


def test_pipeline_upserts_device_resident(enc):
    from pathway_tpu.stdlib.indexing.retrievers import BruteForceKnnIndex
    from pathway_tpu.xpacks.llm._ingest import IngestPipeline

    texts = _mixed_texts(12, seed=13)
    index = BruteForceKnnIndex(dim=enc.dim, capacity=32)
    with IngestPipeline(enc, index) as pipe:
        n = pipe.submit(texts, keys=[f"d{i}" for i in range(12)]).result(timeout=60)
    assert n == 12
    # nothing searched yet: the staged batches must still be device-side
    assert index.index._staged_device, "expected device-staged batches"
    embs = enc.encode(texts)
    for i in (0, 7, 11):
        row = index.search([(embs[i], 1, None)])[0]
        assert row[0][0] == f"d{i}"
        assert row[0][1] == pytest.approx(1.0, abs=1e-5)


def test_pipeline_drains_under_embedder_chaos(enc):
    """PATHWAY_FAULTS chaos on the embedder site fails individual batches
    but never wedges the workers: later batches still complete and the
    pipeline closes cleanly."""
    from pathway_tpu.stdlib.indexing.retrievers import BruteForceKnnIndex
    from pathway_tpu.testing import faults
    from pathway_tpu.xpacks.llm._ingest import IngestPipeline

    texts = _mixed_texts(30, seed=17)
    index = BruteForceKnnIndex(dim=enc.dim, capacity=64)
    ok = errs = 0
    with faults.scoped(seed=5, rules={"embedder": {"fail": 0.4}}):
        with IngestPipeline(enc, index) as pipe:
            futs = [
                pipe.submit([t], keys=[f"c{i}"]) for i, t in enumerate(texts)
            ]
            for f in futs:
                try:
                    f.result(timeout=60)
                    ok += 1
                except faults.FaultInjected:
                    errs += 1
    assert ok + errs == 30 and errs > 0 and ok > 0
    # a clean batch AFTER chaos proves the workers survived
    with IngestPipeline(enc, index) as pipe:
        assert pipe.submit(texts[:3], keys=["x0", "x1", "x2"]).result(timeout=60) == 3


def test_pipeline_tokenize_error_fails_only_that_batch(enc):
    from pathway_tpu.xpacks.llm._ingest import IngestPipeline

    with IngestPipeline(enc) as pipe:
        bad = pipe.submit([None])  # tokenizer raises on non-str
        good = pipe.submit(["hello world"])
        with pytest.raises(Exception):
            bad.result(timeout=60)
        assert good.result(timeout=60).shape == (1, enc.dim)


# ---------------------------------------------------------------------------
# device-resident upsert parity (ops/knn.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("metric", ["cos", "dot", "l2sq"])
def test_upsert_batch_device_parity_with_host(metric):
    from pathway_tpu.ops.knn import DeviceKnnIndex

    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(6, 16)).astype(np.float32)
    host = DeviceKnnIndex(dim=16, metric=metric, capacity=16)
    dev = DeviceKnnIndex(dim=16, metric=metric, capacity=16)
    for i in range(6):
        host.upsert(f"k{i}", vecs[i])
    # device batch padded to a dispatch bucket: pad rows carry garbage
    # that must be DROPPED by the out-of-bounds scatter
    padded = np.full((8, 16), 123.0, np.float32)
    padded[:6] = vecs
    dev.upsert_batch([f"k{i}" for i in range(6)], jnp.asarray(padded))
    q = rng.normal(size=(3, 16)).astype(np.float32)
    for row_h, row_d in zip(host.search(q, 4), dev.search(q, 4)):
        assert [k for k, _ in row_h] == [k for k, _ in row_d]
        np.testing.assert_allclose(
            [s for _, s in row_h], [s for _, s in row_d], atol=1e-5
        )


def test_upsert_batch_interleaved_with_host_writes_last_wins():
    from pathway_tpu.ops.knn import DeviceKnnIndex

    idx = DeviceKnnIndex(dim=4, metric="dot", capacity=8)
    a = np.array([1.0, 0, 0, 0], np.float32)
    b = np.array([0, 1.0, 0, 0], np.float32)
    # device write then NEWER host write for the same key: host must win
    idx.upsert_batch(["k"], jnp.asarray(a.reshape(1, 4)))
    idx.upsert("k", b)
    row = idx.search(np.array([b]), 1)[0]
    assert row[0][0] == "k" and row[0][1] == pytest.approx(1.0)
    # host write then NEWER device write: device must win
    idx.upsert("k", b)
    idx.upsert_batch(["k"], jnp.asarray(a.reshape(1, 4)))
    row = idx.search(np.array([a]), 1)[0]
    assert row[0][1] == pytest.approx(1.0)
    # remove after device stage: the key must be gone
    idx.upsert_batch(["gone"], jnp.asarray(a.reshape(1, 4)))
    idx.remove("gone")
    assert all(k != "gone" for r in idx.search(np.array([a]), 4) for k, _ in r)


def test_upsert_batch_duplicate_keys_last_wins():
    """A repeated key inside ONE device batch must resolve like the host
    path: the LAST row wins (duplicate scatter indices are undefined
    order in XLA, so the earlier row is dropped before dispatch)."""
    from pathway_tpu.ops.knn import DeviceKnnIndex

    idx = DeviceKnnIndex(dim=4, metric="dot", capacity=8)
    a = np.array([[1.0, 0, 0, 0]], np.float32)
    b = np.array([[0, 1.0, 0, 0]], np.float32)
    idx.upsert_batch(["k", "k"], jnp.asarray(np.concatenate([a, b])))
    assert len(idx) == 1
    row = idx.search(b, 1)[0]
    assert row[0][0] == "k" and row[0][1] == pytest.approx(1.0)
    row = idx.search(a, 1)[0]
    assert row[0][1] == pytest.approx(0.0, abs=1e-6)


def test_upsert_batch_grows_capacity():
    from pathway_tpu.ops.knn import DeviceKnnIndex

    idx = DeviceKnnIndex(dim=4, metric="dot", capacity=8)
    vecs = np.eye(4, dtype=np.float32)
    for start in range(0, 24, 4):
        keys = [f"k{start + j}" for j in range(4)]
        idx.upsert_batch(keys, jnp.asarray(vecs))
    assert len(idx) == 24
    assert idx.capacity >= 24
    out = idx.search(vecs[:1], 1)[0]
    assert out and out[0][1] == pytest.approx(1.0)


def test_external_index_flush_batches_adds(enc):
    """ExternalIndexNode applies one flush's adds as a single batch with
    final-state-per-key semantics (retract+insert of the same key ends as
    one upsert)."""
    from pathway_tpu.stdlib.indexing.lowering import ExternalIndexNode
    from pathway_tpu.stdlib.indexing.retrievers import BruteForceKnnIndex

    calls = []
    index = BruteForceKnnIndex(dim=4, capacity=16)
    orig = index.add_batch

    def spy(keys, datas, metas):
        calls.append(list(keys))
        return orig(keys, datas, metas)

    index.add_batch = spy
    node = ExternalIndexNode(
        index,
        doc_data_fn=lambda ctx: ctx[1][0],
        doc_meta_fn=lambda ctx: None,
        query_data_fn=lambda ctx: ctx[1][0],
        query_k_fn=lambda ctx: 1,
        query_filter_fn=lambda ctx: None,
        doc_payload_fn=lambda ctx: tuple(ctx[1]),
    )
    v_old = np.array([1.0, 0, 0, 0], np.float32)
    v_new = np.array([0, 1.0, 0, 0], np.float32)
    w = np.array([0, 0, 1.0, 0], np.float32)
    node.receive(0, [("a", (v_old,), 1), ("b", (w,), 1)])
    node.flush(1)
    # update a (retract old, insert new) and drop b, all in one flush
    node.receive(0, [("a", (v_old,), -1), ("a", (v_new,), 1), ("b", (w,), -1)])
    node.flush(2)
    assert calls == [["a", "b"], ["a"]]
    res = index.search([(v_new, 2, None)])[0]
    assert [k for k, _ in res] == ["a"]


# ---------------------------------------------------------------------------
# tokenizer LRU memoization
# ---------------------------------------------------------------------------


def test_tokenizer_cache_hits_and_identity(monkeypatch):
    from pathway_tpu.internals.flight_recorder import ingest_stats
    from pathway_tpu.models import tokenizer as tok_mod

    tok_mod.reset_token_cache()
    tok = tok_mod.HashTokenizer(vocab_size=512)
    texts = ["alpha beta", "gamma", "alpha beta"]
    ids1, mask1 = tok.encode_batch(texts, max_length=16)
    before = ingest_stats()
    ids2, mask2 = tok.encode_batch(texts, max_length=16)
    after = ingest_stats()
    np.testing.assert_array_equal(ids1, ids2)
    np.testing.assert_array_equal(mask1, mask2)
    # second pass is all hits (dedup within the first batch also hits)
    assert (
        after["tokenizer_cache_hits"] - before["tokenizer_cache_hits"] == 3
    )
    assert after["tokenizer_cache_misses"] == before["tokenizer_cache_misses"]
    # identity with the cache disabled
    monkeypatch.setenv("PATHWAY_TOKENIZER_CACHE", "0")
    tok_mod.reset_token_cache()
    ids3, mask3 = tok.encode_batch(texts, max_length=16)
    np.testing.assert_array_equal(ids1, ids3)
    np.testing.assert_array_equal(mask1, mask3)
    tok_mod.reset_token_cache()


def test_tokenizer_cache_bounded(monkeypatch):
    from pathway_tpu.models import tokenizer as tok_mod

    monkeypatch.setenv("PATHWAY_TOKENIZER_CACHE", "8")
    tok_mod.reset_token_cache()
    tok = tok_mod.HashTokenizer(vocab_size=512)
    for i in range(40):
        tok.encode_batch([f"text number {i}"], max_length=16)
    assert len(tok_mod.token_cache()) <= 8
    tok_mod.reset_token_cache()


def test_tokenizer_cache_status_lines():
    from pathway_tpu.internals.flight_recorder import (
        observability_metrics_lines,
    )

    lines = "\n".join(observability_metrics_lines())
    assert "pathway_tokenizer_cache_hits_total" in lines
    assert "pathway_ingest_docs_total" in lines
    assert "pathway_embed_padding_efficiency" in lines
