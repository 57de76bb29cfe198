"""ISSUE 9: ragged packed-batch attention — kernel parity, packed-layout
dispatch, fused-serving-tick integration, padding-metric decomposition.

The Pallas kernel itself runs in interpret mode on the CPU mesh
(``PATHWAY_RAGGED_KERNEL=pallas``) so tier-1 exercises the real kernel
body, not just the XLA reference."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pathway_tpu.models.encoder import (
    BATCH_BUCKETS,
    EncoderConfig,
    SentenceEncoder,
    TOKEN_BUCKETS,
    ragged_plan,
    ragged_prepare,
)

SMALL = EncoderConfig(
    vocab_size=1024, hidden_dim=32, num_layers=2, num_heads=4, mlp_dim=64,
    max_len=128, dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def golden():
    """flax golden-path encoder; every ragged encoder borrows its params."""
    return SentenceEncoder(cfg=SMALL, max_length=128)


def _ragged(golden, dtype=jnp.float32, **kw):
    import dataclasses

    enc = SentenceEncoder(
        cfg=dataclasses.replace(SMALL, dtype=dtype, attention_impl="ragged"),
        max_length=128,
        **kw,
    )
    enc.params = golden.params
    return enc


def _mixed_texts(n, seed=0, max_words=110):
    rng = np.random.default_rng(seed)
    return [
        " ".join(f"w{rng.integers(0, 50)}" for _ in range(int(k)))
        for k in rng.integers(1, max_words, size=n)
    ]


# ---------------------------------------------------------------------------
# kernel-level: pallas (interpret) and XLA reference vs naive attention
# ---------------------------------------------------------------------------


def _naive_rowwise(q, k, v, cu):
    out = np.zeros_like(np.asarray(q))
    qn, kn, vn = map(np.asarray, (q, k, v))
    d = q.shape[-1]
    for a, b in zip(cu[:-1], cu[1:]):
        s = np.einsum("qhd,khd->hqk", qn[a:b], kn[a:b]) / np.sqrt(d)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[a:b] = np.einsum("hqk,khd->qhd", p, vn[a:b])
    return out


def _packed_inputs(lengths, t_bucket, n_rows, seed=0, h=4, d=8):
    rng = np.random.default_rng(seed)
    cu = np.concatenate([[0], np.cumsum(lengths)])
    seg = np.full(t_bucket, n_rows, np.int32)
    pos = np.zeros(t_bucket, np.int32)
    starts = np.zeros(n_rows, np.int32)
    for r, (a, b) in enumerate(zip(cu[:-1], cu[1:])):
        seg[a:b] = r
        pos[a:b] = np.arange(b - a)
        starts[r] = a
    q, k, v = (
        jnp.asarray(rng.standard_normal((t_bucket, h, d)), jnp.float32)
        for _ in range(3)
    )
    return q, k, v, seg, pos, starts, cu


@pytest.mark.parametrize("mode", ["pallas", "reference"])
def test_kernel_matches_naive_rowwise(mode):
    """Both kernel modes must reproduce per-row softmax attention exactly
    (pallas runs in interpret mode on CPU): mixed lengths, single-token
    rows, and a pad tail in one launch."""
    from pathway_tpu.ops.ragged_attention import (
        ragged_attention,
        ragged_block,
        ragged_bounds,
    )

    lengths = [5, 37, 1, 60, 25]  # includes a single-token row
    t = 256
    q, k, v, seg, pos, starts, cu = _packed_inputs(lengths, t, 8)
    bounds = jnp.asarray(ragged_bounds(cu, t, ragged_block(t)))
    out = ragged_attention(
        q, k, v, jnp.asarray(seg),
        pos=jnp.asarray(pos), starts=jnp.asarray(starts),
        bounds=bounds, num_rows=8, dense_s=64, mode=mode,
    )
    expect = _naive_rowwise(q, k, v, cu)
    t_real = int(cu[-1])
    np.testing.assert_allclose(
        np.asarray(out)[:t_real], expect[:t_real], atol=2e-6, rtol=2e-6
    )
    # the pad tail must come back finite (pooling drops it structurally,
    # but NaN would poison any reduction that touches it)
    assert np.isfinite(np.asarray(out)).all()


def test_kernel_small_launch_no_full_block_padding():
    """A 1-row tick must not pad to a full 128-token block: the 32-token
    bucket launches a single sub-block program (satellite: '1-row batches
    must not pad to a full block')."""
    from pathway_tpu.ops.ragged_attention import (
        ragged_attention,
        ragged_block,
        ragged_bounds,
    )

    lengths = [5]
    t = 32  # sub-block token bucket
    assert ragged_block(t) == 32
    q, k, v, seg, pos, starts, cu = _packed_inputs(lengths, t, 1, seed=3)
    bounds = jnp.asarray(ragged_bounds(cu, t, 32))
    out = ragged_attention(
        q, k, v, jnp.asarray(seg),
        pos=jnp.asarray(pos), starts=jnp.asarray(starts),
        bounds=bounds, num_rows=1, dense_s=32, mode="pallas",
    )
    expect = _naive_rowwise(q, k, v, cu)
    np.testing.assert_allclose(
        np.asarray(out)[:5], expect[:5], atol=2e-6, rtol=2e-6
    )


def test_geometry_validation_names_the_knob():
    """Satellite bugfix: head_dim the 128-lane tile can't divide and
    double-scaling both fail UP FRONT with the impl knob named, instead
    of deep inside Mosaic lowering / silently wrong numerics."""
    from pathway_tpu.ops.flash_attention import flash_attention
    from pathway_tpu.ops.ragged_attention import ragged_attention

    bad = jnp.zeros((2, 32, 4, 48), jnp.float32)  # head_dim 48
    with pytest.raises(ValueError, match="attention_impl='pallas'"):
        flash_attention(bad, bad, bad)
    with pytest.raises(ValueError, match="attention_impl='ragged'"):
        ragged_attention(
            jnp.zeros((32, 4, 48), jnp.float32),
            jnp.zeros((32, 4, 48), jnp.float32),
            jnp.zeros((32, 4, 48), jnp.float32),
            jnp.zeros((32,), jnp.int32),
        )
    good = jnp.zeros((2, 32, 4, 32), jnp.float32)
    with pytest.raises(ValueError, match="double-scale"):
        flash_attention(good, good, good, sm_scale=0.5, pre_scaled=True)
    with pytest.raises(ValueError, match="positive finite"):
        flash_attention(good, good, good, sm_scale=float("nan"))


def test_ragged_bounds_skip_pad_tail_and_span_rows():
    from pathway_tpu.ops.ragged_attention import ragged_bounds

    # rows 100+100 tokens in a 384-token bucket, block 128
    bounds = ragged_bounds([0, 100, 200], 384, 128)
    assert bounds.shape == (3, 2)
    # q block 0 (tokens 0-127) spans rows 0 and 1 -> kv blocks [0, 2)
    assert list(bounds[0]) == [0, 2]
    # q block 1 (tokens 128-255) covers row 1's tail -> kv blocks [0, 2)
    assert list(bounds[1]) == [0, 2]
    # q block 2 is pure pad -> zero-trip loop
    assert list(bounds[2]) == [0, 0]


# ---------------------------------------------------------------------------
# packed-layout dispatch: parity with the flax golden path
# ---------------------------------------------------------------------------


def test_pooled_parity_vs_flax_golden_shuffled_mixed_lengths(golden):
    """Pooled embeddings through the full ragged dispatch (XLA reference
    mode) must match the flax golden path to 1e-5 in f32 across shuffled
    mixed lengths — the acceptance pin."""
    texts = _mixed_texts(37, seed=11)
    ref = golden.encode(texts)
    got = _ragged(golden).encode(texts)
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)


def test_pooled_parity_pallas_interpret_mode(golden, monkeypatch):
    """Same parity with the REAL Pallas kernel (interpret mode on CPU):
    tier-1 exercises the kernel body, not just the reference."""
    monkeypatch.setenv("PATHWAY_RAGGED_KERNEL", "pallas")
    texts = _mixed_texts(9, seed=5, max_words=40)
    ref = golden.encode(texts)
    got = _ragged(golden).encode(texts)
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)


def test_bf16_ragged_runs_and_tracks_f32(golden):
    """bf16 activations (the chip configuration) stay finite and close to
    the f32 result at bf16-appropriate tolerance."""
    texts = _mixed_texts(12, seed=8)
    f32 = _ragged(golden).encode(texts)
    bf16 = _ragged(golden, dtype=jnp.bfloat16).encode(texts)
    assert np.isfinite(bf16).all()
    np.testing.assert_allclose(bf16, f32, atol=5e-2)


def test_single_token_and_1_row_batches(golden):
    """Degenerate rows: a 1-row batch and single-token rows must encode
    exactly like the golden path, and the 1-row launch must use a
    sub-block token bucket (no full-block padding)."""
    ref = golden.encode(["x"])
    got = _ragged(golden).encode(["x"])
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)
    prepared, stats = ragged_prepare(
        *golden.tokenizer.encode_batch(["x"], max_length=128), 128
    )
    assert len(prepared) == 1
    assert stats["padded_tokens"] == 32  # bucket 32, not a 128 block
    assert prepared[0][0].ids.shape == (32,)


def test_order_restoration_under_shuffled_lengths(golden):
    texts = _mixed_texts(23, seed=7)
    enc = _ragged(golden)
    batch = enc.encode(texts)
    for i in [0, 5, 11, 22]:
        np.testing.assert_allclose(
            batch[i], enc.encode([texts[i]])[0], atol=1e-5
        )


def test_cross_encoder_ragged_parity():
    from pathway_tpu.models import CrossEncoder

    pairs = [
        ("query one", "doc one " * 12),
        ("query one", "different doc"),
        ("q", "d"),
    ]
    base = CrossEncoder(cfg=SMALL, max_length=128)
    ref = base.predict(pairs)
    import dataclasses

    rag = CrossEncoder(
        cfg=dataclasses.replace(SMALL, attention_impl="ragged"),
        max_length=128,
    )
    rag.params = base.params
    np.testing.assert_allclose(rag.predict(pairs), ref, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# plan / prepare invariants
# ---------------------------------------------------------------------------


def test_ragged_plan_budget_and_order():
    # mixed plan: one submission-order launch per token-budget window
    groups = ragged_plan([100, 100, 100, 100], 128, max_tokens=250,
                         mix_buckets=True)
    assert [list(g) for g in groups] == [[0, 1], [2, 3]]
    # grouped plan: rows regroup by their own seq bucket, batch-bucket
    # chunked (the XLA reference's attention-cost guard)
    groups = ragged_plan([10, 100, 12, 90, 11], 128, mix_buckets=False)
    by_first = {int(g[0]): list(g) for g in groups}
    assert by_first[0] == [0, 2, 4] and by_first[1] == [1, 3]


def test_ragged_prepare_stats_and_buckets(golden):
    texts = _mixed_texts(30, seed=2)
    ids, mask = golden.tokenizer.encode_batch(texts, max_length=128)
    prepared, stats = ragged_prepare(ids, mask, 128, vocab_size=1024)
    # intra-bucket padding is structurally zero on the ragged layout
    assert stats["row_tokens"] == stats["real_tokens"]
    covered = np.concatenate([rows for _p, rows, _t in prepared])
    assert sorted(covered) == list(range(30))
    for payload, rows, tokens in prepared:
        assert tokens in TOKEN_BUCKETS
        assert payload.starts.shape[0] in BATCH_BUCKETS
        # pad tail carries the out-of-bounds segment id
        real = int(
            sum(min(int(m.sum()), 128) for m in mask[rows])
        )
        assert (np.asarray(payload.seg) == payload.starts.shape[0]).sum() == (
            tokens - real
        )


def test_compile_set_flat_across_heterogeneous_corpora(golden):
    """Two different length mixes drawn from the same token/row buckets
    must add zero ragged-forward compilations — the one-launch path keeps
    the no-recompile guarantee observable via pathway_xla_compile_total."""
    from pathway_tpu.internals.flight_recorder import compile_stats

    enc = _ragged(golden)
    lengths = list(np.random.default_rng(0).integers(1, 110, size=24))
    rng = np.random.default_rng(1)
    corpora = []
    for seed in range(4):
        perm = rng.permutation(len(lengths))
        corpora.append(
            [" ".join(f"w{seed}{i}" for i in range(int(lengths[p])))
             for p in perm]
        )
    enc.encode(corpora[0])
    enc.encode(corpora[1])
    before = compile_stats().get("encoder.forward_ragged", 0)
    assert before > 0
    # shuffled re-mixes of the same length multiset: same token bucket,
    # same row bucket -> zero new compiles
    enc.encode(corpora[2])
    enc.encode(corpora[3])
    assert compile_stats().get("encoder.forward_ragged", 0) == before


# ---------------------------------------------------------------------------
# fused serving tick + ingest pipeline + runtime BULK_INGEST
# ---------------------------------------------------------------------------


def test_encode_padded_ragged_keeps_contract(golden):
    """One launch per tick: device output, pow2 row bucket, pad rows
    all-pad (all-zero) — and bit-close to the host path."""
    enc = _ragged(golden)
    texts = _mixed_texts(3, seed=4)
    dev, n = enc.encode_padded(texts)
    assert n == 3
    arr = np.asarray(dev, dtype=np.float32)
    assert arr.shape[0] in BATCH_BUCKETS and arr.shape[0] >= 3
    np.testing.assert_allclose(arr[:3], golden.encode(texts), atol=1e-5)
    # all-pad rows pool to the zero vector (segment-sum drops OOB ids)
    np.testing.assert_array_equal(arr[3:], 0.0)


def test_fused_serving_tick_parity_with_ragged_impl(golden, monkeypatch):
    """The serving tick's device handoff must work unchanged with
    attention_impl='ragged': ONE ragged launch, a DEVICE array handed to
    the search, results identical to the host path.  The wire dtype is
    bf16 by default now — the handoff is bf16-close to the host path and
    bit-close under the f32 opt-out."""
    from pathway_tpu.ops.knn import DeviceKnnIndex
    from pathway_tpu.xpacks.llm._scheduler import (
        _batch_embed,
        _batch_embed_device,
    )
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    enc = _ragged(golden)
    embedder = SentenceTransformerEmbedder(encoder=enc)
    texts = [f"query about item {i}" for i in range(3)]
    dev = _batch_embed_device(embedder, texts)
    assert isinstance(dev, jax.Array) and not isinstance(dev, np.ndarray)
    assert dev.dtype == jnp.bfloat16
    assert dev.shape[0] >= len(texts)
    host = _batch_embed(embedder, texts)
    np.testing.assert_allclose(
        np.asarray(dev, np.float32)[: len(texts)], host, atol=2e-2
    )
    monkeypatch.setenv("PATHWAY_SERVING_WIRE_DTYPE", "f32")
    dev_f32 = _batch_embed_device(embedder, texts)
    assert dev_f32.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(dev_f32, np.float32)[: len(texts)], host, atol=1e-5
    )
    monkeypatch.delenv("PATHWAY_SERVING_WIRE_DTYPE")

    idx = DeviceKnnIndex(dim=enc.dim, capacity=64)
    rng = np.random.default_rng(2)
    vecs = rng.standard_normal((10, enc.dim)).astype(np.float32)
    idx.upsert_batch([f"d{i}" for i in range(10)], vecs)
    r_dev = idx.search(dev, 4)[: len(texts)]
    r_host = idx.search(host, 4)
    assert [[k for k, _ in row] for row in r_dev] == [
        [k for k, _ in row] for row in r_host
    ]


def test_ingest_pipeline_ragged_parity(golden):
    """The ingest pipeline (its runtime BULK_INGEST chunks) must
    dispatch ragged payloads end to end: futures resolve to embeddings
    identical to direct encode, and with an index attached the staged
    device upsert searches identically."""
    from pathway_tpu.ops.knn import DeviceKnnIndex
    from pathway_tpu.xpacks.llm._ingest import IngestPipeline

    enc = _ragged(golden)
    texts = _mixed_texts(17, seed=13)
    with IngestPipeline(enc) as pipe:
        emb = pipe.submit(texts).result(timeout=120)
    np.testing.assert_allclose(emb, enc.encode(texts), atol=1e-6)

    index = DeviceKnnIndex(dim=enc.dim, capacity=64)
    with IngestPipeline(enc, index) as pipe:
        n = pipe.submit(texts, keys=[f"k{i}" for i in range(17)]).result(
            timeout=120
        )
    assert n == 17
    q = enc.encode([texts[3]])
    keys = [k for k, _ in index.search(q, 1)[0]]
    assert keys == ["k3"]


# ---------------------------------------------------------------------------
# observability: padding decomposition + attention_impl surfacing
# ---------------------------------------------------------------------------


def test_padding_metric_decomposition_and_status_lines(golden):
    from pathway_tpu.internals.flight_recorder import (
        ingest_stats,
        observability_metrics_lines,
        record_padding,
        reset_stage_metrics,
    )

    reset_stage_metrics()
    try:
        # packed-bucket shape: 90 real tokens laid out as 100 row-bucket
        # tokens inside a 128-token launch
        record_padding(90, 128, 100)
        st = ingest_stats()
        assert st["padding_efficiency"] == pytest.approx(90 / 128)
        assert st["intra_bucket_efficiency"] == pytest.approx(90 / 100)
        # ragged shape: row_tokens == real -> intra-bucket pins 1.0
        record_padding(910, 1024 - 128, 910)
        st = ingest_stats()
        assert st["intra_bucket_efficiency"] == pytest.approx(
            1000 / 1010
        )
        _ragged(golden)  # records attention_impl="ragged"
        lines = observability_metrics_lines()
        body = "\n".join(lines)
        assert "# TYPE pathway_embed_intra_bucket_efficiency gauge" in body
        assert 'pathway_attention_impl{impl="ragged"}' in body
    finally:
        reset_stage_metrics()


def test_runtime_stats_surface_attention_impl(golden):
    from pathway_tpu.runtime.executor import DeviceTickRuntime

    _ragged(golden)
    # a NON-global name: a bare "runtime" instance would shadow (and, on
    # GC, delete) the global runtime's weak provider registration.
    # stats() never spawns the executor thread — safe to probe directly.
    assert (
        DeviceTickRuntime(name="runtime-test-probe").stats()["attention_impl"]
        == "ragged"
    )


def test_attention_impl_env_knob(monkeypatch):
    from pathway_tpu.models.encoder import default_attention_impl

    monkeypatch.setenv("PATHWAY_ATTENTION_IMPL", "ragged")
    assert default_attention_impl() == "ragged"
    enc = SentenceEncoder(cfg=None, max_length=32)
    assert enc.cfg.attention_impl == "ragged"
    monkeypatch.setenv("PATHWAY_ATTENTION_IMPL", "bogus")
    with pytest.warns(UserWarning, match="PATHWAY_ATTENTION_IMPL"):
        assert default_attention_impl() == "flax"
