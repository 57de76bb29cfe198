"""Fused serving-tick megakernel tests (ISSUE 20).

Pins the fused-serving contract end to end:

* fused-vs-reference top-k parity BIT-EXACT at f32 — dense (f32/bf16
  storage), int8 codes + rescore ring, the forced Pallas megakernel
  body (interpret mode on CPU), mesh 1/2 sharding, and the tiered hot
  tier all produce the same keys AND scores as the staged legacy chain
  (the two-shard float32 index against the SINGLE-device one: keys
  exact, scores within the float32 bound of a reordered sum);
* exact tie order: equal scores surface lowest-slot-first in every
  formulation (the ``lax.top_k`` stable order the megakernel's online
  merge reproduces);
* normalize-exactly-once: cosine queries are normalized by exactly one
  stage (host, fused jit, or the tiered wrapper — never two of them),
  pinned by bit-exact parity;
* geometry validation raises NAMING the knob under a forced
  ``PATHWAY_SERVING_KERNEL=pallas`` on un-tileable shapes;
* launch accounting: a fused tick costs ≤ 2 launches (1 dense) while
  the staged quantized reference pays ≥ 4, the per-tick ``serving.tick``
  span carries the counts, and the
  ``pathway_serving_launches_total{stage=}`` family is declared AND
  emitted (both directions);
* cache hit/miss bit-exactness through ``RetrievePlane`` under the
  bf16-on-the-wire serving default;
* the kernel-registry lint: every mode literal the parser accepts
  appears in README's knob table, and vice versa (the fault-site
  registry idiom).
"""

from __future__ import annotations

import pathlib
import re
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pathway_tpu.ops import fused_serving as fs
from pathway_tpu.ops.knn import DeviceKnnIndex
from pathway_tpu.parallel import make_mesh
from pathway_tpu.parallel.index import ShardedKnnIndex
from pathway_tpu.tiering import TieredKnnIndex


@pytest.fixture(autouse=True)
def _fresh_launches():
    fs.reset_launch_metrics()
    yield
    fs.reset_launch_metrics()


def _vecs(n: int, dim: int = 16, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((n, dim)).astype(
        np.float32
    )


def _build(index_dtype: str = "f32", metric: str = "cos", n: int = 40,
           dim: int = 16, capacity: int = 64, mesh=None):
    cls_kw = {"mesh": mesh} if mesh is not None else {}
    cls = ShardedKnnIndex if mesh is not None else DeviceKnnIndex
    idx = cls(
        dim=dim, metric=metric, capacity=capacity, index_dtype=index_dtype,
        **cls_kw,
    )
    idx.upsert_batch([f"k{i:03d}" for i in range(n)], _vecs(n, dim))
    return idx


def _search(idx, q, k, mode, monkeypatch):
    monkeypatch.setenv("PATHWAY_SERVING_KERNEL", mode)
    return idx.search(q, k)


# ---------------------------------------------------------------------------
# fused-vs-reference parity (keys AND scores, bit-exact)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("index_dtype", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("metric", ["cos", "dot"])
def test_fused_vs_reference_parity(index_dtype, metric, monkeypatch):
    """The fused single-dispatch path is bit-identical to the staged
    separate-launch chain — host AND device queries, every storage
    dtype.  Each score element is the same length-D reduction in both
    formulations, so equality is exact, not approximate."""
    idx = _build(index_dtype, metric)
    q_host = _vecs(5, seed=3)
    q_dev = jnp.asarray(q_host)
    ref_h = _search(idx, q_host, 7, "reference", monkeypatch)
    ref_d = _search(idx, q_dev, 7, "reference", monkeypatch)
    for mode in ("auto", "fused"):
        assert _search(idx, q_host, 7, mode, monkeypatch) == ref_h
        assert _search(idx, q_dev, 7, mode, monkeypatch) == ref_d


@pytest.mark.parametrize("index_dtype", ["f32", "int8"])
def test_pallas_megakernel_parity(index_dtype, monkeypatch):
    """PATHWAY_SERVING_KERNEL=pallas forces the real megakernel body
    (interpret mode on CPU — tier-1's kernel coverage): online top-k
    merge across corpus blocks must equal the staged chain bit-exactly,
    including the int8 dequant-in-register + rescore-ring handoff."""
    idx = _build(index_dtype, "cos")
    q = _vecs(4, seed=7)
    ref = _search(idx, q, 9, "reference", monkeypatch)
    assert _search(idx, q, 9, "pallas", monkeypatch) == ref
    assert _search(idx, jnp.asarray(q), 9, "pallas", monkeypatch) == \
        _search(idx, jnp.asarray(q), 9, "reference", monkeypatch)


def test_short_rows_tail_parity(monkeypatch):
    """k > live rows: the fused formulations must surface the same
    result rows as the reference's -inf masking.  A 3-row corpus
    right-sizes its capacity below the 32-row tile floor, so the
    megakernel is exercised separately on a tileable corpus whose k
    exceeds its live rows (tombstone + unfilled-lane sentinels both in
    play)."""
    idx = _build("f32", "cos", n=3)
    q = _vecs(2, seed=11)
    ref = _search(idx, q, 8, "reference", monkeypatch)
    assert [len(row) for row in ref] == [3, 3]
    assert _search(idx, q, 8, "auto", monkeypatch) == ref
    assert _search(idx, q, 8, "fused", monkeypatch) == ref
    big = _build("f32", "cos", n=33)  # capacity 64, 33 live rows
    for i in range(30, 33):
        big.remove(f"k{i:03d}")  # tombstoned slots inside the grid
    ref = _search(big, q, 48, "reference", monkeypatch)
    assert [len(row) for row in ref] == [30, 30]
    assert _search(big, q, 48, "pallas", monkeypatch) == ref
    assert _search(big, q, 48, "fused", monkeypatch) == ref


@pytest.mark.parametrize("mesh_n", [1, 2])
@pytest.mark.parametrize("index_dtype", ["f32", "int8"])
def test_sharded_fused_parity(mesh_n, index_dtype, monkeypatch):
    """The fused sharded tick (prep folded into the shard_map dispatch)
    matches both the sharded reference chain and the single-device fused
    path — per-shard launch + ICI merge topology unchanged."""
    shard = _build(index_dtype, "cos", mesh=make_mesh(mesh_n))
    single = _build(index_dtype, "cos", capacity=shard.capacity)
    q = _vecs(5, seed=5)
    ref = _search(shard, q, 7, "reference", monkeypatch)
    assert _search(shard, q, 7, "auto", monkeypatch) == ref
    got = _search(single, q, 7, "auto", monkeypatch)
    if mesh_n == 1 or index_dtype == "int8":
        # one shard is the single-device program; int8 codes dot in
        # integers, which no order of summation changes
        assert got == ref
    else:
        # over two shards the float32 matmul is another XLA program than
        # the single-device one and may sum a row's 16 products in
        # another order: keys and their order stay exact, a cos score
        # may move by 2 * dim * eps (the dot of two unit vectors, and
        # half of that for each of the two norms they were divided by;
        # observed here: 3.0e-8)
        assert [[k for k, _ in row] for row in got] == [
            [k for k, _ in row] for row in ref
        ]
        tol = 2 * q.shape[1] * float(np.finfo(np.float32).eps)
        for row_g, row_r in zip(got, ref):
            for (_, a), (_, b) in zip(row_g, row_r):
                assert abs(a - b) <= tol, (a, b, tol)
    qd = jnp.asarray(q)
    assert _search(shard, qd, 7, "auto", monkeypatch) == \
        _search(shard, qd, 7, "reference", monkeypatch)


def test_tiered_hot_tier_fused_parity(monkeypatch):
    """The tiered index's hot tick rides the fused path; fused and
    reference modes must agree bit-exactly through routing + cold
    rescore + merge."""
    def build(hot_rows, n):
        t = TieredKnnIndex(dim=16, hot_rows=hot_rows, capacity=128, seed=3)
        for i, v in enumerate(_vecs(n, seed=1)):
            t.upsert(f"k{i:03d}", v)
        return t

    q = _vecs(6, seed=9)
    tiered = build(8, 32)
    ref = _search(tiered, q, 7, "reference", monkeypatch)
    assert _search(tiered, q, 7, "auto", monkeypatch) == ref
    # the forced megakernel needs a tileable (>=32-row) hot tier
    big = build(32, 80)
    ref = _search(big, q, 7, "reference", monkeypatch)
    assert _search(big, q, 7, "pallas", monkeypatch) == ref


# ---------------------------------------------------------------------------
# normalize exactly once (satellite bugfix)
# ---------------------------------------------------------------------------


def test_cosine_queries_normalized_exactly_once(monkeypatch):
    """Pre-normalized queries through ``pre_normalized=True`` (the tiered
    hot tick) are bit-identical to raw queries through the normal path —
    i.e. the fused kernel does NOT normalize a second time.  A double
    normalization divides by a norm of 1±ε and would flip low mantissa
    bits across 6x7 f32 scores with near-certainty."""
    idx = _build("f32", "cos")
    q_raw = _vecs(6, seed=13) * 3.7  # decidedly non-unit norms
    norms = np.linalg.norm(q_raw, axis=1, keepdims=True)
    q_unit = q_raw / norms
    for mode in ("auto", "pallas", "reference"):
        monkeypatch.setenv("PATHWAY_SERVING_KERNEL", mode)
        expect = idx.search(q_raw, 7)
        assert idx.search(q_unit, 7, pre_normalized=True) == expect, mode
    # the tiered wrapper (which normalizes host-side before the hot
    # tick) agrees with the flat index over the same rows — ranking
    # identical, scores within storage-normalization rounding (the hot
    # tier re-normalizes resident ROWS on insert; query prep is still
    # exactly once on both routes, which the strict parity above pins)
    tiered = TieredKnnIndex(dim=16, hot_rows=64, capacity=64)
    flat = _build("f32", "cos", n=0)
    for i, v in enumerate(_vecs(20, seed=2)):
        tiered.upsert(f"k{i:03d}", v)
        flat.upsert(f"k{i:03d}", v)
    monkeypatch.setenv("PATHWAY_SERVING_KERNEL", "auto")
    got_t, got_f = tiered.search(q_raw, 5), flat.search(q_raw, 5)
    assert [[k for k, _ in row] for row in got_t] == \
        [[k for k, _ in row] for row in got_f]
    for row_t, row_f in zip(got_t, got_f):
        for (_, a), (_, b) in zip(row_t, row_f):
            assert a == pytest.approx(b, abs=1e-6)


# ---------------------------------------------------------------------------
# exact tie order (the lax.top_k stable contract)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["auto", "pallas", "reference"])
def test_topk_tie_order_lowest_slot_first(mode, monkeypatch):
    """Duplicate rows score exactly equal; every formulation must
    surface them lowest-slot-first (the stable ``lax.top_k`` order the
    megakernel's online merge reproduces across block boundaries)."""
    idx = DeviceKnnIndex(dim=16, metric="cos", capacity=64)
    base = _vecs(8, seed=4)
    rows = np.concatenate([base] * 5)  # slots 0-7, 8-15, ... exact dups
    keys = list(range(len(rows)))
    idx.upsert_batch(keys, rows)
    got = _search(idx, base[:3], 15, mode, monkeypatch)
    for qi, row in enumerate(got):
        # the query's own duplicates tie at score 1.0: keys qi, qi+8, ...
        top = [k for k, _ in row[:5]]
        assert top == [qi + 8 * r for r in range(5)], (mode, qi, top)
        # and every tied group in the tail is ascending-slot too
        scores = [s for _, s in row]
        for a, b in zip(row, row[1:]):
            if a[1] == b[1]:
                assert a[0] < b[0], (mode, row)
        assert scores == sorted(scores, reverse=True)


# ---------------------------------------------------------------------------
# geometry validation names the knob
# ---------------------------------------------------------------------------


def test_geometry_validation_raises_naming_knob(monkeypatch):
    with pytest.raises(ValueError, match="PATHWAY_SERVING_KERNEL"):
        fs.validate_serving_geometry(48, "cos")  # no pow2 block >= 32
    with pytest.raises(ValueError, match="PATHWAY_SERVING_KERNEL"):
        fs.validate_serving_geometry(64, "l2sq")  # no megakernel body
    # and through the serving surface: a forced pallas kernel on an
    # l2sq index refuses loudly instead of silently falling back
    idx = _build("f32", "l2sq")
    monkeypatch.setenv("PATHWAY_SERVING_KERNEL", "pallas")
    with pytest.raises(ValueError, match="PATHWAY_SERVING_KERNEL"):
        idx.search(_vecs(2, seed=1), 3)
    # auto mode on the same geometry uses the fused XLA lowering and
    # matches the staged reference
    auto = _search(idx, _vecs(2, seed=1), 3, "auto", monkeypatch)
    assert auto == _search(idx, _vecs(2, seed=1), 3, "reference", monkeypatch)


def test_bad_knob_values_warn_and_default(monkeypatch):
    monkeypatch.setenv("PATHWAY_SERVING_KERNEL", "warp-drive")
    with pytest.warns(UserWarning, match="PATHWAY_SERVING_KERNEL"):
        assert fs.serving_kernel_mode() == "auto"
    monkeypatch.setenv("PATHWAY_SERVING_WIRE_DTYPE", "fp4")
    with pytest.warns(UserWarning, match="PATHWAY_SERVING_WIRE_DTYPE"):
        assert fs.serving_wire_dtype() == "bf16"
    monkeypatch.delenv("PATHWAY_SERVING_KERNEL")
    monkeypatch.delenv("PATHWAY_SERVING_WIRE_DTYPE")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert fs.serving_kernel_mode() == "auto"
        assert fs.serving_wire_dtype() == "bf16"  # the serving default


# ---------------------------------------------------------------------------
# launch accounting: the <=2 pin, the span, the metrics family
# ---------------------------------------------------------------------------


def test_fused_tick_at_most_two_launches_reference_at_least_four(monkeypatch):
    """THE acceptance pin: a fused serving tick costs ≤ 2 device
    launches (1 dense) while the staged quantized reference pays ≥ 4
    (prep / score / top-c / rescore) — provable without a chip."""
    dense = _build("f32", "cos")
    quant = _build("int8", "cos")
    q = jnp.asarray(_vecs(4, seed=6))  # device queries: prep is a launch

    def launches(idx, mode):
        monkeypatch.setenv("PATHWAY_SERVING_KERNEL", mode)
        with fs.serving_tick() as tick:
            idx.search(q, 5)
        return tick.counts

    fused_dense = launches(dense, "fused")
    assert sum(fused_dense.values()) == 1, fused_dense
    fused_quant = launches(quant, "fused")
    assert sum(fused_quant.values()) <= 2, fused_quant
    pallas_dense = launches(dense, "pallas")
    assert sum(pallas_dense.values()) == 1, pallas_dense
    pallas_quant = launches(quant, "pallas")
    assert sum(pallas_quant.values()) <= 2, pallas_quant
    ref_dense = launches(dense, "reference")
    assert sum(ref_dense.values()) >= 3, ref_dense
    ref_quant = launches(quant, "reference")
    assert sum(ref_quant.values()) >= 4, ref_quant
    assert set(ref_quant) == {"prep", "score", "topk", "rescore"}


def test_serving_tick_span_carries_launch_counts(monkeypatch):
    from pathway_tpu.internals import flight_recorder as fr

    fr.reset_recorder()
    idx = _build("f32", "cos")
    monkeypatch.setenv("PATHWAY_SERVING_KERNEL", "fused")
    idx.search(_vecs(3, seed=8), 5)
    spans = [
        s for s in fr.get_recorder().spans(category="serve")
        if s.name == "serving.tick"
    ]
    assert spans, "no serving.tick span recorded"
    attrs = spans[-1].attrs
    assert attrs["launches"] == attrs["launches.fused"] == 1
    # the kill switch silences both the counters and the span
    fr.reset_recorder()
    fs.reset_launch_metrics()
    monkeypatch.setenv("PATHWAY_LAUNCH_ACCOUNTING", "0")
    idx.search(_vecs(3, seed=8), 5)
    assert fs.launch_totals() == {}
    assert not [
        s for s in fr.get_recorder().spans(category="serve")
        if s.name == "serving.tick"
    ]


def test_launch_metrics_family_declared_and_emitted():
    """Both directions: the family is in the metrics-names registry AND
    the provider emits it with the stage label once a launch lands."""
    from pathway_tpu.internals.metrics_names import METRICS

    kind, _help = METRICS["pathway_serving_launches_total"]
    assert kind == "counter"
    fs.record_launch("fused")
    fs.record_launch("rescore")
    lines = fs._ServingLaunchMetricsProvider().openmetrics_lines()
    assert "# TYPE pathway_serving_launches_total counter" in lines
    joined = "\n".join(lines)
    assert 'pathway_serving_launches_total{stage="fused"} 1' in joined
    assert 'pathway_serving_launches_total{stage="rescore"} 1' in joined
    assert fs.launch_totals() == {"fused": 1, "rescore": 1}


def test_wire_cast_counts_as_wire_stage(monkeypatch):
    """The bf16 embed→search handoff cast is visible as stage="wire"."""
    from pathway_tpu.xpacks.llm._scheduler import _batch_embed_device

    class _Enc:
        def encode_padded(self, texts):
            return jnp.zeros((8, 8), dtype=jnp.float32), len(texts)

    class _Emb:
        def _ensure_encoder(self):
            return _Enc()

    monkeypatch.delenv("PATHWAY_SERVING_WIRE_DTYPE", raising=False)
    out = _batch_embed_device(_Emb(), ["a", "b"])
    assert out is not None and out.dtype == jnp.bfloat16
    assert fs.launch_totals().get("wire", 0) == 1
    # f32 opt-out: no cast, no wire launch
    monkeypatch.setenv("PATHWAY_SERVING_WIRE_DTYPE", "f32")
    out32 = _batch_embed_device(_Emb(), ["a", "b"])
    assert out32 is not None and out32.dtype == jnp.float32
    assert fs.launch_totals().get("wire", 0) == 1


# ---------------------------------------------------------------------------
# cache hit/miss bit-exactness through RetrievePlane (bf16 wire default)
# ---------------------------------------------------------------------------


def test_cache_hit_miss_bit_exact_through_retrieve_plane(monkeypatch):
    """Under the bf16-on-the-wire default AND the fused kernel, a result
    cache hit replays the miss that filled it bit-exactly, and the fused
    plane's results equal the reference plane's — the PR 13 cache
    semantics survive the serving-path rewrite unchanged."""
    from pathway_tpu.models.encoder import EncoderConfig, SentenceEncoder
    from pathway_tpu.stdlib.indexing.lowering import (
        ExternalIndexNode,
        _LIVE_INDEX_NODES,
    )
    from pathway_tpu.stdlib.indexing.retrievers import BruteForceKnnIndex
    from pathway_tpu.xpacks.llm import _query_cache as qc
    from pathway_tpu.xpacks.llm._scheduler import (
        RetrievePlane,
        ServingScheduler,
    )

    qc.reset_query_cache_counters()
    cfg = EncoderConfig(
        vocab_size=512, hidden_dim=32, num_layers=1, num_heads=4,
        mlp_dim=64, max_len=64, dtype=jnp.float32,
    )
    encoder = SentenceEncoder(cfg=cfg, max_length=64)
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    embedder = SentenceTransformerEmbedder(encoder=encoder)
    docs = [f"doc number {i} about topic {i}" for i in range(10)]
    index = BruteForceKnnIndex(dim=encoder.dim, metric="cos", capacity=64)
    index.add_batch(
        list(range(len(docs))), encoder.encode(docs), [{} for _ in docs]
    )
    node = ExternalIndexNode(
        index, None, None, None, None, None, None, name="fused-qc",
    )
    node.doc_payload = {i: (docs[i], {}) for i in range(len(docs))}
    node.bump_commit_seq()
    factory = object()
    _LIVE_INDEX_NODES[id(factory)] = node
    scheduler = ServingScheduler(name="sched-fused-qc")
    plane = RetrievePlane(
        index_factory=factory,
        embedder=embedder,
        payload_columns=["text", "metadata"],
        scheduler=scheduler,
    )

    def dists(rows):
        return [
            [(r["text"], r["dist"]) for r in row["results"]] for row in rows
        ]

    queries = [docs[0], docs[3]]
    monkeypatch.setenv("PATHWAY_SERVING_KERNEL", "fused")
    miss = plane._batch([(q, 3, None) for q in queries])
    s0 = qc.query_cache_stats()["result"]
    assert s0["misses"] >= 2 and s0["hits"] == 0
    hit = plane._batch([(q, 3, None) for q in queries])
    s1 = qc.query_cache_stats()["result"]
    assert s1["hits"] >= 2
    assert dists(hit) == dists(miss)  # bit-exact replay, float equality
    # the staged reference computes the same results the fused tick
    # cached — a mode flip mid-flight cannot poison or split the cache
    monkeypatch.setenv("PATHWAY_SERVING_KERNEL", "reference")
    node2 = ExternalIndexNode(
        index, None, None, None, None, None, None, name="fused-qc-ref",
    )
    node2.doc_payload = dict(node.doc_payload)
    node2.bump_commit_seq()
    factory2 = object()
    _LIVE_INDEX_NODES[id(factory2)] = node2
    ref_plane = RetrievePlane(
        index_factory=factory2,
        embedder=embedder,
        payload_columns=["text", "metadata"],
        scheduler=scheduler,
    )
    ref = ref_plane._batch([(q, 3, None) for q in queries])
    assert dists(ref) == dists(miss)


# ---------------------------------------------------------------------------
# kernel-registry lint (the fault-site registry idiom)
# ---------------------------------------------------------------------------


def _readme_knob_literals(knob: str) -> set[str]:
    readme = (
        pathlib.Path(__file__).resolve().parent.parent / "README.md"
    ).read_text()
    rows = [
        line for line in readme.splitlines()
        if line.startswith(f"| `{knob}`")
    ]
    assert rows, f"README knob table has no row for {knob}"
    # backticked lowercase literals in the default + meaning cells
    # (skip the knob-name cell itself)
    cells = rows[0].split("|")
    return set(re.findall(r"`([a-z0-9]+)`", "|".join(cells[2:])))


def test_kernel_registry_lint_readme_both_directions():
    """Every PATHWAY_SERVING_KERNEL literal the parser accepts appears
    in README's knob table, and the table names no mode the parser would
    reject — a renamed or added mode fails here instead of shipping
    undocumented (or documented-but-dead)."""
    documented = _readme_knob_literals("PATHWAY_SERVING_KERNEL")
    accepted = set(fs.SERVING_KERNEL_MODES)
    assert accepted - documented == set(), (
        f"parser modes missing from README knob table: "
        f"{accepted - documented}"
    )
    assert documented - accepted == set(), (
        f"README documents modes the parser rejects: "
        f"{documented - accepted}"
    )


def test_wire_dtype_registry_lint_readme_both_directions():
    documented = _readme_knob_literals("PATHWAY_SERVING_WIRE_DTYPE")
    accepted = set(fs.SERVING_WIRE_DTYPES)
    assert accepted <= documented, accepted - documented
    assert documented <= accepted, documented - accepted
