"""Multi-chip serving core (ISSUE 8): sharded-vs-single-device parity,
device-batch staging with mesh placement, apply-time scatter coalescing,
and runtime-submitted sharded ticks under INTERACTIVE+BULK contention.

Everything runs on the virtual 8-device CPU mesh (tests/conftest.py
forces ``--xla_force_host_platform_device_count=8``).  Parity: keys,
their order and the counts are pinned EXACT (local top-k ties resolve in
slot order and the ICI merge concatenates shards in global-slot order).
Scores are pinned to the last bit where both sides run the same program
(a 1-device mesh); over more shards the per-shard matmul is another XLA
program than the single-device one and may sum a row's D products in
another order, so scores compare within the float32 bound of that
(:func:`_score_tol`).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pathway_tpu.ops.knn import DeviceKnnIndex
from pathway_tpu.parallel import make_mesh
from pathway_tpu.parallel.index import ShardedKnnIndex, mesh_status


def _pair(mesh_n: int, dim: int = 16, capacity: int = 64, metric: str = "cos"):
    """(single-device, sharded-over-mesh_n) indexes with EQUAL capacity so
    slot assignment — and therefore tie order — is identical."""
    shard = ShardedKnnIndex(
        dim=dim, mesh=make_mesh(mesh_n), metric=metric, capacity=capacity
    )
    single = DeviceKnnIndex(dim=dim, metric=metric, capacity=shard.capacity)
    assert single.capacity == shard.capacity
    return single, shard


def _vecs(n: int, dim: int = 16, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((n, dim)).astype(
        np.float32
    )


def _score_tol(metric: str, q: np.ndarray, *rows: np.ndarray) -> float:
    """How far two float32 programs may lie apart on one score when each
    sums the same D products in its own order.  A float32 sum of D terms
    is within (D-1)*u*sum|terms| of the exact one in ANY order (u =
    eps/2), so two orders are within D*eps*sum|terms| of each other.  A
    score is built from three such sums and their first-order errors
    add.  cos: the dot of two unit vectors (sum|terms| <= 1) and the two
    norms the row and the query were divided by (1/2 each: a relative
    error passes a square root halved), 2 in all.  l2sq, ``2 q.v - |q|^2
    - |v|^2``: 2|q||v| + |q|^2 + |v|^2 = (|q| + |v|)^2.  With D = 16
    that is 3.8e-6 for cos (observed: 1.2e-7) and 2.2e-4 for l2sq
    scores of magnitude 36 (observed: 1.9e-6); a wrong row is off by
    1e-2 and more, and the keys are compared exactly besides."""
    dim = q.shape[-1]
    mass = 2.0
    if metric == "l2sq":
        longest = max(
            float(np.linalg.norm(np.asarray(r), axis=-1).max()) for r in rows
        )
        mass = (float(np.linalg.norm(q, axis=-1).max()) + longest) ** 2
    return dim * float(np.finfo(np.float32).eps) * mass


def _assert_same_hits(got, want, tol: float) -> None:
    """Same keys in the same order for every query (exact); scores equal
    to the last bit when ``tol`` is 0, else within ``tol``."""
    assert [[k for k, _ in row] for row in got] == [
        [k for k, _ in row] for row in want
    ]
    if tol == 0:
        assert got == want
        return
    for row_g, row_w in zip(got, want):
        for (_, a), (_, b) in zip(row_g, row_w):
            assert abs(a - b) <= tol, (a, b, tol)


@pytest.mark.parametrize("mesh_n", [1, 2, 8])
@pytest.mark.parametrize("metric", ["cos", "l2sq"])
def test_sharded_parity_search_upsert_delete(mesh_n, metric):
    single, shard = _pair(mesh_n, metric=metric)
    vecs = _vecs(40)
    keys = [f"k{i}" for i in range(40)]
    # host-batch upserts
    for idx in (single, shard):
        idx.upsert_batch(keys[:20], vecs[:20])
    # DEVICE-batch upserts (the lifted _device_stage_ok path)
    dev = jnp.asarray(vecs[20:])
    for idx in (single, shard):
        idx.upsert_batch(keys[20:], dev)
    q = _vecs(5, seed=3)
    v2 = _vecs(2, seed=9)
    # one shard: the same program as the single-device index, bit-exact
    tol = 0 if mesh_n == 1 else _score_tol(metric, q, vecs, v2)
    _assert_same_hits(shard.search(q, 7), single.search(q, 7), tol)
    # overwrite a host-staged key from a device batch and vice versa
    for idx in (single, shard):
        idx.upsert_batch(keys[:1], jnp.asarray(v2[:1]))
        idx.upsert(keys[25], v2[1])
    _assert_same_hits(shard.search(q, 7), single.search(q, 7), tol)
    # deletes
    for idx in (single, shard):
        for k in keys[5:15]:
            idx.remove(k)
    _assert_same_hits(shard.search(q, 7), single.search(q, 7), tol)


def test_degenerate_single_device_mesh_bit_identical():
    """A 1-device mesh is the degenerate case: the shard_map path must be
    bit-identical to the plain DeviceKnnIndex — same keys, same scores."""
    single, shard = _pair(1)
    vecs = _vecs(30)
    keys = list(range(30))
    single.upsert_batch(keys, jnp.asarray(vecs))
    shard.upsert_batch(keys, jnp.asarray(vecs))
    q = _vecs(8, seed=5)
    assert single.search(q, 10) == shard.search(q, 10)
    # device-array (fused-tick) queries too
    assert single.search(jnp.asarray(q), 10) == shard.search(
        jnp.asarray(q), 10
    )


def test_device_staged_upsert_pins_mesh_placement():
    """Device-batch staging must scatter into the owning shard: after the
    apply, the matrix still carries the mesh sharding (the PR 5
    restriction existed precisely because the old scatter dropped it)."""
    shard = ShardedKnnIndex(dim=16, mesh=make_mesh(8), capacity=64)
    shard.upsert_batch([f"k{i}" for i in range(24)], jnp.asarray(_vecs(24)))
    assert shard._staged_device  # staged, not applied yet
    shard.search(_vecs(1, seed=1), 3)  # apply happens here
    assert not shard._staged_device
    assert shard.vectors.sharding == shard._vec_sharding
    assert shard.valid.sharding == shard._mask_sharding
    rows = shard.shard_row_counts()
    assert sum(rows) == 24 and len(rows) == 8


def test_corpus_larger_than_one_shard_capacity_grows_and_serves():
    """A corpus bigger than one shard's slice of the configured capacity
    (and bigger than the whole configured capacity) must grow the sharded
    matrix, keep placement, and stay in parity with single-device."""
    single, shard = _pair(8, capacity=16)  # rounds to 64 => 8 rows/shard
    n = 200  # > capacity: forces growth through multiple doublings
    vecs = _vecs(n)
    keys = [f"d{i}" for i in range(n)]
    single.upsert_batch(keys, jnp.asarray(vecs))
    shard.upsert_batch(keys, jnp.asarray(vecs))
    q = _vecs(4, seed=11)
    _assert_same_hits(
        shard.search(q, 12), single.search(q, 12), _score_tol("cos", q)
    )
    assert shard.capacity == single.capacity >= n
    assert shard.capacity % shard.n_shards == 0
    assert shard.vectors.sharding == shard._vec_sharding
    assert sum(shard.shard_row_counts()) == n


@pytest.mark.parametrize("mesh_n", [1, 2, 8])
def test_sharded_rebuild_salvages_staged_device_rows(mesh_n):
    """PR 6's fatal-device-fault rebuild over a sharded index with
    device-STAGED rows pending: staged batches salvage to host, the
    rebuilt arrays re-pin to the mesh, and results match single-device."""
    single, shard = _pair(mesh_n)
    vecs = _vecs(24)
    keys = [f"k{i}" for i in range(24)]
    for idx in (single, shard):
        idx.upsert_batch(keys[:12], vecs[:12])          # applied below
        idx.search(_vecs(1, seed=2), 1)                  # force apply
        idx.upsert_batch(keys[12:], jnp.asarray(vecs[12:]))  # staged
        assert idx.rebuild_device_arrays() is True
    assert shard.vectors.sharding == shard._vec_sharding
    q = _vecs(3, seed=7)
    r_single, r_shard = single.search(q, 6), shard.search(q, 6)
    assert [[k for k, _ in row] for row in r_single] == [
        [k for k, _ in row] for row in r_shard
    ]
    for row_s, row_m in zip(r_single, r_shard):
        for (_, a), (_, b) in zip(row_s, row_m):
            assert a == pytest.approx(b, abs=1e-6)


def test_snapshot_provider_rebuild_repins_sharded_layout():
    """Arrays-gone rebuild from snapshot vectors (PR 6's second recovery
    source) reassigns slots and must land back on the mesh."""
    shard = ShardedKnnIndex(dim=8, mesh=make_mesh(8), capacity=64)
    vecs = {f"k{i}": v for i, v in enumerate(_vecs(16, dim=8))}
    shard.upsert_batch(list(vecs), np.stack(list(vecs.values())))
    shard.search(_vecs(1, dim=8, seed=1), 1)

    class _Dead:
        def __array__(self, *a, **k):
            raise RuntimeError("transfer from device failed")

    shard.vectors = _Dead()
    shard.valid = _Dead()
    assert shard.rebuild_device_arrays(vecs) is True
    assert shard.vectors.sharding == shard._vec_sharding
    out = shard.search(vecs["k5"], 2)
    assert out[0][0][0] == "k5"


# ---------------------------------------------------------------------------
# apply-time scatter coalescing (PR 7 follow-up satellite)
# ---------------------------------------------------------------------------


def test_apply_staged_coalescing_parity_and_scatter_count(monkeypatch):
    monkeypatch.setenv("PATHWAY_UPSERT_SLICE_ROWS", "8")
    vecs = _vecs(200, dim=8)
    keys = [f"k{i}" for i in range(200)]
    over = _vecs(16, dim=8, seed=4)
    q = _vecs(3, dim=8, seed=6)

    def build():
        idx = DeviceKnnIndex(dim=8, capacity=256)
        idx.upsert_batch(keys, jnp.asarray(vecs))
        # second device batch re-writes keys staged by the first — the
        # coalesced scatter must keep only the LAST row per slot
        idx.upsert_batch(keys[:16], jnp.asarray(over))
        return idx

    monkeypatch.setenv("PATHWAY_UPSERT_COALESCE_ROWS", "0")
    plain = build()
    staged = len(plain._staged_device)
    assert staged >= 25  # the slicing produced a long backlog
    r_plain = plain.search(q, 5)
    assert plain.scatter_dispatches == staged

    monkeypatch.setenv("PATHWAY_UPSERT_COALESCE_ROWS", "64")
    coal = build()
    r_coal = coal.search(q, 5)
    assert r_coal == r_plain
    # 216 staged rows at ≤64 rows per scatter: ≤ ceil + slack, far
    # below one-per-chunk
    assert coal.scatter_dispatches <= 6


def test_coalescing_keeps_mesh_placement(monkeypatch):
    monkeypatch.setenv("PATHWAY_UPSERT_SLICE_ROWS", "8")
    monkeypatch.setenv("PATHWAY_UPSERT_COALESCE_ROWS", "64")
    shard = ShardedKnnIndex(dim=8, mesh=make_mesh(8), capacity=128)
    single = DeviceKnnIndex(dim=8, capacity=shard.capacity)
    vecs = _vecs(100, dim=8)
    keys = [f"k{i}" for i in range(100)]
    for idx in (single, shard):
        idx.upsert_batch(keys, jnp.asarray(vecs))
    q = _vecs(2, dim=8, seed=8)
    _assert_same_hits(
        shard.search(q, 5), single.search(q, 5), _score_tol("cos", q)
    )
    assert shard.vectors.sharding == shard._vec_sharding
    assert shard.scatter_dispatches <= 3


# ---------------------------------------------------------------------------
# runtime-submitted sharded ticks (INTERACTIVE search + BULK_INGEST
# device-staged embed→upsert on the SAME sharded index)
# ---------------------------------------------------------------------------


def _small_encoder(mesh=None):
    from pathway_tpu.models.encoder import EncoderConfig, SentenceEncoder

    cfg = EncoderConfig(
        vocab_size=512, hidden_dim=32, num_layers=2, num_heads=4,
        mlp_dim=64, max_len=64, dtype=jnp.float32,
    )
    return SentenceEncoder(cfg=cfg, seed=3, max_length=64, mesh=mesh)


def test_runtime_sharded_ticks_under_interactive_and_bulk_contention():
    """Sharded ticks ride the unified runtime as ordinary WorkItems:
    BULK_INGEST chunks embed→upsert (device-staged) into the sharded
    index while INTERACTIVE searches preempt between chunks — no fourth
    loop, executor stays alive, and the final state matches a
    single-device oracle fed the same encoder outputs."""
    from pathway_tpu import runtime as rt_mod
    from pathway_tpu.runtime import QoS, WorkGroup, get_runtime
    from pathway_tpu.stdlib.indexing.retrievers import BruteForceKnnIndex
    from pathway_tpu.xpacks.llm._ingest import IngestPipeline

    mesh = make_mesh(8)
    enc = _small_encoder(mesh)
    sharded = BruteForceKnnIndex(dim=enc.dim, capacity=256, mesh=mesh)
    texts = [f"doc number {i} about subject {i % 5}" for i in range(96)]
    keys = [f"doc{i}" for i in range(96)]

    rt = get_runtime()
    search_group = WorkGroup(
        "sharded-search",
        lambda payloads: [
            sharded.index.search(jnp.asarray(p), 5) for p in payloads
        ],
        max_batch=4,
    )

    results: list = []
    with IngestPipeline(enc, sharded) as pipe:
        futs = [
            pipe.submit(texts[i : i + 16], keys=keys[i : i + 16])
            for i in range(0, 96, 16)
        ]
        # interactive searches racing the bulk backlog
        probe = enc.encode(texts[:2])
        sfuts = [
            rt.submit(search_group, probe, qos=QoS.INTERACTIVE)
            for _ in range(6)
        ]
        assert all(f.result(timeout=120) == 16 for f in futs)
        results = [f.result(timeout=120) for f in sfuts]
    assert all(isinstance(r, list) for r in results)

    # the BULK path staged DEVICE batches and the placement survived
    sharded.index.search(probe, 1)  # final apply
    assert sharded.index.vectors.sharding == sharded.index._vec_sharding
    assert len(sharded.index) == 96
    assert sharded.index.sharded_ticks > 0
    assert rt._thread is not None and rt._thread.is_alive()
    stats = rt_mod.get_runtime().stats()
    assert stats["classes"]["bulk_ingest"]["completed_total"] > 0
    assert stats["classes"]["interactive"]["completed_total"] > 0

    # oracle: same encoder outputs into a single-device index
    oracle = DeviceKnnIndex(dim=enc.dim, capacity=sharded.index.capacity)
    with IngestPipeline(enc, oracle) as pipe:
        pipe.submit(texts, keys=keys).result(timeout=120)
    q = enc.encode(["subject 3 documents"])
    r_shard = sharded.index.search(q, 8)
    r_oracle = oracle.search(q, 8)
    assert [[k for k, _ in row] for row in r_shard] == [
        [k for k, _ in row] for row in r_oracle
    ]


def test_fused_embed_handoff_stays_on_device(monkeypatch):
    """The serving tick's embed half must hand the search a DEVICE array
    (no D2H/H2D round trip), and that array must search identically to
    the host-path embeddings.  Under the bf16-on-the-wire serving
    default the handoff is bf16 (ranking preserved, scores within
    bf16 input rounding); the f32 opt-out restores exact equality."""
    from pathway_tpu.xpacks.llm._scheduler import (
        _batch_embed,
        _batch_embed_device,
    )
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    enc = _small_encoder()
    embedder = SentenceTransformerEmbedder(encoder=enc)
    texts = [f"query about item {i}" for i in range(3)]
    dev = _batch_embed_device(embedder, texts)
    assert isinstance(dev, jax.Array) and not isinstance(dev, np.ndarray)
    assert dev.dtype == jnp.bfloat16  # bf16-on-the-wire serving default
    assert dev.shape[0] >= len(texts)  # dispatch pads ride along
    host = _batch_embed(embedder, texts)

    idx = ShardedKnnIndex(dim=enc.dim, mesh=make_mesh(8), capacity=64)
    idx.upsert_batch(
        [f"d{i}" for i in range(10)], _vecs(10, dim=enc.dim, seed=2)
    )
    r_dev = idx.search(dev, 4)[: len(texts)]
    r_host = idx.search(host, 4)
    assert [[k for k, _ in row] for row in r_dev] == [
        [k for k, _ in row] for row in r_host
    ]
    for row_d, row_h in zip(r_dev, r_host):
        for (_, a), (_, b) in zip(row_d, row_h):
            assert a == pytest.approx(b, abs=2e-2)

    # PATHWAY_SERVING_WIRE_DTYPE=f32 opt-out: the handoff is exact again
    monkeypatch.setenv("PATHWAY_SERVING_WIRE_DTYPE", "f32")
    dev32 = _batch_embed_device(embedder, texts)
    assert dev32.dtype == jnp.float32
    r_dev32 = idx.search(dev32, 4)[: len(texts)]
    for row_d, row_h in zip(r_dev32, r_host):
        for (_, a), (_, b) in zip(row_d, row_h):
            assert a == pytest.approx(b, abs=1e-6)

    # a UDF embedder (no model-backed encoder) opts out — host fallback
    from pathway_tpu.xpacks.llm import mocks

    assert _batch_embed_device(mocks.FakeEmbedder(dim=8), texts) is None


# ---------------------------------------------------------------------------
# observability: pathway_mesh_* series + health mesh block
# ---------------------------------------------------------------------------


def test_mesh_metrics_and_health_surfacing():
    from pathway_tpu.internals.health import get_health
    from pathway_tpu.internals.monitoring import StatsMonitor

    shard = ShardedKnnIndex(dim=8, mesh=make_mesh(8), capacity=64)
    shard.upsert_batch([f"k{i}" for i in range(10)], _vecs(10, dim=8))
    shard.search(_vecs(1, dim=8, seed=1), 3)

    body = StatsMonitor().openmetrics()
    assert "# TYPE pathway_mesh_devices gauge" in body
    assert "# TYPE pathway_mesh_shard_rows gauge" in body
    assert "# TYPE pathway_mesh_sharded_ticks_total counter" in body
    lbl = f'index="{shard.mesh_label}"'
    assert f"pathway_mesh_devices{{{lbl}}} 8" in body

    status = mesh_status()
    assert status is not None and shard.mesh_label in status
    rec = status[shard.mesh_label]
    assert rec["devices"] == 8 and sum(rec["rows_per_shard"]) == 10
    assert rec["sharded_ticks"] >= 1

    snap = get_health().snapshot()
    assert shard.mesh_label in snap.get("mesh", {})
