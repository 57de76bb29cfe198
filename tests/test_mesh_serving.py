"""Mesh-sharded serving through the product API (VERDICT r4 #2).

The reference serves its index as a full replica per timely worker
(src/engine/dataflow/operators/external_index.rs:95-98); the TPU design
row-shards the HBM matrix over a device mesh instead (parallel/index.py).
These tests reach that plane only through user-facing constructors:
``VectorStoreServer(..., mesh=)``, ``DocumentStore(..., mesh=)``,
``BruteForceKnnFactory(mesh=)``, ``SentenceEncoder(mesh=)`` — on the
virtual 8-device CPU mesh, asserting exact parity with the single-device
path, including under streaming upserts and deletes.
"""

import socket
import time

import numpy as np
import pytest

import pathway_tpu as pw
import pathway_tpu.debug as dbg
from pathway_tpu.internals.graph import G
from pathway_tpu.parallel import make_mesh
from pathway_tpu.parallel.index import ShardedKnnIndex
from pathway_tpu.stdlib.indexing.retrievers import (
    BruteForceKnnFactory,
    BruteForceKnnIndex,
    TantivyBM25Factory,
    UsearchKnnFactory,
)
from pathway_tpu.stdlib.indexing.hybrid_index import HybridIndexFactory
from pathway_tpu.xpacks.llm import mocks
from pathway_tpu.xpacks.llm.document_store import DocumentStore
from pathway_tpu.xpacks.llm.vector_store import (
    RetrieveQuerySchema,
    VectorStoreClient,
    VectorStoreServer,
)


@pytest.fixture
def mesh():
    return make_mesh(8)


CORPUS = {
    "doc1.txt": "Berlin is the capital of Germany.",
    "doc2.txt": "Paris is the capital of France.",
    "doc3.txt": "The quick brown fox jumps over the lazy dog.",
    "doc4.txt": "Madrid is the capital of Spain.",
    "doc5.txt": "Rome is the capital of Italy.",
}
QUERIES = [
    "Paris is the capital of France.",
    "Which city is the capital of Spain?",
    "fox jumping over dogs",
]


@pytest.fixture
def corpus_dir(tmp_path):
    for name, text in CORPUS.items():
        (tmp_path / name).write_text(text)
    return tmp_path


def test_factory_builds_sharded_index(mesh):
    inner = BruteForceKnnFactory(dimensions=16, mesh=mesh).build_inner_index()
    assert isinstance(inner, BruteForceKnnIndex)
    assert isinstance(inner.index, ShardedKnnIndex)
    # and without a mesh it stays single-device
    plain = BruteForceKnnFactory(dimensions=16).build_inner_index()
    assert not isinstance(plain.index, ShardedKnnIndex)


def test_vector_store_mesh_knob_reaches_factory(mesh, corpus_dir):
    docs = pw.io.fs.read(
        corpus_dir, format="binary", mode="static", with_metadata=True
    )
    vs = VectorStoreServer(docs, embedder=mocks.FakeEmbedder(dim=16), mesh=mesh)
    assert vs.index_factory.mesh is mesh
    # an explicitly-passed factory with an unset mesh field inherits it —
    # via a copy: the caller's object stays reusable without a mesh
    G.clear()
    docs = pw.io.fs.read(
        corpus_dir, format="binary", mode="static", with_metadata=True
    )
    factory = UsearchKnnFactory(embedder=mocks.FakeEmbedder(dim=16))
    vs = VectorStoreServer(docs, index_factory=factory, mesh=mesh)
    assert vs.index_factory.mesh is mesh
    assert factory.mesh is None


def _batch_retrieve(corpus_dir, mesh, k=3):
    docs = pw.io.fs.read(
        corpus_dir, format="binary", mode="static", with_metadata=True
    )
    vs = VectorStoreServer(docs, embedder=mocks.FakeEmbedder(dim=16), mesh=mesh)
    queries = dbg.table_from_rows(
        RetrieveQuerySchema, [(q, k, None, None) for q in QUERIES]
    )
    _, cols = dbg.table_to_dicts(vs.retrieve_query(queries))
    out = []
    for res in cols["result"].values():
        out.append([(r["text"], round(r["dist"], 5)) for r in res.value])
    return sorted(out)


def test_batch_retrieve_sharded_matches_single_device(corpus_dir, mesh):
    single = _batch_retrieve(corpus_dir, None)
    G.clear()
    sharded = _batch_retrieve(corpus_dir, mesh)
    assert single == sharded


def test_document_store_mesh_propagates_to_hybrid(mesh, corpus_dir):
    docs = pw.io.fs.read(
        corpus_dir, format="binary", mode="static", with_metadata=True
    )
    knn = BruteForceKnnFactory(dimensions=16, embedder=mocks.FakeEmbedder(dim=16))
    bm25 = TantivyBM25Factory()
    hybrid = HybridIndexFactory([knn, bm25])
    store = DocumentStore(docs, hybrid, mesh=mesh)
    subs = store.retriever_factory.retriever_factories
    assert subs[0].mesh is mesh  # KNN sub-factory sharded
    assert getattr(subs[1], "mesh", None) is None  # BM25 untouched
    # caller-owned objects not mutated
    assert knn.mesh is None and hybrid.retriever_factories[0] is knn
    assert store.mesh is mesh


def test_sentence_encoder_mesh_parity(mesh):
    import jax.numpy as jnp

    from pathway_tpu.models.encoder import EncoderConfig, SentenceEncoder

    cfg = EncoderConfig(
        vocab_size=512, hidden_dim=32, num_layers=2, num_heads=4, mlp_dim=64,
        max_len=64, dtype=jnp.float32,
    )
    texts = [f"sample text number {i} about topic {i % 3}" for i in range(12)]
    base = SentenceEncoder(cfg=cfg, seed=3, max_length=64).encode(texts)
    dp = SentenceEncoder(cfg=cfg, seed=3, max_length=64, mesh=mesh).encode(texts)
    np.testing.assert_allclose(base, dp, atol=2e-5)
    # tensor parallelism: heads/MLP split over the model axis
    tp_mesh = make_mesh(8, model_parallel=4)
    tp = SentenceEncoder(cfg=cfg, seed=3, max_length=64, mesh=tp_mesh).encode(texts)
    np.testing.assert_allclose(base, tp, atol=2e-5)


# -- streaming upserts/deletes over HTTP, sharded index end-to-end --------


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_http(fn, timeout=30.0):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            return fn()
        except Exception as exc:  # noqa: BLE001 - retry until deadline
            last = exc
            time.sleep(0.25)
    raise AssertionError(f"timed out: {last}")


def test_streaming_upsert_delete_sharded(corpus_dir, mesh):
    docs = pw.io.fs.read(
        corpus_dir, format="binary", mode="streaming", with_metadata=True,
        refresh_interval=0.2,
    )
    vs = VectorStoreServer(docs, embedder=mocks.FakeEmbedder(dim=16), mesh=mesh)
    port = _free_port()
    vs.run_server(host="127.0.0.1", port=port, threaded=True, with_cache=False)
    client = VectorStoreClient(host="127.0.0.1", port=port)

    res = _wait_http(lambda: client.query("Paris is the capital of France.", k=1))
    assert res[0]["text"] == "Paris is the capital of France."

    # upsert: new file becomes retrievable
    (corpus_dir / "doc6.txt").write_text("Lisbon is the capital of Portugal.")

    def upserted():
        r = client.query("Lisbon is the capital of Portugal.", k=1)
        assert r[0]["text"] == "Lisbon is the capital of Portugal."
        return r

    _wait_http(upserted)

    # delete: removed file drops out of the sharded index
    (corpus_dir / "doc6.txt").unlink()

    def deleted():
        r = client.query("Lisbon is the capital of Portugal.", k=5)
        assert all(x["text"] != "Lisbon is the capital of Portugal." for x in r)
        return r

    _wait_http(deleted)

    # in-place change: re-written content replaces the old row
    (corpus_dir / "doc5.txt").write_text("Oslo is the capital of Norway!!")

    def replaced():
        r = client.query("Oslo is the capital of Norway!!", k=1)
        assert r[0]["text"] == "Oslo is the capital of Norway!!"
        return r

    _wait_http(replaced)


def test_cross_encoder_mesh_parity(mesh):
    import jax.numpy as jnp

    from pathway_tpu.models.cross_encoder import CrossEncoder
    from pathway_tpu.models.encoder import EncoderConfig

    cfg = EncoderConfig(
        vocab_size=512, hidden_dim=32, num_layers=2, num_heads=4, mlp_dim=64,
        max_len=64, dtype=jnp.float32,
    )
    pairs = [
        (f"query {i}", f"document body {i % 3} with words") for i in range(10)
    ]
    base = CrossEncoder(cfg=cfg, seed=4, max_length=64).predict(pairs)
    dp = CrossEncoder(cfg=cfg, seed=4, max_length=64, mesh=mesh).predict(pairs)
    np.testing.assert_allclose(base, dp, atol=2e-5)
    tp_mesh = make_mesh(8, model_parallel=4)
    tp = CrossEncoder(cfg=cfg, seed=4, max_length=64, mesh=tp_mesh).predict(pairs)
    np.testing.assert_allclose(base, tp, atol=2e-5)


def test_serving_mesh_env_knob(monkeypatch, corpus_dir):
    """PATHWAY_SERVING_MESH turns a plain server into a sharded one
    without code changes; 0/unset keeps single-device serving."""
    from pathway_tpu.parallel.mesh import serving_mesh

    monkeypatch.setenv("PATHWAY_SERVING_MESH", "8")
    docs = pw.io.fs.read(
        corpus_dir, format="binary", mode="static", with_metadata=True
    )
    vs = VectorStoreServer(docs, embedder=mocks.FakeEmbedder(dim=16))
    assert vs.index_factory.mesh is not None
    inner = vs.index_factory.build_inner_index()
    assert isinstance(inner.index, ShardedKnnIndex)
    assert inner.index.n_shards == 8
    # one mesh object per env value: the sharded-search cache is keyed
    # on mesh identity, so every server must share it
    assert serving_mesh() is serving_mesh()

    monkeypatch.setenv("PATHWAY_SERVING_MESH", "0")
    G.clear()
    docs = pw.io.fs.read(
        corpus_dir, format="binary", mode="static", with_metadata=True
    )
    vs = VectorStoreServer(docs, embedder=mocks.FakeEmbedder(dim=16))
    assert vs.mesh is None

    monkeypatch.setenv("PATHWAY_SERVING_MESH", "garbage")
    with pytest.warns(UserWarning):
        assert serving_mesh() is None

    # more shards than devices is an error, never "serve on what there is"
    monkeypatch.setenv("PATHWAY_SERVING_MESH", "16")
    with pytest.raises(ValueError, match="only 8 device"):
        serving_mesh()


def _small_real_embedder(mesh=None):
    import jax.numpy as jnp

    from pathway_tpu.models.encoder import EncoderConfig, SentenceEncoder
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    cfg = EncoderConfig(
        vocab_size=512, hidden_dim=32, num_layers=2, num_heads=4, mlp_dim=64,
        max_len=64, dtype=jnp.float32,
    )
    return SentenceTransformerEmbedder(
        encoder=SentenceEncoder(cfg=cfg, seed=7, max_length=64, mesh=mesh)
    )


def test_fused_sharded_serving_e2e_matches_single_device(corpus_dir, mesh):
    """The whole tentpole in one pass: a model-backed embedder serves
    /v1/retrieve through the scheduler's FUSED device path (embeddings
    never round-trip to host between encode and search) over a
    mesh-sharded live index, under the unified runtime — and returns the
    same ranking (scores to 1e-6) as the identical single-device server.
    The sharded tick counter pins that the shard_map path actually ran,
    and /v1/health exposes the mesh block."""
    import json
    import urllib.request

    from pathway_tpu.stdlib.indexing.lowering import live_index_node

    def serve(m):
        docs = pw.io.fs.read(
            corpus_dir, format="binary", mode="streaming", with_metadata=True,
            refresh_interval=0.2,
        )
        vs = VectorStoreServer(docs, embedder=_small_real_embedder(m), mesh=m)
        port = _free_port()
        vs.run_server(
            host="127.0.0.1", port=port, threaded=True, with_cache=False,
            with_scheduler=True,
        )
        client = VectorStoreClient(host="127.0.0.1", port=port)
        probe_text = list(CORPUS.values())[0]

        def ingested():
            r = client.query(probe_text, k=1)
            assert r and r[0]["text"] == probe_text
            return r

        _wait_http(ingested)
        return vs, client, port

    _, single_client, _ = serve(None)
    single = [single_client.query(q, k=3) for q in QUERIES]

    G.clear()
    vs, sharded_client, port = serve(mesh)
    sharded = [sharded_client.query(q, k=3) for q in QUERIES]

    for a_row, b_row in zip(single, sharded):
        assert [r["text"] for r in a_row] == [r["text"] for r in b_row]
        for a, b in zip(a_row, b_row):
            assert a["dist"] == pytest.approx(b["dist"], abs=1e-6)

    node = live_index_node(vs.index_factory)
    inner = node.index.index
    assert isinstance(inner, ShardedKnnIndex)
    assert inner.sharded_ticks > 0  # the fused shard_map path served
    assert sum(inner.shard_row_counts()) == len(CORPUS)

    health = json.loads(
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/v1/health", timeout=10
        ).read()
    )
    assert inner.mesh_label in health.get("mesh", {})
    assert health["mesh"][inner.mesh_label]["devices"] == 8


def test_declarative_mesh_in_yaml_template(corpus_dir):
    """Multi-chip serving is expressible declaratively: a !pw tag builds
    the mesh and threads it into VectorStoreServer (yaml_loader.py)."""
    yaml_text = f"""
$mesh: !pw.parallel.make_mesh
  n_devices: 8

$docs: !pw.io.fs.read
  path: {corpus_dir}
  format: binary
  with_metadata: true
  mode: static

$embedder: !pw.xpacks.llm.mocks.FakeEmbedder
  dim: 16

store: !pw.xpacks.llm.vector_store.VectorStoreServer
  __args__: [$docs]
  embedder: $embedder
  mesh: $mesh
"""
    app = pw.load_yaml(yaml_text)
    vs = app["store"]
    assert vs.index_factory.mesh is not None
    inner = vs.index_factory.build_inner_index()
    assert isinstance(inner.index, ShardedKnnIndex)
