"""Tiered vector index (ISSUE 12): HBM hot tier + routed host-RAM cold
tier with online tier migration.

Covers the tiering contract:

* recall@10 ≥ 0.9 vs the full-HBM f32 oracle with the hot tier capped at
  1/10 of the corpus (the 10×-over-HBM acceptance shape) at the default
  probe width, and EXACT key parity when the probe is exhaustive;
* tier-independent scores: migration-under-load stays bit-exact vs a
  never-migrated oracle — INTERACTIVE searches interleaved with
  BULK_INGEST tier migrations on one DeviceTickRuntime, including
  deletes of in-flight-migrating keys, and the mesh-sharded hot tier
  (mesh 1/2/8);
* placement snapshots: the reserved placement row + delta-chunk header
  (PR 6 framing) rebuild the exact same hot set and routing after a
  restore — bit-for-bit, zero re-embeds;
* the LshProjector/PartitionRouter seed-persistence satellite (specs
  survive save_delta → compaction → restore);
* fatal-device-fault recovery of the hot tier from the host mirror;
* pathway_tier_* metrics on /status and the "tiering" block on
  /v1/health; the PATHWAY_TIER_HOT_ROWS env default reaching the
  factory surface (and serving) with zero plumbing.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

import jax.numpy as jnp

from pathway_tpu.ops.knn import DeviceKnnIndex
from pathway_tpu.parallel import make_mesh
from pathway_tpu.tiering import TieredKnnIndex, tiering_status


def _clustered(n, dim=48, n_centers=32, seed=0):
    """Mixture-of-gaussians corpus + queries (embedding-like structure —
    the same generator knn_crossover.py measures with)."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((n_centers, dim)).astype(np.float32)
    assign = rng.integers(0, n_centers, size=n)
    corpus = (centers[assign] + 0.3 * rng.standard_normal((n, dim))).astype(
        np.float32
    )
    queries = (
        centers[rng.integers(0, n_centers, size=32)]
        + 0.3 * rng.standard_normal((32, dim))
    ).astype(np.float32)
    return corpus, queries


def _vecs(n, dim=32, seed=0):
    return np.random.default_rng(seed).standard_normal((n, dim)).astype(
        np.float32
    )


def _keys(results):
    return [[k for k, _ in row] for row in results]


def _recall(oracle, got):
    hits = total = 0
    for a, b in zip(oracle, got):
        truth = {k for k, _ in a}
        hits += len(truth & {k for k, _ in b})
        total += len(truth)
    return hits / max(total, 1)


# ---------------------------------------------------------------------------
# recall / parity
# ---------------------------------------------------------------------------


def test_recall_at_10_hot_tenth_vs_full_hbm_oracle():
    """The acceptance shape: hot tier capped at 1/10 of the corpus, the
    rest served from routed host-RAM partitions — recall@10 ≥ 0.9 vs the
    full-HBM f32 oracle at the DEFAULT probe width, with the device
    footprint an order of magnitude below the oracle's."""
    n, dim = 4096, 48
    corpus, queries = _clustered(n, dim)
    oracle = DeviceKnnIndex(dim=dim, metric="cos", capacity=n)
    oracle.upsert_batch(list(range(n)), corpus)
    tiered = TieredKnnIndex(
        dim=dim, hot_rows=n // 10, metric="cos", capacity=n,
        n_partitions=64, probe_partitions=8, migrate_batch=0,
    )
    tiered.upsert_batch(list(range(n)), corpus)
    r_oracle = oracle.search(queries, 10)
    r_tiered = tiered.search(queries, 10)
    assert _recall(r_oracle, r_tiered) >= 0.9
    # the HBM bill is the hot tier only — ~1/10 of the oracle's
    assert tiered.hbm_bytes() < oracle.hbm_bytes() / 5
    # the probe really is bounded: far fewer rows scanned than the corpus
    assert tiered.probe_rows_total / tiered.searches < n / 2


@pytest.mark.parametrize("metric", ["cos", "l2sq", "dot"])
def test_exhaustive_probe_matches_oracle_exactly(metric):
    """probe_partitions >= n_partitions makes the cold probe exhaustive:
    result KEYS equal the brute-force oracle's for every metric (scores
    come from the host f32 mirror, so they are exact by construction)."""
    n, dim = 512, 32
    corpus = _vecs(n, dim, seed=3)
    queries = _vecs(8, dim, seed=4)
    oracle = DeviceKnnIndex(dim=dim, metric=metric, capacity=n)
    oracle.upsert_batch(list(range(n)), corpus)
    tiered = TieredKnnIndex(
        dim=dim, hot_rows=32, metric=metric, capacity=n,
        n_partitions=16, probe_partitions=16, migrate_batch=0,
    )
    tiered.upsert_batch(list(range(n)), corpus)
    assert _keys(tiered.search(queries, 10)) == _keys(oracle.search(queries, 10))


def test_upsert_delete_reupsert_and_growth():
    """Deletes vanish from both tiers, re-upserts serve the new vector,
    and the host store grows past its initial capacity."""
    dim = 16
    t = TieredKnnIndex(
        dim=dim, hot_rows=8, capacity=16, n_partitions=4,
        probe_partitions=4, migrate_batch=0,
    )
    vecs = _vecs(40, dim, seed=5)
    t.upsert_batch([f"k{i}" for i in range(40)], vecs)  # grows host 16→64
    assert len(t) == 40 and t.capacity >= 40
    assert len(t._hot_keys) == 8  # budget enforced, never grown past

    # delete a hot key and a cold key
    hot_key = next(iter(t._hot_keys))
    t.remove(hot_key)
    t.remove("k30")
    res = t.search(vecs, 40)
    flat = {k for row in res for k, _ in row}
    assert hot_key not in flat and "k30" not in flat
    assert hot_key not in t._hot_keys

    # re-upsert with a NEW vector: the new row serves
    q = _vecs(1, dim, seed=99)
    t.upsert("k7", q[0])
    top = t.search(q, 1)[0]
    assert top[0][0] == "k7"


def test_device_query_batch_and_n_valid():
    """Fused-tick contract: device query arrays (with trailing dispatch
    pad rows) search identically to host arrays, and n_valid caps the
    assembled rows."""
    dim = 16
    t = TieredKnnIndex(
        dim=dim, hot_rows=8, capacity=64, n_partitions=4,
        probe_partitions=4, migrate_batch=0,
    )
    t.upsert_batch([f"k{i}" for i in range(30)], _vecs(30, dim, seed=1))
    q = _vecs(3, dim, seed=2)
    padded = np.concatenate([q, np.zeros((5, dim), np.float32)])
    r_dev = t.search(jnp.asarray(padded), 5, n_valid=3)
    r_host = t.search(q, 5)
    assert len(r_dev) == 3
    assert r_dev == r_host


# ---------------------------------------------------------------------------
# online migration
# ---------------------------------------------------------------------------


def _tiered_pair(n=384, dim=32, migrate_batch=64, mesh=None, seed=11):
    """(migrating, never-migrated oracle) with exhaustive probe so the
    candidate set is complete and parity is bit-exact by construction."""
    corpus = _vecs(n, dim, seed=seed)
    kw = dict(
        dim=dim, metric="cos", capacity=n, n_partitions=8,
        probe_partitions=8,
    )
    a = TieredKnnIndex(hot_rows=48, migrate_batch=migrate_batch, mesh=mesh, **kw)
    b = TieredKnnIndex(hot_rows=48, migrate_batch=0, **kw)
    keys = [f"doc{i}" for i in range(n)]
    a.upsert_batch(keys, corpus)
    b.upsert_batch(keys, corpus)
    return a, b, corpus, keys


def test_migration_under_load_parity_with_never_migrated_oracle():
    """The PR 7 contention idiom: INTERACTIVE searches interleave with
    BULK_INGEST tier-migration items on ONE runtime; results stay
    bit-exact (keys AND scores) vs a never-migrated oracle the whole
    time, and the placement really moved."""
    from pathway_tpu.runtime import QoS, WorkGroup, get_runtime

    a, b, corpus, keys = _tiered_pair()
    hot0 = set(a._hot_keys)
    rt = get_runtime()
    bulk_before = rt.stats()["classes"]["bulk_ingest"]["completed_total"]
    search_group = WorkGroup(
        "tiered-search", lambda payloads: [a.search(p, 5) for p in payloads],
        max_batch=4,
    )
    # hammer a cold slice so its hit counts overtake the hot tier's;
    # every search may schedule a BULK_INGEST migration item
    probe = corpus[300:308]
    futs = [
        rt.submit(search_group, probe, qos=QoS.INTERACTIVE)
        for _ in range(24)
    ]
    interactive = [f.result(timeout=60) for f in futs]
    b_res = b.search(probe, 5)
    assert all(r == b_res for r in interactive)

    # wait for the scheduled migration items to drain
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if a.migrations["promote"] > 0 and not a._migration_pending:
            break
        a.search(probe, 5)
        time.sleep(0.02)
    assert a.migrations["promote"] > 0
    assert a._hot_keys != hot0  # placement actually changed
    # the migration ran as a REAL deferred BULK_INGEST item, not inline
    # inside the triggering interactive tick (the defer=True contract)
    assert (
        rt.stats()["classes"]["bulk_ingest"]["completed_total"] > bulk_before
    )

    # full parity after migration: bit-exact keys AND scores
    q = _vecs(8, 32, seed=77)
    assert a.search(q, 10) == b.search(q, 10)
    assert rt._thread is not None and rt._thread.is_alive()


def test_migration_failure_never_fails_the_triggering_search(monkeypatch):
    """Tier maintenance is best-effort: a fault in migrate()/the runtime
    submit must not ride the error path of the interactive query that
    happened to be the Nth search — the query keeps its computed
    results, the error is counted, and the trigger re-arms."""
    a, b, corpus, keys = _tiered_pair(migrate_batch=64)

    def boom(*_a, **_k):
        raise RuntimeError("transient device fault")

    monkeypatch.setattr(a, "migrate", boom)
    monkeypatch.setattr(
        type(a), "MIGRATE_CHECK_EVERY", 1, raising=True
    )
    probe = corpus[300:304]
    res = a.search(probe, 5)  # must NOT raise
    assert res == b.search(probe, 5)
    # the failing migrate() runs as a deferred runtime item: its fault
    # is absorbed on the tick thread, counted, and the trigger re-armed
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and (
        a.migrate_errors < 1 or a._migration_pending
    ):
        time.sleep(0.02)
    assert a.migrate_errors >= 1
    assert not a._migration_pending  # re-armed, not stuck
    # healing: with migrate restored the next trigger succeeds again
    monkeypatch.undo()
    for _ in range(a.MIGRATE_CHECK_EVERY):
        a.search(probe, 5)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if a.migrations["promote"] > 0 and not a._migration_pending:
            break
        a.search(probe, 5)
        time.sleep(0.02)
    assert a.migrations["promote"] > 0


def test_delete_of_in_flight_migrating_key_is_a_noop():
    """A key deleted between planning and applying a migration batch is
    skipped (never resurrected into the hot tier), and parity holds."""
    # auto-scheduling off (migrate_batch=0): the test drives the plan /
    # delete / apply interleaving by hand to pin the in-flight window
    a, b, corpus, keys = _tiered_pair(migrate_batch=0)
    # make a definite plan: hammer cold keys
    for _ in range(4):
        a.search(corpus[200:208], 5)
        b.search(corpus[200:208], 5)
    plan = a.plan_migrations(limit=32)
    promos, demos = plan
    assert promos
    victims = [promos[0]] + (demos[:1] if demos else [])
    for v in victims:
        a.remove(v)
        b.remove(v)
    out = a.migrate(plan=plan)
    assert out["promoted"] + out["demoted"] >= 0
    for v in victims:
        assert v not in a._hot_keys
        assert v not in a.slot_of_key
    q = _vecs(8, 32, seed=78)
    assert a.search(q, 10) == b.search(q, 10)


@pytest.mark.parametrize("mesh_n", [1, 2, 8])
def test_sharded_hot_tier_parity(mesh_n):
    """Per-shard hot tiers: a tiered index whose hot tier is
    mesh-sharded answers bit-identically to the single-device tiered
    index, through migrations and deletes."""
    a, b, corpus, keys = _tiered_pair(mesh=make_mesh(mesh_n))
    assert a.n_shards == mesh_n
    for _ in range(3):
        a.search(corpus[100:108], 5)
        b.search(corpus[100:108], 5)
    a.migrate()  # sharded promotions ride the mesh-pinned scatter
    a.remove("doc5")
    b.remove("doc5")
    q = _vecs(8, 32, seed=79)
    assert a.search(q, 10) == b.search(q, 10)
    # the hot tier's arrays still carry the mesh sharding after scatters
    if a.index_dtype == "f32" and mesh_n > 1:
        a.hot.search(q, 1)  # apply staged
        assert a.hot.vectors.sharding == a.hot._vec_sharding


# ---------------------------------------------------------------------------
# snapshots: placement + routing specs
# ---------------------------------------------------------------------------


def test_placement_restore_is_bit_for_bit():
    """restore_placement (what the snapshot plane replays) pins the hot
    set BEFORE rows stream in: the rebuilt index has the exact same
    placement and answers bit-identically — regardless of restore
    iteration order."""
    a, _b, corpus, keys = _tiered_pair(migrate_batch=64)
    for _ in range(4):
        a.search(corpus[200:216], 5)
    a.migrate()
    blob = a.placement_blob()

    restored = TieredKnnIndex(
        dim=32, hot_rows=48, metric="cos", capacity=384,
        n_partitions=8, probe_partitions=8, migrate_batch=0,
    )
    restored.restore_placement(blob)
    # restore in a DIFFERENT (reversed) order than the original ingest
    order = list(range(len(keys)))[::-1]
    restored.upsert_batch(
        [keys[i] for i in order], corpus[np.asarray(order)]
    )
    restored.finish_restore()
    assert restored._hot_keys == a._hot_keys
    assert restored.placement_digest() == a.placement_digest()
    q = _vecs(8, 32, seed=80)
    assert restored.search(q, 10) == a.search(q, 10)


def test_shrunk_hot_budget_truncates_placement_deterministically():
    """An operator lowering PATHWAY_TIER_HOT_ROWS between runs: the
    over-budget placement blob truncates DETERMINISTICALLY (repr-sorted
    prefix), so two restores of the same snapshot — even in different
    row orders — place the same keys hot."""
    a, _b, corpus, keys = _tiered_pair()
    blob = a.placement_blob()
    assert len(blob["hot_keys"]) == 48

    def restore(order):
        r = TieredKnnIndex(
            dim=32, hot_rows=16, metric="cos", capacity=384,
            n_partitions=8, probe_partitions=8, migrate_batch=0,
        )
        r.restore_placement(blob)
        r.upsert_batch([keys[i] for i in order], corpus[np.asarray(order)])
        r.finish_restore()
        return r

    fwd = restore(list(range(len(keys))))
    rev = restore(list(range(len(keys)))[::-1])
    assert len(fwd._hot_keys) == 16
    assert fwd._hot_keys == rev._hot_keys
    assert fwd._hot_keys == set(sorted(blob["hot_keys"], key=repr)[:16])


def test_placement_rides_the_snapshot_plane_end_to_end(tmp_path):
    """Node-level e2e over the PR 6 chunked-snapshot plane: the reserved
    placement row + delta-chunk header persist through save_delta →
    restore, and the restored node rebuilds the same placement with zero
    encoder involvement."""
    from pathway_tpu.persistence import ChunkedOperatorSnapshot, FilesystemKV
    from pathway_tpu.stdlib.indexing.lowering import ExternalIndexNode
    from pathway_tpu.stdlib.indexing.retrievers import BruteForceKnnFactory

    def make_node(pid="tiered-test"):
        factory = BruteForceKnnFactory(
            dimensions=16, reserved_space=64, hot_rows=12
        )
        node = ExternalIndexNode(
            factory.build_inner_index(),
            doc_data_fn=lambda ctx: ctx[1][0],
            doc_meta_fn=lambda ctx: ctx[1][1],
            query_data_fn=lambda ctx: ctx[1][0],
            query_k_fn=lambda ctx: 3,
            query_filter_fn=lambda ctx: None,
            doc_payload_fn=lambda ctx: (ctx[1][2],),
            name=pid,
        )
        node.persistent_id = pid
        return node

    rng = np.random.default_rng(21)
    entries = [
        (f"doc{i}", (rng.standard_normal(16).astype(np.float32),
                     {"i": i}, f"text {i}"), 1)
        for i in range(40)
    ]
    kv = FilesystemKV(str(tmp_path / "kv"))
    snap = ChunkedOperatorSnapshot(kv, background=False)
    node = make_node()
    node._op_snapshot = snap
    node.receive(0, entries)
    node.flush(1)
    node.end_of_step(1)

    inner = node.index.index  # the TieredKnnIndex
    assert len(inner._hot_keys) == 12
    # migrate, then a doc change commits the new placement
    for _ in range(4):
        inner.search(np.stack([entries[30][1][0]]), 3)
    inner.migrate()
    node.receive(0, [entries[0]])
    node.flush(2)
    node.end_of_step(2)

    restored = make_node()
    snap2 = ChunkedOperatorSnapshot(kv, background=False)
    state, last_t = snap2.restore("tiered-test")
    assert last_t == 2
    # the driver applies the header (routing spec) before the rows
    header = snap2.last_restored_header("tiered-test")
    assert header and "router" in header
    restored.apply_snapshot_header(header)
    restored.restore_snapshot(state)
    r_inner = restored.index.index
    assert r_inner._hot_keys == inner._hot_keys
    assert r_inner.placement_digest() == inner.placement_digest()
    assert restored.restored_rows == 40  # the placement row is NOT a doc
    q = entries[7][1][0]
    assert restored._answer([(q,)]) == node._answer([(q,)])


def test_idle_migration_flushes_placement_without_new_input(tmp_path):
    """A migration driven purely by query traffic (no ingest in flight)
    must still reach the snapshot plane: the node reports
    placement_flush_pending, the engine surfaces it, and an idle
    end_of_step persists the new placement — a kill in an ingest lull
    then restores the MIGRATED placement, not the older one."""
    from pathway_tpu.persistence import ChunkedOperatorSnapshot, FilesystemKV
    from pathway_tpu.stdlib.indexing.lowering import ExternalIndexNode
    from pathway_tpu.stdlib.indexing.retrievers import BruteForceKnnFactory

    def make_node(pid="tiered-idle"):
        factory = BruteForceKnnFactory(
            dimensions=16, reserved_space=64, hot_rows=12
        )
        node = ExternalIndexNode(
            factory.build_inner_index(),
            doc_data_fn=lambda ctx: ctx[1][0],
            doc_meta_fn=lambda ctx: ctx[1][1],
            query_data_fn=lambda ctx: ctx[1][0],
            query_k_fn=lambda ctx: 3,
            query_filter_fn=lambda ctx: None,
            doc_payload_fn=lambda ctx: (ctx[1][2],),
            name=pid,
        )
        node.persistent_id = pid
        return node

    rng = np.random.default_rng(23)
    entries = [
        (f"doc{i}", (rng.standard_normal(16).astype(np.float32),
                     {"i": i}, f"text {i}"), 1)
        for i in range(40)
    ]
    kv = FilesystemKV(str(tmp_path / "kv"))
    snap = ChunkedOperatorSnapshot(kv, background=False)
    node = make_node()
    node._op_snapshot = snap
    node.receive(0, entries)
    node.flush(1)
    node.end_of_step(1)
    assert not node.placement_flush_pending()

    # pure query traffic migrates the tier — NO new input follows
    inner = node.index.index
    for _ in range(4):
        inner.search(np.stack([entries[30][1][0]]), 3)
    moved = inner.migrate()
    assert moved["promoted"] or moved["demoted"]
    assert node.placement_flush_pending()

    # the engine surfaces the pending flush to the streaming driver
    from pathway_tpu.internals.engine import Engine

    class _Eng:
        nodes = [node]
        has_placement_flush_pending = Engine.has_placement_flush_pending

    assert _Eng().has_placement_flush_pending()

    # ...which steps once while idle: the placement row persists with no
    # doc deltas in flight
    node.end_of_step(2)
    assert not node.placement_flush_pending()

    restored = make_node()
    snap2 = ChunkedOperatorSnapshot(kv, background=False)
    state, last_t = snap2.restore("tiered-idle")
    assert last_t == 2
    restored.apply_snapshot_header(snap2.last_restored_header("tiered-idle"))
    restored.restore_snapshot(state)
    assert restored.index.index._hot_keys == inner._hot_keys
    assert (
        restored.index.index.placement_digest() == inner.placement_digest()
    )


def test_router_and_lsh_specs_survive_header_compaction(tmp_path):
    """Satellite bugfix: seeds/projections persist in the delta-chunk
    header (FORMAT_VERSION-compatible) and survive compaction — a
    restored process recreates bit-identical projections/centroids."""
    from pathway_tpu.ops.lsh import LshProjector, PartitionRouter
    from pathway_tpu.persistence import ChunkedOperatorSnapshot, MemoryKV

    proj = LshProjector(dim=12, n_or=4, n_and=6, seed=1234)
    router = PartitionRouter(dim=12, n_partitions=8, seed=77)
    header = {"lsh": proj.spec(), "router": router.spec()}

    kv = MemoryKV()
    snap = ChunkedOperatorSnapshot(kv, background=False)
    for t in range(1, 6):
        snap.save_delta(
            "pid", t, {f"k{t}": t}, live_entries=5, header=header
        )
    snap.mark_committed(5)
    snap.compact_now("pid")
    snap2 = ChunkedOperatorSnapshot(kv)
    state, last_t = snap2.restore("pid")
    assert last_t == 5 and len(state) == 5
    assert snap2.last_restored_header("pid") == header

    # rebuilt-from-spec objects route identically
    v = _vecs(20, 12, seed=6)
    proj2 = LshProjector.from_spec(header["lsh"])
    assert np.array_equal(proj.signatures(v), proj2.signatures(v))
    router2 = PartitionRouter.from_spec(header["router"])
    assert np.array_equal(router.assign(v), router2.assign(v))
    assert np.array_equal(router.route(v, 3), router2.route(v, 3))


def test_lsh_index_applies_restored_header():
    """An LshKnnIndex restored under a DIFFERENT default seed adopts the
    persisted projector spec and buckets the same vectors identically to
    the writer — the restore-parity pin for the seed satellite."""
    from pathway_tpu.stdlib.indexing.retrievers import LshKnnIndex

    dim = 16
    vecs = _vecs(30, dim, seed=8)
    writer = LshKnnIndex(dim=dim, seed=4242)
    for i in range(30):
        writer.add(f"k{i}", vecs[i], None)
    header = writer.snapshot_header()
    assert header["lsh"]["seed"] == 4242

    reader = LshKnnIndex(dim=dim)  # default seed — WOULD route differently
    reader.apply_snapshot_header(header)
    assert reader.projector.spec() == writer.projector.spec()
    for i in range(30):
        reader.add(f"k{i}", vecs[i], None)
    q = [(vecs[3], 5, None)]
    assert reader.search(q) == writer.search(q)

    # applying a conflicting spec over a NON-empty index must refuse
    other = LshKnnIndex(dim=dim)
    other.add("k0", vecs[0], None)
    with pytest.raises(RuntimeError):
        other.apply_snapshot_header({"lsh": writer.projector.spec()})


def test_quant_record_dequantizes_into_tiered_index():
    """A dtype transition: int8-era snapshot records load into a tiered
    index by dequantizing once (the cold store is f32)."""
    from pathway_tpu.ops.quantized_scoring import quantize_record_np

    t = TieredKnnIndex(
        dim=16, hot_rows=4, capacity=32, n_partitions=4,
        probe_partitions=4, migrate_batch=0,
    )
    v = _vecs(1, 16, seed=9)[0]
    rec = quantize_record_np(v, normalize=True)
    t.upsert_coded("a", rec)
    assert len(t) == 1
    top = t.search(v[None, :], 1)[0]
    assert top[0][0] == "a"


# ---------------------------------------------------------------------------
# device-fault recovery
# ---------------------------------------------------------------------------


def test_hot_tier_rebuilds_from_host_mirror(monkeypatch):
    """Fatal device fault: even when the hot index's own rebuild fails,
    the tier rebuilds from the host mirror — same placement, same
    answers, rebuild counter bumped."""
    t = TieredKnnIndex(
        dim=16, hot_rows=8, capacity=64, n_partitions=4,
        probe_partitions=4, migrate_batch=0,
    )
    t.upsert_batch([f"k{i}" for i in range(30)], _vecs(30, 16, seed=10))
    q = _vecs(4, 16, seed=11)
    before = t.search(q, 5)
    hot_before = set(t._hot_keys)

    monkeypatch.setattr(
        type(t.hot), "rebuild_device_arrays", lambda self, v=None: False
    )
    assert t.rebuild_device_arrays() is True
    assert t.rebuilds == 1
    assert t._hot_keys == hot_before
    assert len(t.hot) == len(hot_before)
    assert t.search(q, 5) == before


# ---------------------------------------------------------------------------
# observability + factory surface
# ---------------------------------------------------------------------------


def test_tiering_status_metrics_and_health():
    from pathway_tpu.internals.health import get_health, reset_health
    from pathway_tpu.internals.monitoring import register_metrics_provider_once
    from pathway_tpu.tiering.index import _TierMetricsProvider

    _tier_provider = register_metrics_provider_once("tiering", _TierMetricsProvider)

    t = TieredKnnIndex(
        dim=16, hot_rows=8, capacity=64, n_partitions=4,
        probe_partitions=3, migrate_batch=0,
    )
    t.upsert_batch([f"k{i}" for i in range(20)], _vecs(20, 16, seed=12))
    t.search(_vecs(2, 16, seed=13), 3)

    status = tiering_status()
    assert status is not None
    info = status[t.tier_label]
    assert info["hot_rows"] == 8 and info["cold_rows"] == 12
    assert info["probe_partitions"] == 3
    assert info["searches"] >= 2
    assert info["hbm_bytes"] == t.hbm_bytes()
    assert info["host_bytes"] > 0

    lines = "\n".join(_tier_provider.openmetrics_lines())
    assert f'pathway_tier_rows{{index="{t.tier_label}",tier="hot"}} 8' in lines
    assert f'pathway_tier_rows{{index="{t.tier_label}",tier="cold"}} 12' in lines
    assert (
        f'pathway_tier_migrations_total{{index="{t.tier_label}",'
        f'direction="promote"}} 0' in lines
    )
    assert f'pathway_tier_probe_partitions{{index="{t.tier_label}"}} 3' in lines

    reset_health()
    snap = get_health().snapshot()
    assert "tiering" in snap
    assert snap["tiering"][t.tier_label]["hot_rows_budget"] == 8
    reset_health()

    # the hot tier surfaces its role next to the quantization block
    from pathway_tpu.ops.knn import quantization_status

    q = quantization_status() or {}
    assert q[t.hot.quant_label]["role"] == "hot"


def test_status_openmetrics_includes_tier_series():
    from pathway_tpu.internals.monitoring import StatsMonitor

    t = TieredKnnIndex(
        dim=16, hot_rows=4, capacity=32, n_partitions=4,
        probe_partitions=4, migrate_batch=0,
    )
    t.upsert("a", _vecs(1, 16, seed=14)[0])
    text = StatsMonitor().openmetrics()
    assert "pathway_tier_rows" in text
    assert "pathway_tier_migrations_total" in text


def test_env_knob_reaches_factory(monkeypatch):
    """PATHWAY_TIER_HOT_ROWS flows through the factory surface with zero
    plumbing; 0/garbage keeps the untiered device index."""
    from pathway_tpu.stdlib.indexing.retrievers import BruteForceKnnIndex

    monkeypatch.setenv("PATHWAY_TIER_HOT_ROWS", "16")
    idx = BruteForceKnnIndex(dim=8, capacity=64)
    assert isinstance(idx.index, TieredKnnIndex)
    assert idx.index.hot_rows == 16

    monkeypatch.setenv("PATHWAY_TIER_HOT_ROWS", "bogus")
    idx2 = BruteForceKnnIndex(dim=8, capacity=64)
    assert isinstance(idx2.index, DeviceKnnIndex)

    monkeypatch.delenv("PATHWAY_TIER_HOT_ROWS")
    idx3 = BruteForceKnnIndex(dim=8, capacity=64)
    assert isinstance(idx3.index, DeviceKnnIndex)


def test_env_knob_reaches_serving_retrieve(monkeypatch, tmp_path):
    """PATHWAY_TIER_HOT_ROWS=N through the product API: the same corpus
    retrieves the same documents through VectorStoreServer, and the live
    index really is tiered."""
    import pathway_tpu as pw
    import pathway_tpu.debug as dbg
    from pathway_tpu.internals.graph import G
    from pathway_tpu.xpacks.llm import mocks
    from pathway_tpu.xpacks.llm.vector_store import (
        RetrieveQuerySchema,
        VectorStoreServer,
    )

    corpus = {
        "doc1.txt": "Berlin is the capital of Germany.",
        "doc2.txt": "Paris is the capital of France.",
        "doc3.txt": "The quick brown fox jumps over the lazy dog.",
    }
    for name, text in corpus.items():
        (tmp_path / name).write_text(text)
    queries = ["Which city is the capital of France?", "fox jumping"]

    def run():
        docs = pw.io.fs.read(
            tmp_path, format="binary", mode="static", with_metadata=True
        )
        vs = VectorStoreServer(docs, embedder=mocks.FakeEmbedder(dim=16))
        qt = dbg.table_from_rows(
            RetrieveQuerySchema, [(q, 2, None, None) for q in queries]
        )
        _, cols = dbg.table_to_dicts(vs.retrieve_query(qt))
        return sorted(
            [[r["text"] for r in res.value] for res in cols["result"].values()]
        )

    base = run()
    G.clear()
    before = set(tiering_status() or {})
    monkeypatch.setenv("PATHWAY_TIER_HOT_ROWS", "2")
    monkeypatch.setenv("PATHWAY_TIER_PROBE_PARTITIONS", "64")
    tiered = run()
    assert tiered == base
    status = tiering_status() or {}
    fresh = [
        info for label, info in status.items() if label not in before
    ]
    assert fresh and fresh[0]["hot_rows_budget"] == 2
    assert fresh[0]["searches"] >= 1
