"""A batch of connector rows is traced like a request (ISSUE 38): an engine
timestamp that carries them has one trace id, derived from the engine and the
timestamp, and seven segments from the connector's read to the index that add
up to its ``ingest.read_to_indexed``, in ``pathway_request_stage_ms{stage=}``
and in the flight recorder's ring.

The graphs are small and run on the CPU: a watched directory, a parser, the
index node over a deterministic encoder (an ``AsyncMicroBatcher`` through the
tick runtime, or the same batcher on the loop: a host UDF that rides no
tick), and a recording stand-in for the index.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu import debug as pwd
from pathway_tpu.internals import flight_recorder as fr
from pathway_tpu.internals import udfs
from pathway_tpu.internals.monitoring import (
    INGEST_SEGMENTS,
    FreshnessTracker,
)
from pathway_tpu.stdlib.indexing.data_index import _build_index_operator
from pathway_tpu.stdlib.indexing.lowering import live_index_node
from pathway_tpu.stdlib.indexing.retrievers import InnerIndexFactory
from pathway_tpu.xpacks.llm._utils import AsyncMicroBatcher
from pathway_tpu.xpacks.llm.embedders import BaseEmbedder

DIM = 8


def _vector(text: str) -> np.ndarray:
    seed = int.from_bytes(text.encode()[:8].ljust(8, b"\0"), "little") % 2**32
    return np.random.default_rng(seed).normal(size=DIM).astype(np.float32)


class BatchedEmbedder(BaseEmbedder):
    """A deterministic embedder over an ``AsyncMicroBatcher``, as
    ``SentenceTransformerEmbedder`` is: ``use_scheduler`` True sends its
    calls through the tick runtime, False batches them on the loop."""

    def __init__(self, use_scheduler: bool = True):
        super().__init__(executor=udfs.async_executor(), deterministic=True)
        self._batcher = AsyncMicroBatcher(self._batch, use_scheduler=use_scheduler)

    def _batch(self, texts):
        return [_vector(t) for t in texts]

    async def __wrapped__(self, input: str, **kwargs) -> np.ndarray:
        return await self._batcher.call(input)

    def get_embedding_dimension(self, **kwargs) -> int:
        return DIM


class _Index:
    def __init__(self):
        self.rows: dict = {}

    def add_batch(self, keys, datas, metas):
        for key, data, meta in zip(keys, datas, metas):
            self.rows[key] = (data, meta)

    def remove(self, key):
        self.rows.pop(key, None)

    def search(self, queries):
        return [[(key, 1.0) for key in self.rows] for _ in queries]


class _Factory(InnerIndexFactory):
    def build_inner_index(self):
        return _Index()


def _batches(scope: int) -> dict[int, list[fr.Span]]:
    """This engine's batch records in the ring, by engine timestamp."""
    prefix = fr.batch_trace_id(scope, 0)[:16]
    out: dict[int, list[fr.Span]] = {}
    for s in fr.get_recorder().spans(mark_read=False):
        if s.trace_id and s.trace_id.startswith(prefix):
            out.setdefault(int(s.trace_id[16:], 16), []).append(s)
    return out


def _closed(spans: list[fr.Span]) -> list[fr.Span]:
    return [s for s in spans if s.name == "ingest.read_to_indexed"]


def _run_watched(tmp_path, use_scheduler: bool) -> tuple[int, dict, list]:
    """Drop two files one batch apart, delete the first alone, stop; returns
    the engine's scope, its batches in the ring and the docs' updates as
    ``(engine time, is_addition)``."""
    watched = tmp_path / "watched"
    watched.mkdir()
    files = pw.io.fs.read(
        watched, format="plaintext_by_file", mode="streaming",
        refresh_interval=0.05,
    )
    # the parser: an operator of the step before the late index node
    docs = files.select(
        text=pw.apply_with_type(lambda s: s.strip().lower(), str, pw.this.data)
    )
    queries = pwd.table_from_markdown(
        """
        | q
    90  | x
    """
    )
    factory = _Factory()
    embedder = BatchedEmbedder(use_scheduler=use_scheduler)
    replies = _build_index_operator(
        docs, queries, factory, embedder(docs.text), queries.q, k=10
    )
    pw.io.subscribe(replies, on_change=lambda key, row, t, add: None)
    updates: list[tuple[int, bool]] = []
    pw.io.subscribe(
        docs, on_change=lambda key, row, t, add: updates.append((t, add))
    )
    subject = files._operator.params["subject"]
    found: dict = {}
    errors: list = []

    def drive():
        try:
            deadline = time.monotonic() + 60
            while live_index_node(factory) is None:
                assert time.monotonic() < deadline, "the index never came up"
                time.sleep(0.01)
            found["scope"] = scope = live_index_node(factory)._freshness_scope

            def settle(n):
                while len(_closed(sum(_batches(scope).values(), []))) < n:
                    assert time.monotonic() < deadline, f"{n} batches never closed"
                    time.sleep(0.01)

            (watched / "a.txt").write_text("Alpha document")
            settle(1)
            (watched / "b.txt").write_text("Beta document, longer")
            settle(2)
            (watched / "a.txt").unlink()
            settle(3)
        except BaseException as exc:  # noqa: BLE001 — reported below
            errors.append(exc)
        finally:
            subject.close()

    th = threading.Thread(target=drive)
    th.start()
    pw.run()
    th.join(timeout=60)
    assert not errors, errors
    return found["scope"], _batches(found["scope"]), updates


@pytest.mark.parametrize("use_scheduler", [True, False], ids=["ticks", "host_udf"])
def test_seven_segments_add_up_to_read_to_indexed(tmp_path, use_scheduler):
    fr.reset_recorder()
    scope, batches, updates = _run_watched(tmp_path, use_scheduler)
    removal = [t for t, add in updates if not add]
    assert len(removal) == 1 and (removal[0], True) not in updates
    traced = {t: spans for t, spans in batches.items() if _closed(spans)}
    assert len(traced) == 3 and removal[0] in traced  # the all-removes one too
    for t, spans in traced.items():
        (root,) = _closed(spans)
        assert root.attrs["t"] == t and root.parent_id is None
        segments = {s.name: s for s in spans if s.parent_id == root.span_id}
        assert sorted(segments) == sorted(INGEST_SEGMENTS)
        assert [s.name for s in spans if s.name in INGEST_SEGMENTS] == list(
            INGEST_SEGMENTS
        ), "each segment is observed once, in order"
        total = sum(s.duration_ms for s in segments.values())
        assert total == pytest.approx(root.duration_ms, abs=1e-3)  # 1 us
        for a, b in zip(INGEST_SEGMENTS, INGEST_SEGMENTS[1:]):
            assert segments[a].duration_ms >= 0.0
            end = segments[a].start_s + segments[a].duration_ms / 1000.0
            assert end == pytest.approx(segments[b].start_s, abs=1e-6)
        assert segments["ingest.commit_to_step"].attrs["messages"] == 1
        (doc_data,) = [s for s in spans if s.name == "index.doc_data"]
        ticks = [s for s in spans if s.name.startswith("tick:")]
        if use_scheduler:
            assert ticks, "the embed calls rode a tick under the batch's id"
            assert segments["ingest.tick"].duration_ms > 0.0
        else:
            # no tick: the whole of index.doc_data is ingest.index_to_tick
            assert not ticks
            assert segments["ingest.tick"].duration_ms == 0.0
            assert segments["ingest.tick_to_embedded"].duration_ms == 0.0
            assert segments["ingest.index_to_tick"].duration_ms == pytest.approx(
                doc_data.duration_ms, abs=1e-3
            )


def test_stages_are_observed_once_per_indexed_batch(tmp_path):
    fr.reset_recorder()
    before = _stage_counts()
    _scope, batches, _updates = _run_watched(tmp_path, True)
    after = _stage_counts()
    closed = after["ingest.read_to_indexed"] - before.get("ingest.read_to_indexed", 0)
    assert closed >= 3
    for stage in INGEST_SEGMENTS:
        assert after[stage] - before.get(stage, 0) == closed


def _stage_counts() -> dict[str, float]:
    out = {}
    for line in fr.observability_metrics_lines():
        head = 'pathway_request_stage_ms_count{stage="'
        if line.startswith(head):
            stage, value = line[len(head):].split('"} ')
            out[stage] = float(value)
    return out


def test_one_batch_comes_back_by_its_trace_id(tmp_path):
    """``/v1/debug/traces?trace_id=`` of one batch returns its whole way:
    the seven segments under their root, the step's ``flush:<node>`` spans,
    ``index.doc_data`` and the tick's span; ``?category=ingest`` lists it."""
    from pathway_tpu.xpacks.llm.vector_store import (
        VectorStoreClient,
        VectorStoreServer,
    )

    fr.reset_recorder()
    for i in range(3):
        (tmp_path / f"doc{i}.txt").write_text(f"Document {i} with marker m{i}.")
    docs = pw.io.fs.read(
        tmp_path, format="binary", mode="streaming", with_metadata=True,
        refresh_interval=0.05,
    )
    vs = VectorStoreServer(docs, embedder=BatchedEmbedder())
    import socket

    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    vs.run_server(host="127.0.0.1", port=port, threaded=True, with_cache=False)
    client = VectorStoreClient(host="127.0.0.1", port=port)
    deadline = time.monotonic() + 60

    def get(query: str) -> dict:
        url = f"http://127.0.0.1:{port}/v1/debug/traces?{query}"
        return json.loads(urllib.request.urlopen(url, timeout=10).read())

    while True:
        try:
            if client.query("Document 1 with marker m1.", k=1):
                roots = [s for s in get("category=ingest")["spans"]
                         if s["name"] == "ingest.read_to_indexed"]
                if roots:
                    break
        except Exception:  # noqa: BLE001 — server still starting
            pass
        assert time.monotonic() < deadline, "no batch was ever indexed"
        time.sleep(0.1)
    trace_id = roots[0]["trace_id"]
    spans = get(f"trace_id={trace_id}")["spans"]
    assert {s["trace_id"] for s in spans} == {trace_id}
    names = [s["name"] for s in spans]
    assert set(INGEST_SEGMENTS) <= set(names)
    assert names.count("ingest.read_to_indexed") == 1
    assert "index.doc_data" in names
    flushes = [n for n in names if n.startswith("flush:")]
    assert any(n.startswith("flush:index#") for n in flushes)
    assert len(flushes) > 1, "the step's other operators file under the batch"
    assert any(n.startswith("tick:") for n in names)
    assert not [s for s in fr.get_recorder().spans(mark_read=False)
                if s.name.startswith("commit:")], "the zero-length record is gone"


def test_pending_batches_stay_bounded():
    """An engine that stamps faster than it indexes: the pending record keeps
    the newest ``MAX_PENDING`` timestamps, and no milestone of an evicted one
    brings it back."""
    tracker = FreshnessTracker()
    n = tracker.MAX_PENDING + 300
    for t in range(1, n + 1):
        tracker.note_source("conn-0", t, 100.0 + t, scope=7)
        tracker.note_commit("conn-0", t, 100.5 + t, 1, scope=7)
        assert tracker.note_step(t, 101.0 + t, scope=7)
    assert len(tracker._batches) == len(tracker._source_order) == tracker.MAX_PENDING
    assert tracker._source_order[0] == fr.batch_trace_id(7, n - tracker.MAX_PENDING + 1)
    evicted = 5
    assert not tracker.note_step(evicted, 0.0, scope=7)
    assert not tracker.note_index(evicted, 0.0, scope=7)
    tracker.note_embedded(evicted, 0.0, scope=7)
    tracker.note_tick([(fr.batch_trace_id(7, evicted), None)], 0.0, 1.0)
    tracker.note_commit("conn-0", evicted, 0.0, 1, scope=7)
    assert fr.batch_trace_id(7, evicted) not in tracker._batches
    assert len(tracker._batches) == tracker.MAX_PENDING
    # a milestone of a pending one stamps it; closing it pops it
    assert tracker.note_index(n, 102.0 + n, scope=7)
    tracker.note_ingest(n, 101.0 + n, scope=7)
    tracker.note_indexed("idx", n, scope=7)
    assert fr.batch_trace_id(7, n) not in tracker._batches


def test_milestones_fill_and_clamp():
    """A milestone not stamped takes the next one's time, and none lies
    after the next: the seven segments always add up to the whole way."""
    from pathway_tpu.internals.monitoring import _Batch

    batch = _Batch()
    batch.step, batch.index, batch.embedded = 2.0, 3.0, 5.0
    batch.tick_start, batch.tick_end = 4.0, 5.5  # the tick ended after
    ms = batch.milestones(1.0, None, 6.0)
    assert ms == [1.0, 2.0, 2.0, 3.0, 4.0, 5.0, 5.0, 6.0]
    assert ms[-1] - ms[0] == sum(b - a for a, b in zip(ms, ms[1:]))
    assert _Batch().milestones(1.0, 1.5, 4.0) == [1.0, 1.5] + [4.0] * 6


def test_ring_start_and_profiler_event_share_one_clock(tmp_path):
    """A span's ring ``start_s`` and its ``pw.`` event in the CPU profiler's
    trace agree within 1 ms once the event's ``start_ns`` is put on the wall
    clock: a host event's ``start_ns`` counts from the session's
    ``profile_start_time`` (a stat of the ``Task Environment`` plane, in
    nanoseconds since the epoch)."""
    import os
    import sys

    import jax
    from jax.profiler import ProfileData

    bench = os.path.join(os.path.dirname(os.path.dirname(__file__)), "perfbench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import trace_reduce

    fr.reset_recorder()

    def body():
        fr.name_thread("pw-clock-test")
        for _ in range(5):
            with fr.span("clock", "unit"):
                time.sleep(0.002)
            time.sleep(0.005)

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        th = threading.Thread(target=body)
        th.start()
        th.join(timeout=30)
    finally:
        jax.profiler.stop_trace()
    data = ProfileData.from_file(trace_reduce.find_xplane(str(tmp_path)))
    (base_ns,) = [v for plane in data.planes for k, v in plane.stats
                  if k == "profile_start_time"]
    (line,) = [ln for plane in data.planes if plane.name.startswith("/host:CPU")
               for ln in plane.lines if ln.name == "pw-clock-test"]
    events = [ev for ev in line.events if ev.name == "pw.unit.clock"]
    ring = fr.get_recorder().spans(category="unit", mark_read=False)
    assert len(events) == len(ring) == 5
    for ev, s in zip(events, ring):
        wall = (base_ns + ev.start_ns) / 1e9
        assert abs(wall - s.start_s) < 1e-3
        assert abs(ev.duration_ns / 1e6 - s.duration_ms) < 1.0
