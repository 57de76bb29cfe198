"""Serving-scheduler tests (ISSUE 2): cross-request coalescing, the
max_wait_ms flush, deadline/overload shedding (work never executes),
fused embed→search parity with the engine-routed two-stage path, and
scheduler observability on the OpenMetrics endpoint."""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

import pathway_tpu as pw
from pathway_tpu.xpacks.llm import mocks
from pathway_tpu.xpacks.llm._scheduler import (
    DeadlineExceeded,
    SchedulerOverloaded,
    ServingScheduler,
    WorkGroup,
    get_scheduler,
)
from pathway_tpu.xpacks.llm.vector_store import VectorStoreClient, VectorStoreServer


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_http(call, timeout=15.0):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            return call()
        except Exception as exc:  # noqa: BLE001 — server still starting
            last = exc
            time.sleep(0.2)
    raise TimeoutError(f"server did not come up: {last}")


# ---------------------------------------------------------------------------
# scheduler unit behavior
# ---------------------------------------------------------------------------


def test_scheduler_coalesces_across_threads():
    """N concurrent submitters (the stand-in for N in-flight REST
    requests) must land in one multi-request device batch."""
    sched = ServingScheduler(max_wait_ms=150, name="t-coalesce")
    sizes = []

    def fn(xs):
        sizes.append(len(xs))
        return [x + 1 for x in xs]

    group = WorkGroup("inc", fn)
    n = 8
    barrier = threading.Barrier(n)
    results = {}

    def worker(i):
        barrier.wait()
        results[i] = sched.submit(group, i).result(timeout=10)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == {i: i + 1 for i in range(n)}
    assert max(sizes) > 1, f"no multi-request batch formed: {sizes}"
    stats = sched.stats()
    assert stats["multi_item_batches_total"] >= 1
    assert stats["batch_occupancy_max"] == max(sizes)
    assert stats["completed_total"] == n


def test_max_wait_flushes_idle_queue():
    """A lone item must not wait for max_batch: the max_wait_ms window
    closes and the batch dispatches."""
    sched = ServingScheduler(max_wait_ms=20, max_batch=1024, name="t-flush")
    group = WorkGroup("echo", lambda xs: xs)
    t0 = time.monotonic()
    assert sched.submit(group, "x").result(timeout=5) == "x"
    assert time.monotonic() - t0 < 2.0
    assert sched.stats()["batch_occupancy_max"] == 1


def test_deadline_shed_never_executes():
    """An expired item is answered with DeadlineExceeded and its device
    work never runs."""
    sched = ServingScheduler(max_wait_ms=80, retry_after_s=0.5, name="t-shed")
    executed = []

    def fn(xs):
        executed.extend(xs)
        return xs

    group = WorkGroup("record", fn)
    # the 80 ms admission window is an order of magnitude past the 5 ms
    # deadline, so the item is guaranteed expired at drain time
    fut = sched.submit(group, "doomed", deadline_s=0.005)
    with pytest.raises(DeadlineExceeded) as err:
        fut.result(timeout=5)
    assert err.value.retry_after_s == 0.5
    assert executed == []
    assert sched.stats()["shed_deadline_total"] == 1
    # no deadline → never shed, even through the same window
    assert sched.submit(group, "ok").result(timeout=5) == "ok"
    assert executed == ["ok"]


def test_overload_admission_refused():
    """Submissions beyond max_queue are refused immediately
    (backpressure), not queued unboundedly."""
    sched = ServingScheduler(max_wait_ms=1, max_queue=2, name="t-full")
    release = threading.Event()
    started = threading.Event()

    def blocking(xs):
        started.set()
        release.wait(10)
        return xs

    blocker = WorkGroup("block", blocking)
    fast = WorkGroup("fast", lambda xs: xs)
    held = sched.submit(blocker, 0)
    assert started.wait(5), "scheduler loop never picked up the blocker"
    # the loop is inside the blocked tick: these two fill the queue …
    q1 = sched.submit(fast, 1)
    q2 = sched.submit(fast, 2)
    # … and the next sheddable submission is refused at admission
    with pytest.raises(SchedulerOverloaded):
        sched.submit(fast, 3, sheddable=True).result(timeout=5)
    # engine-plane (unsheddable) work is exempt: it must never be refused
    exempt = sched.submit(fast, 4)
    assert sched.stats()["shed_queue_total"] == 1
    release.set()
    assert held.result(5) == 0 and q1.result(5) == 1 and q2.result(5) == 2
    assert exempt.result(5) == 4


def test_batch_handler_error_propagates_to_every_waiter():
    sched = ServingScheduler(max_wait_ms=50, name="t-err")

    def boom(xs):
        raise RuntimeError("kaput")

    group = WorkGroup("boom", boom)
    futs = [sched.submit(group, i) for i in range(3)]
    for fut in futs:
        with pytest.raises(RuntimeError, match="kaput"):
            fut.result(timeout=5)
    assert sched.stats()["failed_total"] == 3


def test_no_new_xla_compiles_per_distinct_concurrent_k():
    """Heterogeneous serving k and ragged tick sizes must reuse the
    power-of-two buckets (bucket_k / bucket_q): after warming one variant
    per bucket, no distinct (Q, k) combination compiles a new program."""
    import numpy as np

    from pathway_tpu.ops import topk
    from pathway_tpu.ops.knn import DeviceKnnIndex

    idx = DeviceKnnIndex(dim=8, capacity=64)
    rng = np.random.default_rng(0)
    for i in range(40):
        idx.upsert(i, rng.standard_normal(8))
    # one warm search per k bucket in play (k≤8 → buckets 4 and 8)
    idx.search(rng.standard_normal((3, 8)), k=4)
    idx.search(rng.standard_normal((3, 8)), k=8)
    n0 = topk.topk_search._cache_size()
    for k in (3, 4, 5, 6, 7, 8):
        for q in (1, 2, 5, 8):  # ragged scheduler-tick batch sizes
            rows = idx.search(rng.standard_normal((q, 8)), k=k)
            assert len(rows) == q and all(len(r) == k for r in rows)
    assert topk.topk_search._cache_size() == n0, (
        "a distinct concurrent (Q, k) compiled a fresh XLA program"
    )


# ---------------------------------------------------------------------------
# REST serving integration
# ---------------------------------------------------------------------------


@pytest.fixture
def corpus_dir(tmp_path):
    for i in range(6):
        (tmp_path / f"doc{i}.txt").write_text(
            f"Document {i} about topic-{i % 3} with unique marker m{i}."
        )
    return tmp_path


def _start_server(corpus_dir, **server_kwargs):
    docs = pw.io.fs.read(
        corpus_dir, format="binary", mode="streaming", with_metadata=True,
        refresh_interval=0.2,
    )
    vs = VectorStoreServer(docs, embedder=mocks.FakeEmbedder(dim=8))
    port = _free_port()
    vs.run_server(
        host="127.0.0.1", port=port, threaded=True, with_cache=False,
        **server_kwargs,
    )
    return vs, VectorStoreClient(host="127.0.0.1", port=port)


def test_fused_embed_search_parity_with_engine_path(corpus_dir):
    """The scheduler's fused tick must return exactly what the two-stage
    engine-routed path returns (recall parity, scores included)."""
    probe = "Document 2 about topic-2 with unique marker m2."
    _, engine_client = _start_server(corpus_dir, with_scheduler=False)
    engine_res = _wait_http(lambda: engine_client.query(probe, k=3))
    assert engine_res and engine_res[0]["text"] == probe

    from pathway_tpu.runtime import get_runtime

    def interactive_done():
        return get_runtime().stats()["classes"]["interactive"]["completed_total"]

    pw.global_graph.clear()  # second server: its own graph, same corpus
    done_before = interactive_done()
    _, sched_client = _start_server(corpus_dir, with_scheduler=True)
    sched_res = _wait_http(lambda: sched_client.query(probe, k=3))

    assert [r["text"] for r in sched_res] == [r["text"] for r in engine_res]
    for a, b in zip(sched_res, engine_res):
        assert a["dist"] == pytest.approx(b["dist"], abs=1e-6)
        assert a["metadata"].get("path") == b["metadata"].get("path")
    # the served query ran on the runtime: interactive work moved
    assert interactive_done() >= done_before + 1


def test_http_deadline_zero_sheds_with_503_retry_after(corpus_dir):
    """A request whose deadline already passed gets a fast 503 with a
    Retry-After hint instead of queueing."""
    _, client = _start_server(corpus_dir, with_scheduler=True)
    probe = "Document 0 about topic-0 with unique marker m0."
    _wait_http(lambda: client.query(probe, k=1))  # serving and warm

    req = urllib.request.Request(
        client.url + "/v1/retrieve",
        data=json.dumps({"query": probe, "k": 1, "deadline_ms": 0}).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(req, timeout=10)
    assert err.value.code == 503
    assert float(err.value.headers["Retry-After"]) > 0


def test_client_honors_retry_after_once():
    """VectorStoreClient(retry_on_unavailable=True) sleeps out the 503's
    Retry-After and retries exactly once."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    hits = []

    class Handler(BaseHTTPRequestHandler):
        def do_POST(self):  # noqa: N802 — stdlib API
            self.rfile.read(int(self.headers.get("Content-Length", 0)))
            hits.append(time.monotonic())
            if len(hits) == 1:
                self.send_response(503)
                self.send_header("Retry-After", "0.05")
                self.end_headers()
                return
            body = json.dumps([{"ok": True}]).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):
            pass

    server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        port = server.server_address[1]
        client = VectorStoreClient(
            host="127.0.0.1", port=port, retry_on_unavailable=True,
            max_retry_after_s=1.0,
        )
        assert client.query("q") == [{"ok": True}]
        assert len(hits) == 2
        assert hits[1] - hits[0] >= 0.05

        # off by default: the 503 surfaces to the caller
        hits.clear()
        bare = VectorStoreClient(host="127.0.0.1", port=port)
        with pytest.raises(urllib.error.HTTPError):
            bare.query("q")
        assert len(hits) == 1
    finally:
        server.shutdown()


def test_scheduler_metrics_on_openmetrics_endpoint():
    """Scheduler counters render on the monitoring /status endpoint."""
    from pathway_tpu.internals.monitoring import (
        StatsMonitor,
        start_http_server_thread,
    )

    sched = ServingScheduler(max_wait_ms=5, name="t-metrics")
    group = WorkGroup("echo", lambda xs: xs)
    assert sched.submit(group, 1).result(timeout=5) == 1

    monitor = StatsMonitor()
    snap = monitor.snapshot()
    assert snap["providers"]["t-metrics"]["submitted_total"] == 1

    server = start_http_server_thread(monitor, port=_free_port())
    try:
        port = server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/status", timeout=5
        ).read().decode()
    finally:
        server.shutdown()
    assert 'pathway_scheduler_submitted_total{scheduler="t-metrics"} 1' in body
    assert 'pathway_scheduler_batches_total{scheduler="t-metrics"} 1' in body
    assert 'pathway_scheduler_wait_ms_bucket{scheduler="t-metrics",le="+Inf"} 1' in body


@pytest.mark.slow
def test_concurrent_http_load_forms_multi_request_batches(corpus_dir):
    """8 concurrent REST clients must coalesce into >1-occupancy device
    batches on the shared scheduler (the tentpole's throughput claim)."""
    _, client = _start_server(corpus_dir, with_scheduler=True)
    probe = "Document 0 about topic-0 with unique marker m0."
    _wait_http(lambda: client.query(probe, k=1))
    before = get_scheduler().stats()

    n, per = 8, 5
    barrier = threading.Barrier(n)
    errors = []

    def worker(wid):
        barrier.wait()
        for i in range(per):
            try:
                res = client.query(f"Document {i % 6} about topic-{i % 3} "
                                   f"with unique marker m{i % 6}.", k=3)
                assert res
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

    threads = [threading.Thread(target=worker, args=(w,)) for w in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    after = get_scheduler().stats()
    assert after["completed_total"] - before["completed_total"] >= n * per
    assert after["batch_occupancy_max"] > 1, (
        "concurrent load never coalesced into a multi-request batch"
    )
