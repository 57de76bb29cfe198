"""Unified device-tick runtime tests (ISSUE 7): QoS policy (interactive
preempts a saturating bulk backlog at tick granularity, the starvation
bound keeps ingest progressing under sustained interactive load),
per-class admission control with Retry-After, inline re-entrant submits
without class inversion, tick-budget composition, bounded upsert
slicing, and runtime observability on /status and /v1/health."""

import socket
import threading
import time
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.runtime import (
    AdmissionRefused,
    DeadlineExceeded,
    DeviceTickRuntime,
    QoS,
    WorkGroup,
    get_runtime,
)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# QoS policy
# ---------------------------------------------------------------------------


def test_interactive_preempts_saturating_bulk_backlog():
    """With a bulk-ingest backlog queued deep, an interactive item
    arriving later must ride one of the very next ticks — ahead of the
    still-queued bulk chunks (preemption at tick granularity)."""
    rt = DeviceTickRuntime(
        tick_tokens=100, max_wait_ms=5, name="t-preempt"
    )
    order = []

    def record(xs):
        order.extend(xs)
        time.sleep(0.01)  # one tick ≈ 10 ms of "device" work
        return xs

    bulk = WorkGroup("bulk", record, max_batch=1)
    inter = WorkGroup("inter", record, max_batch=8)
    futs = [
        rt.submit(bulk, ("b", i), qos=QoS.BULK_INGEST, tokens=90,
                  coalesce_s=0.0)
        for i in range(20)
    ]
    # let the backlog start draining, then preempt
    time.sleep(0.035)
    fi = rt.submit(inter, ("q", 0), tokens=10)
    fi.result(timeout=30)
    for f in futs:
        f.result(timeout=30)
    pos = order.index(("q", 0))
    # the query executed while most of the backlog was still queued —
    # it waited at most a few in-flight ticks, never the whole backlog
    assert pos <= 8, f"interactive ran at position {pos} of {len(order)}"
    assert rt.stats()["preemptions_total"] >= 1


def test_starvation_bound_guarantees_bulk_progress():
    """Sustained interactive load must not starve a queued bulk backlog:
    every contended tick grants bulk its min-share (≥ 1 item), so the
    backlog finishes interleaved with — not after — the query flood.
    With min_share=0 the same flood starves bulk completely (the knob
    is load-bearing)."""

    def run_flood(min_share: float):
        rt = DeviceTickRuntime(
            tick_tokens=100,
            max_wait_ms=1,
            name=f"t-share-{min_share}",
            min_share={QoS.BULK_INGEST: min_share},
        )
        order = []

        def record(xs):
            order.extend(xs)
            return xs

        inter = WorkGroup("inter", record, max_batch=64)
        bulk = WorkGroup("bulk", record, max_batch=64)
        gate = threading.Event()

        def blocker(xs):
            gate.wait(10)
            return xs

        # hold the tick thread so both queues fill before composition
        held = rt.submit(WorkGroup("gate", blocker), 0)
        time.sleep(0.05)
        # tokens=tick_tokens → exactly one interactive item per tick
        ifuts = [
            rt.submit(inter, ("q", i), tokens=100) for i in range(30)
        ]
        bfuts = [
            rt.submit(bulk, ("b", i), qos=QoS.BULK_INGEST, tokens=5,
                      coalesce_s=0.0)
            for i in range(10)
        ]
        gate.set()
        held.result(10)
        for f in ifuts + bfuts:
            f.result(timeout=30)
        return order, rt.stats()

    order, stats = run_flood(0.1)
    last_bulk = max(order.index(("b", i)) for i in range(10))
    # all 10 bulk items ran before the flood's tail: ≥1 per contended
    # tick means the backlog clears within ~10 interactive ticks
    assert last_bulk < order.index(("q", 25)), order
    assert stats["bulk_share_mean"] is not None and stats["bulk_share_mean"] > 0

    order0, _ = run_flood(0.0)
    # no reservation → strict priority starves bulk until the flood ends
    first_bulk = min(order0.index(("b", i)) for i in range(10))
    assert first_bulk > order0.index(("q", 29)), order0


def test_per_class_admission_rejects_with_retry_after():
    """Sheddable submissions beyond a class's queue-depth target are
    refused immediately with the configured Retry-After; engine-plane
    (unsheddable) work is exempt."""
    rt = DeviceTickRuntime(
        tick_tokens=1000, max_wait_ms=1, retry_after_s=0.7,
        depth={QoS.INTERACTIVE: 2}, name="t-admit",
    )
    release = threading.Event()
    started = threading.Event()

    def blocking(xs):
        started.set()
        release.wait(10)
        return xs

    blocker = WorkGroup("block", blocking)
    fast = WorkGroup("fast", lambda xs: xs)
    held = rt.submit(blocker, 0)
    assert started.wait(5), "runtime loop never picked up the blocker"
    q1 = rt.submit(fast, 1, deadline_s=30)
    q2 = rt.submit(fast, 2, deadline_s=30)
    with pytest.raises(AdmissionRefused) as err:
        rt.submit(fast, 3, deadline_s=30).result(timeout=5)
    assert err.value.retry_after_s == 0.7
    # a different class is not at ITS target: still admitted
    ok_other = rt.submit(fast, 5, qos=QoS.LLM_RERANK, deadline_s=30)
    # unsheddable work is never refused
    exempt = rt.submit(fast, 4)
    stats = rt.stats()
    assert stats["classes"]["interactive"]["admission_rejected_total"] == 1
    assert stats["classes"]["llm_rerank"]["admission_rejected_total"] == 0
    release.set()
    assert held.result(5) == 0 and q1.result(5) == 1 and q2.result(5) == 2
    assert exempt.result(5) == 4 and ok_other.result(5) == 5


def test_inline_submit_inherits_tick_class_no_inversion():
    """A re-entrant LLM_RERANK submit from inside an INTERACTIVE tick
    executes inline under the tick's budget: it never enters the
    llm_rerank queue (no queue jump) and its completion is accounted to
    the interactive tick it rode."""
    rt = DeviceTickRuntime(tick_tokens=1000, max_wait_ms=1, name="t-inline")
    inner = WorkGroup("inner", lambda xs: [x + 100 for x in xs])
    inline_done_inside_handler = []

    def outer_fn(xs):
        fut = rt.submit(inner, 5, qos=QoS.LLM_RERANK)
        # inline execution: the result is already available IN the tick
        inline_done_inside_handler.append(fut.done())
        return [fut.result(timeout=0) + x for x in xs]

    outer = WorkGroup("outer", outer_fn)
    assert rt.submit(outer, 1).result(timeout=10) == 106
    assert inline_done_inside_handler == [True]
    stats = rt.stats()
    llm = stats["classes"]["llm_rerank"]
    assert llm["inline_total"] == 1
    assert llm["queue_depth_max"] == 0, "inline submit entered the queue"
    assert llm["completed_total"] == 0, (
        "inline work was accounted to llm_rerank instead of the "
        "running interactive tick"
    )
    assert stats["classes"]["interactive"]["completed_total"] == 2


def test_tick_budget_composition_strict_priority_with_reservation():
    """One composed tick under budget 100 with bulk pending: interactive
    fills up to 100 − reserved(10), bulk gets its guaranteed ≥1 item in
    the SAME tick, the rest stays queued for later ticks."""
    rt = DeviceTickRuntime(
        tick_tokens=100, max_wait_ms=1, name="t-compose",
        min_share={QoS.BULK_INGEST: 0.1},
    )
    calls: list[tuple[str, int]] = []

    def make(label):
        def fn(xs):
            calls.append((label, len(xs)))
            return xs

        return WorkGroup(label, fn, max_batch=64)

    inter, bulk = make("inter"), make("bulk")
    gate = threading.Event()
    held = rt.submit(WorkGroup("gate", lambda xs: (gate.wait(10), xs)[1]), 0)
    time.sleep(0.05)
    ifuts = [rt.submit(inter, i, tokens=30) for i in range(5)]
    bfuts = [
        rt.submit(bulk, i, qos=QoS.BULK_INGEST, tokens=10, coalesce_s=0.0)
        for i in range(4)
    ]
    gate.set()
    held.result(10)
    for f in ifuts + bfuts:
        f.result(timeout=10)
    # first contended tick: 3×30 interactive (90 ≤ 100−10 reserved) + the
    # one 10-token bulk item the reservation admits; leftovers drain in
    # later ticks
    first_inter = next(c for c in calls if c[0] == "inter")
    first_bulk = next(c for c in calls if c[0] == "bulk")
    assert first_inter == ("inter", 3), calls
    assert first_bulk == ("bulk", 1), calls
    assert sum(n for l, n in calls if l == "inter") == 5
    assert sum(n for l, n in calls if l == "bulk") == 4


# ---------------------------------------------------------------------------
# tick-granularity upsert slicing (ops/knn.py)
# ---------------------------------------------------------------------------


def test_apply_staged_budget_incremental_parity():
    """apply_staged_budget drains staged device scatters in bounded
    doses without changing what a search eventually sees, and never
    over-applies its budget."""
    from pathway_tpu.ops.knn import DeviceKnnIndex

    rng = np.random.default_rng(1)
    vecs = rng.normal(size=(12, 8)).astype(np.float32)
    inc = DeviceKnnIndex(dim=8, capacity=32)
    allatonce = DeviceKnnIndex(dim=8, capacity=32)
    for j in range(0, 12, 2):  # six staged device batches of 2 rows
        keys = [f"k{j}", f"k{j + 1}"]
        inc.upsert_batch(keys, jnp.asarray(vecs[j : j + 2]))
        allatonce.upsert_batch(keys, jnp.asarray(vecs[j : j + 2]))
    assert inc.apply_staged_budget(2) == 2
    assert len(inc._staged_device) == 4
    assert inc.apply_staged_budget(100) == 4  # drains the rest
    assert inc._staged_device == []
    assert inc.apply_staged_budget(2) == 0  # idempotent when drained
    q = rng.normal(size=(2, 8)).astype(np.float32)
    for row_i, row_a in zip(inc.search(q, 4), allatonce.search(q, 4)):
        assert [k for k, _ in row_i] == [k for k, _ in row_a]
        np.testing.assert_allclose(
            [s for _, s in row_i], [s for _, s in row_a], atol=1e-6
        )


def test_upsert_batch_slices_jumbo_device_batches():
    """A jumbo device batch stages as bounded slices (each scatter stays
    a bounded dispatch) and search results match row-by-row host staging."""
    from pathway_tpu.ops.knn import DeviceKnnIndex, upsert_slice_rows

    step = upsert_slice_rows()
    n = step * 2 + 100
    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(n, 8)).astype(np.float32)
    dev = DeviceKnnIndex(dim=8, capacity=4096)
    dev.upsert_batch([f"k{i}" for i in range(n)], jnp.asarray(vecs))
    assert len(dev._staged_device) == 3
    assert all(v.shape[0] <= step for _s, v in dev._staged_device)
    host = DeviceKnnIndex(dim=8, capacity=4096)
    for i in range(n):
        host.upsert(f"k{i}", vecs[i])
    q = rng.normal(size=(2, 8)).astype(np.float32)
    for row_h, row_d in zip(host.search(q, 5), dev.search(q, 5)):
        assert [k for k, _ in row_h] == [k for k, _ in row_d]
        np.testing.assert_allclose(
            [s for _, s in row_h], [s for _, s in row_d], atol=1e-5
        )


# ---------------------------------------------------------------------------
# observability: /status series + /v1/health state
# ---------------------------------------------------------------------------


def test_runtime_metrics_on_status_and_health():
    """Per-class runtime state renders as pathway_runtime_* on /status
    and rides the /v1/health snapshot."""
    from pathway_tpu.internals.health import get_health
    from pathway_tpu.internals.monitoring import (
        StatsMonitor,
        start_http_server_thread,
    )

    rt = get_runtime()
    group = WorkGroup("echo", lambda xs: xs)
    assert rt.submit(group, 1).result(timeout=5) == 1
    assert rt.submit(group, 2, qos=QoS.BULK_INGEST).result(timeout=5) == 2

    monitor = StatsMonitor()
    snap = monitor.snapshot()
    assert "runtime" in snap["providers"]
    classes = snap["providers"]["runtime"]["classes"]
    assert classes["interactive"]["completed_total"] >= 1
    assert classes["bulk_ingest"]["completed_total"] >= 1

    server = start_http_server_thread(monitor, port=_free_port())
    try:
        port = server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/status", timeout=5
        ).read().decode()
    finally:
        server.shutdown()
    assert 'pathway_runtime_submitted_total{qos="interactive"}' in body
    assert 'pathway_runtime_queue_depth{qos="bulk_ingest"}' in body
    assert "pathway_runtime_ticks_total" in body
    assert 'pathway_runtime_wait_ms_bucket{qos="interactive",le="+Inf"}' in body

    health = get_health().snapshot()
    assert "runtime" in health
    assert "interactive" in health["runtime"]["classes"]
    assert health["runtime"]["min_share"]["bulk_ingest"] >= 0


def test_runtime_deadline_shed_contract():
    """Deadline shedding through the runtime keeps the serving contract:
    work never executes and DeadlineExceeded carries the hint."""
    rt = DeviceTickRuntime(
        tick_tokens=100, max_wait_ms=60, retry_after_s=0.4, name="t-shed-rt"
    )
    executed = []
    group = WorkGroup("rec", lambda xs: (executed.extend(xs), xs)[1])
    fut = rt.submit(group, "doomed", deadline_s=0.005, coalesce_s=0.06)
    with pytest.raises(DeadlineExceeded) as err:
        fut.result(timeout=5)
    assert err.value.retry_after_s == 0.4
    assert executed == []
    assert rt.stats()["classes"]["interactive"]["shed_deadline_total"] == 1
