"""Paged-KV continuous-batching decode (ISSUE 14, ROADMAP item 3).

The acceptance contract: paged continuous-batching decode is
token-for-token identical to the dense ``lax.scan`` oracle (greedy, f32
and bf16, mixed prompt lengths, mid-stream admit/retire, block reuse
after free has no ghost attention), the dense decoder's compile set is
flat across request-level ``max_new_tokens``, decode rides the runtime
as GENERATE-class work without unbounding INTERACTIVE latency, and the
serving plane streams TPU-native answers over live HTTP.
"""

import json
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.generation import (
    BlockAllocator,
    DecodeSession,
    PagedDecoder,
    PagedKVPool,
    paged_decode_attention,
    validate_decoder_geometry,
)
from pathway_tpu.generation.engine import generation_status
from pathway_tpu.models.decoder import CausalLM, DecoderConfig

TINY = DecoderConfig(
    vocab_size=211, hidden_dim=64, num_layers=2, num_heads=4, mlp_dim=128,
    max_len=128, dtype=jnp.float32,
)
TINY_BF16 = DecoderConfig(
    vocab_size=211, hidden_dim=64, num_layers=2, num_heads=4, mlp_dim=128,
    max_len=128, dtype=jnp.bfloat16,
)

_LMS: dict = {}


def _lm(cfg=TINY) -> CausalLM:
    """One CausalLM per config for the whole module (compiles are the
    expensive part of every test here)."""
    key = (cfg.dtype.__name__, cfg.hidden_dim)
    if key not in _LMS:
        _LMS[key] = CausalLM(cfg=cfg, seed=3)
    return _LMS[key]


def _session(cfg=TINY, **kw) -> DecodeSession:
    kw.setdefault("auto", False)
    kw.setdefault("pool_tokens", 2048)
    kw.setdefault("block_size", 16)
    return DecodeSession(cfg, _lm(cfg).params, **kw)


MIXED_PROMPTS = [
    [5, 9, 17, 4],
    [8, 3],
    [11, 12, 13, 14, 15, 16, 17],
    list(range(40, 63)),
]


# ---------------------------------------------------------------------------
# allocator / pool units
# ---------------------------------------------------------------------------


def test_block_allocator_fifo_reuse_and_bounds():
    a = BlockAllocator(4)
    first = a.alloc(3)
    assert first == [0, 1, 2] and a.free_count == 1
    assert a.alloc(2) is None  # over capacity: caller keeps it queued
    a.free(first)
    # FIFO: the freed blocks come back in the order they were freed
    got = a.alloc(4)
    assert got == [3, 0, 1, 2]
    with pytest.raises(ValueError):
        a.free([99])  # foreign id
    a.free([3])
    with pytest.raises(ValueError, match="double free"):
        a.free([3])  # already back at refcount 0
    with pytest.raises(ValueError, match="duplicate"):
        a.free([0, 0])  # duplicate ids in one call
    # failed frees must not have corrupted state: 0..2 still held once
    assert a.free_count == 1 and a.used_count == 3


def test_pool_geometry_and_hbm_bytes():
    pool = PagedKVPool(TINY, block_size=16, pool_tokens=2048)
    assert pool.num_blocks == 128
    assert pool.blocks_per_seq == 8  # ceil(max_len / block_size)
    assert pool.blocks_for(1) == 1 and pool.blocks_for(17) == 2
    # [L, NB, bs, H, Dh] * 2 pools * 4 bytes
    want = 2 * 2 * 128 * 16 * 4 * 16 * 4
    assert pool.hbm_bytes() == want


# ---------------------------------------------------------------------------
# decode-step kernel unit: pallas(interpret) vs XLA reference
# ---------------------------------------------------------------------------


def test_paged_attention_kernel_matches_reference():
    rng = np.random.default_rng(0)
    L, NB, bs, H, Dh = 2, 12, 8, 4, 16
    rows, W = 3, 4
    k_pool = jnp.asarray(rng.normal(size=(L, NB, bs, H, Dh)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(L, NB, bs, H, Dh)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(rows, H, Dh)), jnp.float32)
    bt = jnp.asarray(rng.permutation(NB)[: rows * W].reshape(rows, W), jnp.int32)
    lengths = jnp.asarray([5, 8 * W, 13], jnp.int32)  # mixed, incl. full
    for layer in range(L):
        ref = paged_decode_attention(
            q, k_pool, v_pool, bt, lengths, layer, block_size=bs,
            mode="reference",
        )
        pal = paged_decode_attention(
            q, k_pool, v_pool, bt, lengths, layer, block_size=bs,
            mode="pallas",
        )
        np.testing.assert_allclose(
            np.asarray(pal), np.asarray(ref), atol=2e-5, rtol=2e-5
        )


def test_paged_attention_inactive_row_emits_zeros():
    rng = np.random.default_rng(1)
    k_pool = jnp.asarray(rng.normal(size=(1, 4, 8, 2, 16)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(1, 4, 8, 2, 16)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(2, 2, 16)), jnp.float32)
    bt = jnp.zeros((2, 2), jnp.int32)
    out = paged_decode_attention(
        q, k_pool, v_pool, bt, jnp.asarray([0, 7]), 0, block_size=8,
        mode="pallas",
    )
    # a retired/pad row (length 0) must contribute exact zeros, not a
    # uniform softmax over garbage
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)
    assert float(jnp.abs(out[1]).sum()) > 0.0


def test_validate_decoder_geometry_names_the_knob():
    validate_decoder_geometry(16)   # divides 128
    validate_decoder_geometry(256)  # multiple of 128
    with pytest.raises(ValueError, match="PATHWAY_DECODE_KERNEL"):
        validate_decoder_geometry(48, knob="PATHWAY_DECODE_KERNEL=pallas")
    # the session applies the check up front in pallas mode
    bad = DecoderConfig(
        vocab_size=64, hidden_dim=96, num_layers=1, num_heads=2, mlp_dim=64,
        max_len=64,
    )  # head_dim 48: neither divides nor is a multiple of 128
    lm = CausalLM(cfg=bad, seed=0)
    with pytest.raises(ValueError, match="head_dim"):
        DecodeSession(bad, lm.params, mode="pallas", auto=False)


def test_decode_kernel_env_knob_garbage_warns_to_auto(monkeypatch):
    from pathway_tpu.generation.decode_kernel import decode_kernel_mode

    monkeypatch.setenv("PATHWAY_DECODE_KERNEL", "banana")
    with pytest.warns(UserWarning, match="PATHWAY_DECODE_KERNEL"):
        assert decode_kernel_mode() == "auto"
    monkeypatch.setenv("PATHWAY_DECODE_KERNEL", "reference")
    assert decode_kernel_mode() == "reference"


# ---------------------------------------------------------------------------
# paged-vs-dense token parity (the acceptance pin)
# ---------------------------------------------------------------------------


def test_paged_greedy_parity_mixed_lengths_f32():
    lm = _lm()
    dense = lm.generate_ids(MIXED_PROMPTS, max_new_tokens=12)
    pd = PagedDecoder(TINY, lm.params, pool_tokens=2048, block_size=16)
    paged = pd.generate_ids(MIXED_PROMPTS, max_new_tokens=12)
    for i in range(len(MIXED_PROMPTS)):
        assert dense[i].tolist() == paged[i], i


def test_paged_greedy_parity_pallas_interpret_mode():
    """tier-1 exercises the REAL kernel body (interpret mode on CPU)."""
    lm = _lm()
    dense = lm.generate_ids(MIXED_PROMPTS[:2], max_new_tokens=8)
    pd = PagedDecoder(
        TINY, lm.params, pool_tokens=2048, block_size=16, mode="pallas"
    )
    paged = pd.generate_ids(MIXED_PROMPTS[:2], max_new_tokens=8)
    for i in range(2):
        assert dense[i].tolist() == paged[i], i


def test_paged_greedy_parity_bf16():
    lm = _lm(TINY_BF16)
    dense = lm.generate_ids(MIXED_PROMPTS[:3], max_new_tokens=10)
    pd = PagedDecoder(TINY_BF16, lm.params, pool_tokens=2048, block_size=16)
    paged = pd.generate_ids(MIXED_PROMPTS[:3], max_new_tokens=10)
    for i in range(3):
        assert dense[i].tolist() == paged[i], i


def test_midstream_admit_and_retire_parity():
    """A sequence admitted while others are mid-decode, and one retiring
    early, must not perturb anyone's tokens — each matches its own
    dense oracle regardless of batch composition (pow2 row buckets make
    the launch shape flat; masking makes the content independent)."""
    lm = _lm()
    s = _session()
    ha = s.submit(MIXED_PROMPTS[0], max_new_tokens=10)
    hb = s.submit(MIXED_PROMPTS[1], max_new_tokens=3)  # retires early
    for _ in range(4):
        s.tick()
    assert hb.done and not ha.done
    hc = s.submit(MIXED_PROMPTS[2], max_new_tokens=8)  # admitted mid-stream
    s.drain()
    assert ha.result() == lm.generate_ids([MIXED_PROMPTS[0]], 10)[0].tolist()
    assert hb.result() == lm.generate_ids([MIXED_PROMPTS[1]], 3)[0].tolist()
    assert hc.result() == lm.generate_ids([MIXED_PROMPTS[2]], 8)[0].tolist()
    assert s.stats()["kv_blocks_used"] == 0


def test_block_reuse_after_free_has_no_ghost_attention():
    """Blocks are reused VERBATIM (no zeroing): a second wave landing on
    the first wave's freed blocks must still match the dense oracle —
    stale tail data is structurally unreachable through the length
    mask."""
    lm = _lm()
    # small pool: wave 2 MUST land on wave-1 blocks
    s = _session(pool_tokens=512, block_size=16)  # 32 blocks
    wave1 = [list(range(30, 50)), list(range(60, 80))]
    handles = [s.submit(p, max_new_tokens=8) for p in wave1]
    s.drain()
    for h, p in zip(handles, wave1):
        assert h.result() == lm.generate_ids([p], 8)[0].tolist()
    used_before = s.pool.allocator.used_count
    assert used_before == 0  # all freed
    wave2 = [list(range(100, 117)), [7, 5, 3], list(range(140, 170))]
    handles = [s.submit(p, max_new_tokens=8) for p in wave2]
    s.drain()
    for h, p in zip(handles, wave2):
        assert h.result() == lm.generate_ids([p], 8)[0].tolist()
    assert s.stats()["kv_blocks_used"] == 0


def test_extend_resumes_from_live_kv_blocks():
    """The adaptive-RAG re-ask path: a retained sequence continues from
    its LIVE paged blocks — extension tokens ride decode steps, the
    original prompt is never re-prefilled, and the continuation matches
    a dense oracle over the full concatenated sequence."""
    lm = _lm()
    s = _session()
    before = generation_status()["prefill_tokens_total"]
    prompt = [11, 12, 13]
    h = s.submit(prompt, max_new_tokens=6, retain=True)
    s.drain()
    g1 = h.result()
    assert s.stats()["retained"] == 1
    h2 = s.extend(h, [20, 21], max_new_tokens=5)
    s.drain()
    g2 = h2.result()
    oracle = lm.generate_ids([prompt + g1 + [20, 21]], 5)[0].tolist()
    assert g2 == oracle
    # prefill ran ONCE, for the original prompt only
    after = generation_status()["prefill_tokens_total"]
    assert after - before == len(prompt)
    s.release(h2)
    assert s.stats()["kv_blocks_used"] == 0
    with pytest.raises(ValueError, match="retain=True"):
        s.extend(h2, [1], max_new_tokens=2)


def test_cancel_frees_blocks_in_every_state():
    """cancel() is the abandoned-stream path: queued, live and retained
    sequences all release their blocks (a disconnecting client must not
    park retain=True blocks forever)."""
    s = _session()
    h = s.submit([1, 2, 3], max_new_tokens=20, retain=True)
    s.tick()
    assert s.stats()["kv_blocks_used"] > 0 and not h.done
    s.cancel(h)  # live
    assert h.done and s.stats()["kv_blocks_used"] == 0
    h2 = s.submit([1, 2, 3], max_new_tokens=4)
    s.cancel(h2)  # still queued
    assert h2.done and s.stats()["pending"] == 0
    h3 = s.submit([4, 5, 6], max_new_tokens=3, retain=True)
    s.drain()
    assert s.stats()["retained"] == 1
    s.cancel(h3)  # retained
    assert s.stats()["retained"] == 0 and s.stats()["kv_blocks_used"] == 0
    s.cancel(h3)  # idempotent on a forgotten handle


def test_abandoned_adaptive_stream_frees_retained_blocks():
    """Closing the rounds generator mid-round (client disconnect) must
    cancel the retained sequence — its KV blocks return to the pool."""
    from pathway_tpu.xpacks.llm.llms import JaxPipelineChat
    from pathway_tpu.xpacks.llm.question_answering import (
        AdaptiveRAGQuestionAnswerer,
    )

    lm = _lm()
    chat = JaxPipelineChat(model=None, causal_lm=lm)
    qa = AdaptiveRAGQuestionAnswerer(
        llm=chat, indexer=None, n_starting_documents=1, max_iterations=2
    )
    it = qa._stream_rounds(
        lm, "what is beta?", ["doc text"], max_new_tokens=8,
        temperature=0.0, seed=0, deadline_s=None,
    )
    first = next(it)  # round 0 streaming — the retained sequence is live
    assert first[0] == "token"
    it.close()  # disconnect
    sess = lm.paged_session()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        st = sess.stats()
        if (
            st["retained"] == 0 and st["live_sequences"] == 0
            and st["kv_blocks_used"] == 0
        ):
            break
        time.sleep(0.05)
    st = sess.stats()
    assert st["retained"] == 0 and st["kv_blocks_used"] == 0, st


def test_streaming_callback_delivers_tokens_in_order():
    lm = _lm()
    s = _session()
    seen: list[int] = []
    h = s.submit(
        MIXED_PROMPTS[0], max_new_tokens=6, stream_cb=seen.append
    )
    s.drain()
    assert seen == h.result()
    # a late consumer still receives the full ordered stream
    assert list(h.stream()) == seen


def test_sampled_decode_deterministic_per_seed_and_batch_independent():
    """Sampling keys fold (seq seed, step count): the draw for one
    request is deterministic and independent of WHO ELSE shares its
    ticks."""
    s = _session()
    a = s.submit([5, 6, 7], max_new_tokens=6, temperature=0.9, seed=4)
    s.drain()
    s2 = _session()
    b = s2.submit([5, 6, 7], max_new_tokens=6, temperature=0.9, seed=4)
    s2.submit([9, 9, 9, 9], max_new_tokens=6, temperature=0.5, seed=1)
    s2.drain()
    assert a.result() == b.result()


# ---------------------------------------------------------------------------
# admission control / shedding
# ---------------------------------------------------------------------------


def test_admission_refused_when_request_can_never_fit():
    from pathway_tpu.runtime import AdmissionRefused

    s = _session(pool_tokens=64, block_size=16)  # 4 blocks
    with pytest.raises(AdmissionRefused, match="PATHWAY_DECODE_POOL_TOKENS"):
        s.submit(list(range(70)), max_new_tokens=32)


def test_submit_refuses_prompt_beyond_packed_prefill_cap():
    """An over-cap prompt must be refused at submit — admitted, it would
    blow up inside tick() and fail EVERY in-flight sequence with it."""
    import dataclasses

    from pathway_tpu.ops.ragged_attention import MAX_PACKED_TOKENS
    from pathway_tpu.runtime import AdmissionRefused

    big = dataclasses.replace(TINY, max_len=MAX_PACKED_TOKENS + 2048)
    s = DecodeSession(
        big, _lm().params, auto=False, pool_tokens=2048, block_size=16
    )
    with pytest.raises(AdmissionRefused, match="packed prefill"):
        s.submit(list(range(MAX_PACKED_TOKENS + 100)), max_new_tokens=8)


def test_submit_refuses_max_new_beyond_max_len():
    """max_new_tokens past max_len can NEVER fit the per-sequence block
    table (blocks_per_seq entries) — admitted, the decode tick's
    block-table row would overflow and _fail_all every in-flight
    sequence."""
    from pathway_tpu.runtime import AdmissionRefused

    s = _session()  # TINY: max_len=128
    with pytest.raises(AdmissionRefused, match="max_len"):
        s.submit([1, 2, 3], max_new_tokens=TINY.max_len + 1)
    # at exactly max_len the (tail-trimmed) request still fits
    h = s.submit([1, 2, 3], max_new_tokens=TINY.max_len)
    s.cancel(h)
    assert s.stats()["kv_blocks_used"] == 0


def test_prefill_failure_fails_admitted_batch_without_leaking_blocks():
    """A failed prefill launch must fail the admitted batch's waiters
    and free its blocks — those sequences are in neither _live nor
    _pending, so _fail_all alone would miss them (hung clients + a
    permanently shrunken pool).  Under the ISSUE 18 containment
    contract the failure is contained to the launch: tick() itself no
    longer raises, and the session keeps serving."""
    s = _session()

    def exploding(batch, tokens=None, replay=False):
        raise RuntimeError("synthetic prefill failure")

    s._prefill_batch_locked = exploding
    h = s.submit([1, 2, 3, 4], max_new_tokens=4)
    s.tick()  # contained: the tick survives the launch failure
    assert h.done
    with pytest.raises(RuntimeError, match="synthetic prefill"):
        h.result(timeout=1)
    assert s.stats()["kv_blocks_used"] == 0  # blocks back in the pool
    assert s.stats()["pending"] == 0 and s.stats()["live_sequences"] == 0


def test_stream_plane_build_failure_is_retryable():
    """One transient plane-build failure must NOT latch the tried flag
    into a permanent 501 — the next request retries and succeeds."""
    from pathway_tpu.xpacks.llm.question_answering import (
        BaseRAGQuestionAnswerer,
    )

    calls = {"n": 0}
    sentinel = object()

    def plane_factory():
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient embedder load hiccup")
        return sentinel

    class _Indexer:
        scheduler_retrieve_plane = staticmethod(plane_factory)

    class _Stub:
        indexer = _Indexer()
        _stream_retrieve_plane = BaseRAGQuestionAnswerer._stream_retrieve_plane
        _stream_retrieve_plane_locked = (
            BaseRAGQuestionAnswerer._stream_retrieve_plane_locked
        )

    qa = _Stub()
    assert qa._stream_retrieve_plane() is None  # build failed: NOT latched
    assert qa._stream_retrieve_plane() is sentinel  # retry succeeds
    assert qa._stream_retrieve_plane() is sentinel  # now cached
    assert calls["n"] == 2


def test_generate_stream_abandoned_iterator_cancels():
    """Breaking out of CausalLM.generate_stream's paged iterator must
    stop the sequence — no orphan burning GENERATE ticks to max_new."""
    lm = _lm()
    it = lm.generate_stream("hello world paging", max_new_tokens=40)
    first = next(it)
    assert isinstance(first, str) and first
    it.close()
    sess = lm.paged_session()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        st = sess.stats()
        if st["live_sequences"] == 0 and st["kv_blocks_used"] == 0:
            break
        time.sleep(0.05)
    st = sess.stats()
    assert st["live_sequences"] == 0 and st["kv_blocks_used"] == 0, st


def test_generate_stream_falls_back_to_dense_on_permanent_refusal():
    """A pool that can NEVER hold the request (retry_after_s == 0) falls
    back to the dense chunked path in auto mode — the docstring
    contract; paged=True keeps raising, and transient backpressure is
    never absorbed (admission control stays visible to serving)."""
    from pathway_tpu.runtime import AdmissionRefused

    lm = CausalLM(cfg=TINY, seed=7)
    sess = lm.paged_session(pool_tokens=32, block_size=16, auto=False)
    assert sess.pool.num_blocks == 2
    prompt = "a long prompt that can never fit such a tiny paged pool"
    pieces = list(lm.generate_stream(prompt, max_new_tokens=32))
    assert pieces and all(isinstance(p, str) for p in pieces)
    with pytest.raises(AdmissionRefused):
        lm.generate_stream(prompt, max_new_tokens=32, paged=True)


def test_pending_queue_depth_backpressure():
    from pathway_tpu.runtime import AdmissionRefused

    s = _session(max_pending=1)
    s.submit([1, 2, 3], max_new_tokens=4)
    with pytest.raises(AdmissionRefused, match="pending queue full"):
        s.submit([4, 5, 6], max_new_tokens=4)


def test_deadline_shedding_of_queued_requests():
    from pathway_tpu.runtime import DeadlineExceeded

    before = generation_status()["shed_total"]
    s = _session()
    h = s.submit([1, 2, 3], max_new_tokens=4, deadline_s=0.01)
    time.sleep(0.05)
    s.tick()
    assert h.done
    with pytest.raises(DeadlineExceeded):
        h.result()
    assert generation_status()["shed_total"] == before + 1
    assert s.stats()["kv_blocks_used"] == 0  # never allocated


def test_pool_exhaustion_keeps_request_queued_until_blocks_free():
    s = _session(pool_tokens=128, block_size=16)  # 8 blocks
    big = s.submit(list(range(40)), max_new_tokens=24)  # 4 blocks
    second = s.submit(list(range(50)), max_new_tokens=40)  # 6 blocks: waits
    s.tick()
    assert s.stats()["pending"] == 1  # queued, NOT failed
    s.drain(timeout=120)
    assert len(big.result()) == 24 and len(second.result()) == 40


# ---------------------------------------------------------------------------
# dense decoder: flat compile set across max_new_tokens (bugfix satellite)
# ---------------------------------------------------------------------------


def test_dense_decode_compile_flat_across_max_new_tokens():
    from pathway_tpu.internals.flight_recorder import compile_stats

    lm = _lm()
    lm.generate_ids([[1, 2, 3]], max_new_tokens=5)  # warm the bucket
    base = compile_stats()
    for mn in (3, 8, 17, 30):  # all inside one 32-step chunk horizon
        out = lm.generate_ids([[1, 2, 3]], max_new_tokens=mn)
        assert out.shape == (1, mn)
    after = compile_stats()
    assert after.get("decoder.generate", 0) == base.get("decoder.generate", 0)
    assert after.get("decoder.prefill", 0) == base.get("decoder.prefill", 0)
    # crossing a horizon boundary adds AT MOST one program per site
    # (pow2 chunk-count grid), never one per max_new value
    lm.generate_ids([[1, 2, 3]], max_new_tokens=40)
    lm.generate_ids([[1, 2, 3]], max_new_tokens=55)
    final = compile_stats()
    assert final.get("decoder.generate", 0) <= base.get("decoder.generate", 0) + 1
    assert final.get("decoder.prefill", 0) <= base.get("decoder.prefill", 0) + 1


def test_dense_decode_eos_early_exit_and_masking():
    lm = _lm()
    probe = lm.generate_ids([[1, 2, 3]], max_new_tokens=1)
    eos = int(probe[0, 0])  # greedy: the first emitted token
    out = lm.generate_ids([[1, 2, 3]], max_new_tokens=100, eos_id=eos)
    assert out.shape == (1, 100)
    # everything from the first EOS on is reported as EOS
    assert (out[0] == eos).all()


# ---------------------------------------------------------------------------
# runtime integration: GENERATE class, INTERACTIVE latency bound
# ---------------------------------------------------------------------------


def test_decode_rides_generate_class_on_runtime():
    from pathway_tpu.runtime import get_runtime

    rt = get_runtime()
    before = rt.stats()["classes"]["generate"]["completed_total"]
    s = DecodeSession(
        TINY, _lm().params, pool_tokens=2048, block_size=16,
        auto=True,
    )
    h = s.submit([4, 5, 6], max_new_tokens=5)
    assert h.result(timeout=120) == _lm().generate_ids([[4, 5, 6]], 5)[0].tolist()
    after = rt.stats()["classes"]["generate"]["completed_total"]
    assert after > before
    s.close()


def test_interactive_p99_bounded_while_decode_backlog_drains():
    """The resource-partitioning pin: a decode backlog draining as
    GENERATE-class ticks must not unbound INTERACTIVE latency — each
    probe waits at most one bounded decode step, not the backlog."""
    from pathway_tpu.runtime import QoS, WorkGroup, get_runtime

    lm = _lm()
    s = DecodeSession(
        TINY, lm.params, pool_tokens=4096, block_size=16,
        auto=True,
    )
    # warm every launch shape the backlog will use (row bucket 8)
    warm = [s.submit([i + 1, i + 2, i + 3], max_new_tokens=2) for i in range(6)]
    for h in warm:
        h.result(timeout=240)
    handles = [
        s.submit([i + 1, i + 2, i + 3], max_new_tokens=24) for i in range(6)
    ]
    rt = get_runtime()
    grp = WorkGroup("p99-probe", lambda xs: xs, max_batch=8)
    waits = []
    for i in range(30):
        t0 = time.monotonic()
        rt.submit(grp, i, qos=QoS.INTERACTIVE).result(timeout=60)
        waits.append(time.monotonic() - t0)
        time.sleep(0.004)
    for h in handles:
        h.result(timeout=240)  # starvation bound: decode still finishes
    waits.sort()
    med = waits[len(waits) // 2]
    p99 = waits[min(len(waits) - 1, int(0.99 * len(waits)))]
    # non-preemptable decode would park EVERY probe behind the whole
    # backlog (median ≈ half the multi-second drain); preemption at tick
    # granularity keeps the typical wait at ~one decode step.  The tail
    # bound is generous: under the full suite, earlier tests' threaded
    # engines keep competing for this box's CPU.
    assert med < 0.5, f"INTERACTIVE median {med:.3f}s under decode backlog"
    assert p99 < 3.0, f"INTERACTIVE p99 {p99:.3f}s under decode backlog"
    s.close()


# ---------------------------------------------------------------------------
# observability: metrics provider, /status lines, health block
# ---------------------------------------------------------------------------


def test_metrics_provider_and_health_block():
    from pathway_tpu.generation.engine import _PROVIDER

    before = generation_status()["tokens_generated_total"]
    s = _session()
    h = s.submit([2, 3, 4], max_new_tokens=5)
    s.drain()
    assert len(h.result()) == 5
    text = "\n".join(_PROVIDER.openmetrics_lines())
    assert "pathway_decode_live_sequences" in text
    assert 'pathway_decode_kv_blocks{state="free"}' in text
    assert "pathway_decode_tokens_total" in text
    assert generation_status()["tokens_generated_total"] - before == 5
    # registry lint: every emitted family is declared
    from pathway_tpu.internals.metrics_names import declared_metric_names

    allowed = declared_metric_names()
    for line in _PROVIDER.openmetrics_lines():
        if line.startswith("#"):
            continue
        name = line.split("{")[0].split(" ")[0]
        assert name in allowed, name
    # /v1/health block (module imported here, so the gate is open)
    from pathway_tpu.internals.health import get_health

    snap = get_health().snapshot()
    assert "generation" in snap
    assert snap["generation"]["sessions"] >= 1
    assert snap["generation"]["kernel_mode"] in ("auto", "pallas", "reference")


def test_status_endpoint_carries_decode_series():
    from pathway_tpu.internals.monitoring import StatsMonitor

    s = _session()
    h = s.submit([2, 3], max_new_tokens=3)
    s.drain()
    h.result()
    text = StatsMonitor().openmetrics()
    assert "pathway_decode_tokens_total" in text
    assert "pathway_decode_kv_blocks" in text


# ---------------------------------------------------------------------------
# serving: TPU-native streamed answers over live HTTP
# ---------------------------------------------------------------------------


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_http(call, timeout=90.0):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            return call()
        except Exception as exc:  # noqa: BLE001 — server still starting
            last = exc
            time.sleep(0.3)
    raise TimeoutError(f"server did not come up: {last}")


def test_streamed_rag_answer_over_live_http(tmp_path):
    """The acceptance e2e: an end-to-end RAG answer served over live
    HTTP through BaseRAGQuestionAnswerer with the tokens generated by
    the paged continuous-batching decode path and streamed back as
    chunked NDJSON — plus the shared breaker contract (open breaker →
    degraded retrieval-only line, never a 5xx)."""
    import pathway_tpu as pw
    from pathway_tpu.xpacks.llm import mocks
    from pathway_tpu.xpacks.llm.llms import JaxPipelineChat
    from pathway_tpu.xpacks.llm.question_answering import (
        BaseRAGQuestionAnswerer,
        RAGClient,
    )
    from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer

    (tmp_path / "doc1.txt").write_text("Berlin is the capital of Germany.")
    (tmp_path / "doc2.txt").write_text("Paris is the capital of France.")
    docs = pw.io.fs.read(
        tmp_path, format="binary", mode="streaming", with_metadata=True,
        refresh_interval=0.2,
    )
    vs = VectorStoreServer(docs, embedder=mocks.FakeEmbedder(dim=8))
    chat = JaxPipelineChat(model=None, causal_lm=_lm(), max_new_tokens=6)
    qa = BaseRAGQuestionAnswerer(llm=chat, indexer=vs)
    port = _free_port()
    qa.build_server(host="127.0.0.1", port=port)
    qa.server.run(threaded=True, with_cache=False)
    client = RAGClient(host="127.0.0.1", port=port)

    def ask():
        evs = list(
            client.pw_ai_answer_stream(
                "What is the capital of France?",
                max_new_tokens=6, return_context_docs=True,
            )
        )
        assert evs and evs[-1].get("event") == "done", evs
        assert evs[-1]["response"] is not None, evs
        return evs

    evs = _wait_http(ask)
    ctx = [e for e in evs if e["event"] == "context"]
    assert ctx and any("France" in d for d in ctx[0]["context_docs"])
    toks = [e for e in evs if e["event"] == "token"]
    assert toks  # tokens streamed BEFORE the final line
    done = evs[-1]
    assert done["degraded"] is False
    assert "".join(t["text"] for t in toks).strip() == done["response"]
    # decode ticks rode the GENERATE class on the shared runtime
    from pathway_tpu.runtime import get_runtime

    assert get_runtime().stats()["classes"]["generate"]["completed_total"] > 0

    # malformed deadline_ms: clean 400, not an unhandled 500
    import urllib.error
    import urllib.request

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/pw_ai_answer_stream",
        data=json.dumps({"prompt": "x", "deadline_ms": "abc"}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=30)
    assert ei.value.code == 400

    # decode-queue backpressure is SHED, not LLM sickness: 503 +
    # Retry-After (the retrieval stage's contract), breaker untouched
    from pathway_tpu.runtime import AdmissionRefused

    orig_rounds = qa._stream_rounds

    def shed_rounds(*a, **k):
        def gen():
            raise AdmissionRefused(
                "decode pending queue full (synthetic)", retry_after_s=1.0
            )
            yield  # pragma: no cover — makes this a generator

        return gen()

    qa._stream_rounds = shed_rounds
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            list(client.pw_ai_answer_stream("anything queued?"))
        assert ei.value.code == 503
        assert ei.value.headers.get("Retry-After")
    finally:
        qa._stream_rounds = orig_rounds
    assert qa.llm_breaker.state == "closed"

    # breaker contract: open breaker answers retrieval-only, no 5xx
    for _ in range(20):
        qa.llm_breaker.record_failure(RuntimeError("synthetic LLM fault"))
    evs2 = list(client.pw_ai_answer_stream("What is the capital of Germany?"))
    d2 = evs2[-1]
    assert d2["event"] == "done" and d2["degraded"] is True
    assert d2["response"] is None and d2["context_docs"]


def test_adaptive_stream_rounds_resume_without_reprefill(monkeypatch):
    """AdaptiveRAG re-asks resume from the LIVE KV blocks: an unanswered
    round escalates via DecodeSession.extend — the prefill counter
    advances ONLY for the first round's prompt, every escalation rides
    decode steps."""
    from pathway_tpu.xpacks.llm.llms import JaxPipelineChat
    from pathway_tpu.xpacks.llm.question_answering import (
        _NO_INFO,
        AdaptiveRAGQuestionAnswerer,
    )

    lm = _lm()
    chat = JaxPipelineChat(model=None, causal_lm=lm)
    qa = AdaptiveRAGQuestionAnswerer(
        llm=chat, indexer=None, n_starting_documents=1, factor=2,
        max_iterations=3,
    )
    max_new = 4
    orig_decode = lm.decode_tokens
    calls = {"n": 0}

    def fake_decode(ids):
        # round 0 decodes exactly max_new times (one per streamed token);
        # report "no info" there to force an escalation round
        calls["n"] += 1
        if calls["n"] <= max_new:
            return _NO_INFO
        return orig_decode(ids)

    monkeypatch.setattr(lm, "decode_tokens", fake_decode)
    before = generation_status()["prefill_tokens_total"]
    events = list(
        qa._stream_rounds(
            lm, "what is alpha?", ["doc one text", "doc two text"],
            max_new_tokens=max_new, temperature=0.0, seed=0, deadline_s=None,
        )
    )
    kinds = [(k, r) for k, r, _ in events]
    assert ("final", 1) in kinds  # answered on the escalated round
    assert any(k == "token" and r == 1 for k, r in kinds)
    after = generation_status()["prefill_tokens_total"]
    prompt0 = lm.encode_prompt(
        __import__(
            "pathway_tpu.xpacks.llm.prompts", fromlist=["x"]
        ).prompt_qa_geometric_rag(
            "what is alpha?", ["doc one text"],
            information_not_found_response=_NO_INFO,
        )
    )
    # AT MOST one prefill, round-0 only: the escalated round's (longer)
    # prompt rides extend().  Prefix sharing may shrink round 0's
    # prefill too — the cached session's prefix index can already hold
    # this prompt's blocks from earlier submits — but a round-1
    # re-prefill would push the delta past len(prompt0).
    assert after - before <= len(prompt0)
    # retained blocks released at the end of the escalation
    assert lm.paged_session().stats()["retained"] == 0
