"""Speculative multi-token decode + copy-on-write KV prefix sharing
(ISSUE 16, ROADMAP item 2).

Acceptance pins: paged+speculative greedy decode is token-for-token
identical to non-speculative decode (both kernel modes, mixed prompt
lengths, mid-stream admit/retire); a second request sharing a prefix
prefills only its tail (prefill-counter pin); a writer COWs a shared
block before mutating; refcounted free never releases a block another
sequence still reads; cancel()/extend()/pool-exhaustion stay correct
with shared blocks; the new metric families are declared, emitted, and
rolled into the health block; the HBM ledger charges the pool once, not
per referencing sequence.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.generation import (
    BlockAllocator,
    DecodeSession,
    PagedDecoder,
    PagedKVPool,
    PrefixIndex,
    paged_verify_attention,
    propose_draft,
)
from pathway_tpu.generation.engine import generation_status
from pathway_tpu.models.decoder import CausalLM, DecoderConfig

TINY = DecoderConfig(
    vocab_size=211, hidden_dim=64, num_layers=2, num_heads=4, mlp_dim=128,
    max_len=128, dtype=jnp.float32,
)

_LMS: dict = {}


def _lm(cfg=TINY) -> CausalLM:
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
# allocator refcounts + prefix index units
# ---------------------------------------------------------------------------


def test_allocator_refcounted_acquire_and_lingering_revival():
    a = BlockAllocator(4)
    blocks = a.alloc(2)
    assert a.acquire(blocks[0]) == 2
    assert a.shared_count == 1
    # one reader frees: block must NOT rejoin the free list
    a.free([blocks[0]])
    assert a.free_count == 2 and a.refcount(blocks[0]) == 1
    a.free(blocks)  # last readers
    assert a.free_count == 4
    # lingering revival: acquire pulls a refcount-0 block back out of
    # the free list (sequential re-ask of a freed prefix)
    assert a.acquire(blocks[1]) == 1
    assert a.free_count == 3 and a.refcount(blocks[1]) == 1


def test_prefix_index_chain_match_verifies_content():
    ix = PrefixIndex(4)
    params = object()
    root = PrefixIndex.root_key(params)
    k1 = ix.register_full(root, [1, 2, 3, 4], block=7)
    ix.register_full(k1, [5, 6, 7, 8], block=9)
    full, _key, partial = ix.match(params, [1, 2, 3, 4, 5, 6, 7, 8, 99])
    assert full == [7, 9] and partial is None
    # diverging second chunk: only the first block matches
    full, _key, _ = ix.match(params, [1, 2, 3, 4, 5, 6, 0, 0, 0])
    assert full == [7]
    # the cap: at least one token must remain to produce logits
    full, _key, _ = ix.match(params, [1, 2, 3, 4])
    assert full == []  # usable = 3 < block_size
    # different params identity: no sharing across weights
    full, _key, _ = ix.match(object(), [1, 2, 3, 4, 5, 6, 7, 8, 99])
    assert full == []


def test_prefix_index_partial_tail_lcp_and_truncate():
    ix = PrefixIndex(4)
    params = object()
    root = PrefixIndex.root_key(params)
    k1 = ix.register_full(root, [1, 2, 3, 4], block=0)
    ix.register_partial(k1, [10, 11, 12], block=3)
    full, key, partial = ix.match(params, [1, 2, 3, 4, 10, 11, 99, 98])
    assert full == [0] and key == k1 and partial == (3, 2)  # lcp=2
    # owner writes slot 1: only the first entry stays shareable
    ix.truncate_partial(3, 1)
    _, _, partial = ix.match(params, [1, 2, 3, 4, 10, 11, 99, 98])
    assert partial == (3, 1)
    ix.truncate_partial(3, 0)
    _, _, partial = ix.match(params, [1, 2, 3, 4, 10, 11, 99, 98])
    assert partial is None


def test_propose_draft_prompt_lookup():
    # suffix [7, 8] recurs earlier; drafts continue from the most
    # recent prior occurrence
    toks = [1, 7, 8, 9, 4, 7, 8, 5, 6, 7, 8]
    assert propose_draft(toks, 3) == [5, 6, 7]
    assert propose_draft(toks, 1) == [5]
    assert propose_draft([1, 2, 3], 4) == []  # no recurrence
    assert propose_draft([9], 4) == []


# ---------------------------------------------------------------------------
# verify-mode kernel: K=1 bundle must equal the single-token step
# ---------------------------------------------------------------------------


def test_verify_kernel_modes_match_and_k1_matches_single():
    from pathway_tpu.generation import paged_decode_attention

    rng = np.random.default_rng(1)
    L, NB, bs, H, Dh = 2, 12, 8, 4, 16
    rows, W, K = 3, 4, 4
    k_pool = jnp.asarray(rng.normal(size=(L, NB, bs, H, Dh)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(L, NB, bs, H, Dh)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(rows, K, H, Dh)), jnp.float32)
    bt = jnp.asarray(
        rng.permutation(NB)[: rows * W].reshape(rows, W), jnp.int32
    )
    base = jnp.asarray([5, 20, 13], jnp.int32)
    n_new = jnp.asarray([4, 2, 1], jnp.int32)
    ref = paged_verify_attention(
        q, k_pool, v_pool, bt, base, n_new, 1, block_size=bs,
        mode="reference",
    )
    pal = paged_verify_attention(
        q, k_pool, v_pool, bt, base, n_new, 1, block_size=bs, mode="pallas",
    )
    # pad lanes (k >= n_new[r]) are unspecified — the host never commits
    # them — so compare the REAL lanes only
    for r in range(rows):
        n = int(n_new[r])
        np.testing.assert_allclose(
            np.asarray(pal)[r, :n], np.asarray(ref)[r, :n],
            atol=2e-5, rtol=2e-5,
        )
    # K=1 bundle == the single-token decode step (the greedy parity
    # argument rides this; the token ids themselves are pinned exact in
    # test_speculative_greedy_parity_both_kernel_modes).  The bundle and
    # the step are two XLA programs over the same numbers ([rows, K, ..]
    # against [rows, ..] einsums) and may sum in different orders, so
    # they agree to the float32 bound of that, not to the last bit: a
    # score q.k/sqrt(Dh) sums Dh products, so two orders differ by at
    # most ds = Dh * eps * |q||k| / sqrt(Dh); a softmax weight then
    # moves by a share of 2 * ds (numerator and normaliser); the output
    # sums T = W * bs weighted values, T * eps * max|v| more.  Observed
    # here: 2.4e-7 against a bound of some 1e-4.
    single = paged_decode_attention(
        q[:, 0], k_pool, v_pool, bt, base + 1, 1, block_size=bs,
        mode="reference",
    )
    eps = float(np.finfo(np.float32).eps)
    q_len = float(np.linalg.norm(np.asarray(q[:, 0]), axis=-1).max())
    k_len = float(np.linalg.norm(np.asarray(k_pool[1]), axis=-1).max())
    ds = Dh * eps * q_len * k_len / np.sqrt(Dh)
    tol = (W * bs * eps + 2 * ds) * float(np.abs(np.asarray(v_pool[1])).max())
    np.testing.assert_allclose(
        np.asarray(ref[:, 0]), np.asarray(single), rtol=0, atol=tol
    )


# ---------------------------------------------------------------------------
# speculative greedy parity (the acceptance pin)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["reference", "pallas"])
def test_speculative_greedy_parity_both_kernel_modes(mode):
    lm = _lm()
    base = PagedDecoder(
        TINY, lm.params, pool_tokens=2048, block_size=16, mode=mode,
        spec_k=0, prefix_share=False,
    )
    want = base.generate_ids(MIXED_PROMPTS, max_new_tokens=12)
    spec = PagedDecoder(
        TINY, lm.params, pool_tokens=2048, block_size=16, mode=mode,
        spec_k=4, prefix_share=True,
    )
    got = spec.generate_ids(MIXED_PROMPTS, max_new_tokens=12)
    for i in range(len(MIXED_PROMPTS)):
        assert want[i] == got[i], i
    # dense oracle agrees too
    dense = lm.generate_ids(MIXED_PROMPTS, max_new_tokens=12)
    for i in range(len(MIXED_PROMPTS)):
        assert dense[i].tolist() == got[i], i


def test_speculative_midstream_admit_and_retire_parity():
    lm = _lm()
    s = _session(spec_k=4, prefix_share=True)
    ha = s.submit(MIXED_PROMPTS[0], max_new_tokens=10)
    hb = s.submit(MIXED_PROMPTS[1], max_new_tokens=3)  # retires early
    for _ in range(4):
        s.tick()
    assert hb.done
    hc = s.submit(MIXED_PROMPTS[2], max_new_tokens=8)  # admitted mid-stream
    s.drain()
    assert ha.result() == lm.generate_ids([MIXED_PROMPTS[0]], 10)[0].tolist()
    assert hb.result() == lm.generate_ids([MIXED_PROMPTS[1]], 3)[0].tolist()
    assert hc.result() == lm.generate_ids([MIXED_PROMPTS[2]], 8)[0].tolist()
    assert s.stats()["kv_blocks_used"] == 0


def test_speculative_repetitive_prompt_accepts_drafts():
    """A repetitive prompt makes prompt-lookup drafts land: acceptance
    must show up in the counters AND tokens must still match the
    non-speculative stream."""
    lm = _lm()
    prompt = [7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8]
    base = PagedDecoder(
        TINY, lm.params, pool_tokens=2048, block_size=16,
        spec_k=0, prefix_share=False,
    )
    want = base.generate_ids([prompt], max_new_tokens=16)[0]
    before = dict(generation_status())
    spec = PagedDecoder(
        TINY, lm.params, pool_tokens=2048, block_size=16,
        spec_k=4, prefix_share=False,
    )
    got = spec.generate_ids([prompt], max_new_tokens=16)[0]
    after = dict(generation_status())
    assert got == want
    assert after["draft_proposed_total"] > before["draft_proposed_total"]
    # the tiny random model may reject everything, but the decode must
    # have finished in fewer ticks than tokens whenever anything landed
    assert after["draft_accepted_total"] >= before["draft_accepted_total"]


# ---------------------------------------------------------------------------
# COW prefix-sharing semantics
# ---------------------------------------------------------------------------


def test_shared_prefix_second_request_prefills_only_tail():
    """The tentpole's serving win: request B sharing A's full prompt
    blocks skips their prefill (counter pin) and still matches its own
    non-shared oracle."""
    lm = _lm()
    shared = list(range(10, 42))  # two full 16-token blocks
    pa = shared + [50, 51]
    pb = shared + [60, 61, 62]
    plain = PagedDecoder(
        TINY, lm.params, pool_tokens=2048, block_size=16,
        prefix_share=False,
    )
    want_a = plain.generate_ids([pa], max_new_tokens=8)[0]
    want_b = plain.generate_ids([pb], max_new_tokens=8)[0]

    s = _session(prefix_share=True)
    before = generation_status()["prefill_tokens_total"]
    ha = s.submit(pa, max_new_tokens=8)
    s.drain()
    mid = generation_status()["prefill_tokens_total"]
    assert mid - before == len(pa)  # first request prefills in full
    hb = s.submit(pb, max_new_tokens=8)
    s.drain()
    after = generation_status()["prefill_tokens_total"]
    # B's two full shared blocks never re-prefill; its tail rides the
    # decode ticks as forced input (prefill counter untouched)
    assert after == mid
    assert ha.result() == want_a
    assert hb.result() == want_b
    st = generation_status()
    assert st["prefix_hit_blocks_total"] > 0
    assert 0.0 < st["prefix_hit_rate"] <= 1.0


def test_writer_cows_shared_block_before_mutating():
    """Two live sequences share partial-tail content: the second
    adopter copy-on-writes before its first divergent token, so the
    first sequence's tokens are untouched — and the COW counter
    moves."""
    lm = _lm()
    shared = list(range(100, 120))  # 1 full block + 4-token partial tail
    pa = shared + [1]
    pb = shared + [2]
    plain = PagedDecoder(
        TINY, lm.params, pool_tokens=2048, block_size=16,
        prefix_share=False,
    )
    want_a = plain.generate_ids([pa], max_new_tokens=10)[0]
    want_b = plain.generate_ids([pb], max_new_tokens=10)[0]
    before = generation_status()["cow_copies_total"]
    s = _session(prefix_share=True)
    ha = s.submit(pa, max_new_tokens=10, retain=True)
    s.drain()  # A finishes and parks retained: its blocks stay resident
    hb = s.submit(pb, max_new_tokens=10)
    s.drain()
    assert ha.result() == want_a
    assert hb.result() == want_b
    assert generation_status()["cow_copies_total"] > before
    s.release(ha)
    assert s.stats()["kv_blocks_used"] == 0


def test_refcounted_free_keeps_shared_block_for_remaining_reader():
    """A retires while B still reads the shared blocks: the blocks must
    not rejoin the free list until B is done too."""
    lm = _lm()
    shared = list(range(10, 42))  # two full blocks
    pa = shared + [50]
    pb = shared + [60]
    plain = PagedDecoder(
        TINY, lm.params, pool_tokens=2048, block_size=16,
        prefix_share=False,
    )
    want_b = plain.generate_ids([pb], max_new_tokens=12)[0]
    s = _session(prefix_share=True)
    ha = s.submit(pa, max_new_tokens=2)
    s.drain()
    # park A's blocks as lingering-registered, then make B adopt them
    hb = s.submit(pb, max_new_tokens=12, retain=True)
    s.tick()
    assert s.pool.allocator.used_count > 0
    # C adopts the same prefix while B is retained-live
    pc = shared + [70]
    want_c = plain.generate_ids([pc], max_new_tokens=12)[0]
    hc = s.submit(pc, max_new_tokens=12)
    s.drain()
    assert hb.result() == want_b
    assert hc.result() == want_c
    # B retained: its (previously shared) blocks must still be held
    assert s.stats()["retained"] == 1
    assert s.pool.allocator.used_count > 0
    s.release(hb)
    assert s.stats()["kv_blocks_used"] == 0
    assert ha.result() is not None


def test_cancel_and_extend_with_shared_blocks():
    """cancel() of one sharer decrements refcounts without yanking the
    other's blocks; extend() of a retained sharer COWs its tail and
    matches the oracle."""
    lm = _lm()
    shared = list(range(60, 84))  # 1.5 blocks
    pa = shared + [3]
    pb = shared + [4]
    plain = PagedDecoder(
        TINY, lm.params, pool_tokens=2048, block_size=16,
        prefix_share=False,
    )
    want_b = plain.generate_ids([pb], max_new_tokens=6)[0]
    s = _session(prefix_share=True)
    ha = s.submit(pa, max_new_tokens=20, retain=True)
    s.tick()  # A live, blocks registered
    hb = s.submit(pb, max_new_tokens=6)
    s.tick()  # B admitted via prefix match, shares A's blocks
    s.cancel(ha)  # cancel the FIRST owner mid-flight
    s.drain()
    assert hb.result() == want_b  # B unharmed by A's cancel
    assert s.stats()["kv_blocks_used"] == 0

    # extend() on a retained sequence whose tail got shared
    h1 = s.submit(pa, max_new_tokens=4, retain=True)
    s.drain()
    g1 = h1.result()
    h2 = s.submit(pa + g1, max_new_tokens=4)  # adopts h1's blocks
    s.tick()
    h3 = s.extend(h1, [90, 91], max_new_tokens=4)
    s.drain()
    oracle = lm.generate_ids([pa + g1 + [90, 91]], 4)[0].tolist()
    assert h3.result() == oracle
    assert h2.result() is not None
    s.release(h3)
    assert s.stats()["kv_blocks_used"] == 0


def test_pool_exhaustion_with_shared_blocks_keeps_queueing():
    """Admission discounts matched blocks — a request that only fits
    BECAUSE of sharing gets in; one that cannot fit stays queued and
    runs once blocks free (no deadlock, no double-release)."""
    lm = _lm()
    shared = list(range(0, 32))  # two full blocks
    s = _session(pool_tokens=128, block_size=16, prefix_share=True)  # 8 blocks
    # A: 2 prompt blocks + tail/generation ⇒ 3 blocks
    ha = s.submit(shared + [40], max_new_tokens=8, retain=True)
    s.drain()
    used = s.pool.allocator.used_count
    assert used == 3
    # B shares A's two full blocks: needs only 1 + 1 fresh with the
    # discount (3 without) — fits in the 5 remaining
    hb = s.submit(shared + [41], max_new_tokens=8)
    # C needs 5 fresh blocks (64-token prompt, no shared prefix): more
    # than the 4 free while B runs, exactly what B's retirement frees
    hc = s.submit(list(range(200, 264)), max_new_tokens=5)
    s.drain()
    assert hb.done and hc.done
    assert hb.result() is not None and hc.result() is not None
    s.release(ha)
    assert s.stats()["kv_blocks_used"] == 0


# ---------------------------------------------------------------------------
# observability: registry lint both directions, health block, HBM ledger
# ---------------------------------------------------------------------------


def test_new_metric_families_declared_and_emitted():
    from pathway_tpu.internals.metrics_names import declared_metric_names
    from pathway_tpu.generation.engine import _PROVIDER

    declared = declared_metric_names()
    fams = [
        "pathway_decode_prefix_hit_blocks_total",
        "pathway_decode_shared_blocks",
        "pathway_decode_cow_copies_total",
        "pathway_decode_draft_proposed_total",
        "pathway_decode_draft_accepted_total",
    ]
    for f in fams:
        assert f in declared, f
    lines = _PROVIDER.openmetrics_lines()
    emitted = {
        ln.split("{")[0].split(" ")[0]
        for ln in lines if ln and not ln.startswith("#")
    }
    for f in fams:
        assert f in emitted, f
    # every emitted series resolves to a declared family
    for ln in lines:
        if ln.startswith("#") or not ln.strip():
            continue
        name = ln.split("{")[0].split(" ")[0]
        assert name in declared, ln


def test_health_block_carries_rates():
    s = generation_status()
    assert "prefix_hit_rate" in s and "draft_acceptance_rate" in s
    assert 0.0 <= s["prefix_hit_rate"] <= 1.0
    assert 0.0 <= s["draft_acceptance_rate"] <= 1.0
    assert "shared_blocks" in s


def test_hbm_ledger_charges_pool_once_despite_sharing():
    """Shared blocks live in the SAME preallocated pool arrays — the
    ledger entry is the pool's constant footprint, registered once per
    session, never per referencing sequence."""
    from pathway_tpu.observability.hbm_ledger import get_ledger

    s = _session(name="ledger-probe", prefix_share=True)
    want = s.pool.hbm_bytes()
    rows = [
        (c, b) for c, _shard, b in get_ledger().entries()
        if c.startswith("kv_pool:ledger-probe")
    ]
    assert len(rows) == 1 and rows[0][1] == want
    shared = list(range(10, 42))
    h1 = s.submit(shared + [1], max_new_tokens=4, retain=True)
    s.drain()
    h2 = s.submit(shared + [2], max_new_tokens=4, retain=True)
    s.drain()
    rows = [
        (c, b) for c, _shard, b in get_ledger().entries()
        if c.startswith("kv_pool:ledger-probe")
    ]
    assert len(rows) == 1 and rows[0][1] == want
    s.release(h1)
    s.release(h2)
