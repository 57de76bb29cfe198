"""The causal embedder's latent layers (multi-head latent attention in its
prefill form, a sigmoid router with a selection bias) against their plain
reference, ``perfbench/checks/joyai.py`` (one source: the benchmark's own
reference is imported, not copied).

Everything runs the configuration file's tiny ``rehearse`` preset on the CPU:
5 layers (latent/dense, four latent/sparse), hidden 64, 4 heads whose query
and key are 16 rotary-free + 8 rotary dimensions and whose value is 16, ranks
24 (q) and 16 (kv), 16 experts top-4 of width 32, dense width 128, vocabulary
512.  The ratio of the score's size to its rotary-free part is the published
one (24/16 = 192/128), so a wrong scale is as wrong here as there.

Tolerances, and why (``tests/test_causal_moe_embedder.py`` has the argument in
full; the readings here are this model's).  A ``float32`` program
(``dtype=float32``) must match the float32 reference to ``F32_TOL``: what is
left is the order of float32 sums (read: 2.8e-7 to 4.4e-7 a layer).  That pins
the mathematics: the two latent norms, the one rotary key, the interleaved
pairs, the scale, the value's size, the bias in the choice and not in the
weights; each control below breaks one of them and has to FAIL it.  The
program as deployed (bfloat16 operands) is held per layer, fed the reference's
own input, to ``BF16_LAYER_TOL`` of the layer's addition for the MEDIAN token
(read: 0.0027-0.0058), because a near-tie of the router flips one token's
fourth expert; against the reference at the STATED precision it differs by the
order of float32 sums alone (read: 6e-8 to 8e-8; ``STATED_LAYER_TOL``), and
the reference one step of precision down (``lowered``) lies 0.0076-0.0104 from
the stated one and has to FAIL that tolerance a hundredfold.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import costs_joyai  # noqa: E402
import costs_laguna  # noqa: E402
from checks import joyai as reference  # noqa: E402
from encoders import joyai as builder  # noqa: E402

from pathway_tpu.internals import flight_recorder  # noqa: E402
from pathway_tpu.models import causal_moe_embedder as cme  # noqa: E402
from pathway_tpu.models.encoder import SentenceEncoder, ragged_prepare  # noqa: E402
from pathway_tpu.ops import routed_experts as rx  # noqa: E402

SEED = 2147483659
F32_TOL = 2e-5  # relative; float32 sums in another order
BF16_LAYER_TOL = 0.0072  # the median token's error relative to the layer's addition
BF16_WHOLE_TOL = 0.3  # five layers at 16 experts: flips compound
STATED_LAYER_TOL = 1e-5  # the median token against the stated-precision reference


def _merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merged(base[k], v) if isinstance(v, dict) and isinstance(base.get(k), dict) else v
    return out


@pytest.fixture(scope="module")
def published() -> dict:
    with open(os.path.join(BENCH, "configs", "vs-joyai-flash-bf16-marcodoc.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny(published) -> dict:
    return _merged(published, published["rehearse"])


@pytest.fixture(scope="module")
def params(tiny):
    return builder.params(tiny, SEED)


def _cfg(tiny, **over):
    return dataclasses.replace(builder.model_config(tiny), **over)


def _ids(n: int, vocab: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.concatenate([[1], rng.integers(4, vocab, n - 2), [2]]).astype(np.int32)


def _reference_layer(tiny, layer: int, p, x, precision="float32"):
    st = reference.layer_statics(tiny, layer)
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.layer_forward(
            p, jnp.asarray(x), st["freq"], precision=precision, **st["kw"]).astype(jnp.float32))


def _reference_states(tiny, ids):
    """The reference's residual stream before each layer and after the last
    (float32, one document)."""
    emb = builder.embedding_params(tiny, SEED)
    states = [np.asarray(emb["tok_emb"][jnp.asarray(ids)].astype(jnp.float32))]
    for layer in range(int(tiny["num_hidden_layers"])):
        states.append(_reference_layer(
            tiny, layer, builder.layer_params(tiny, SEED, layer), states[-1]))
    return states


def _program_layer(cfg, p, layer: int, x):
    t = x.shape[0]
    out, _sizes = cme._layer(cfg, layer, p, jnp.asarray(x)[None], jnp.arange(t)[None], None,
                             jnp.ones((1, t), bool))
    return np.asarray(out[0])


def _rel(a, b) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _token_errors(got, want, x) -> np.ndarray:
    """Each token's error relative to what the layer added to it."""
    return np.linalg.norm(got - want, axis=1) / np.linalg.norm(want - x, axis=1)


def _encode_reference(tiny, rows, precision="float32"):
    """``reference.encode`` over token rows instead of texts."""
    texts = [" ".join(f"w{i}" for i in r) for r in rows]
    table = dict(zip(texts, rows))
    old = reference.tokenize
    reference.tokenize = lambda text, _v, _m: [int(i) for i in table[text]]
    try:
        return reference.encode(tiny, texts, lambda: builder.embedding_params(tiny, SEED),
                                lambda l: builder.layer_params(tiny, SEED, l), precision=precision)
    finally:
        reference.tokenize = old


def _padded(rows):
    width = max(len(r) for r in rows)
    ids = np.zeros((len(rows), width), np.int32)
    mask = np.zeros((len(rows), width), np.uint8)
    for i, r in enumerate(rows):
        ids[i, : len(r)], mask[i, : len(r)] = r, 1
    return ids, mask


def _program_rows(cfg, params, rows):
    """The dense forward over token rows padded to one width: [n, D]."""
    out, _counters = cme.CausalMoeEmbedder(cfg).apply({"params": params}, *_padded(rows))
    return np.asarray(out)


def _packed(cfg, rows):
    """``rows`` (token id arrays) as ONE prepared launch of ``cfg``."""
    ids, mask = _padded(rows)
    prepared, _stats = ragged_prepare(ids, mask, ids.shape[1], vocab_size=cfg.vocab_size, cfg=cfg)
    assert len(prepared) == 1
    return prepared[0][0]


# -- each layer kind, and the whole forward -------------------------------------


@pytest.mark.parametrize("layer,kind", [(0, "latent attention, dense MLP"),
                                        (1, "latent attention, routed experts"),
                                        (4, "latent attention, routed experts, last")])
def test_each_layer_kind_matches_the_reference_fed_the_references_input(
        tiny, params, layer, kind):
    states = _reference_states(tiny, _ids(40, tiny["vocab_size"]))
    x, want = states[layer], states[layer + 1]
    p = params[f"layer_{layer}"]
    got32 = _program_layer(_cfg(tiny, dtype=jnp.float32), p, layer, x)
    assert _rel(got32 - x, want - x) < F32_TOL
    got = _program_layer(_cfg(tiny), p, layer, x)
    errors = _token_errors(got, want, x)
    assert np.median(errors) < BF16_LAYER_TOL
    assert np.sum(errors > 5 * BF16_LAYER_TOL) <= 2  # a flipped fourth expert
    # against the reference at the stated precision only the order of sums is left;
    # the same layer with everything in bfloat16 is a thousand times further
    stated, low = (_reference_layer(tiny, layer, p, x, precision)
                   for precision in ("stated", "lowered"))
    assert np.median(_token_errors(got, stated, x)) < STATED_LAYER_TOL
    assert np.sum(_token_errors(got, stated, x) > STATED_LAYER_TOL) <= 2
    assert np.median(_token_errors(low, stated, x)) > 100 * STATED_LAYER_TOL
    assert np.median(_token_errors(low, want, x)) > BF16_LAYER_TOL


@pytest.mark.parametrize("length", [3, 16, 17, 40, 64])
def test_whole_forward_at_several_lengths(tiny, params, length):
    """Under, at and over a query block (16), and the longest row (64: the
    reference pads every shorter one behind its text up to it)."""
    row = _ids(length, tiny["vocab_size"], seed=length)
    want = _encode_reference(tiny, [row])[0]
    got32 = _program_rows(_cfg(tiny, dtype=jnp.float32), params, [row])[0]
    assert _rel(got32, want) < 5 * F32_TOL  # five layers
    got = _program_rows(_cfg(tiny), params, [row])[0]
    assert _rel(got, want) < BF16_WHOLE_TOL
    stated = _encode_reference(tiny, [row], precision="stated")[0]
    assert _rel(got, stated) < (1e-5 if length <= 3 else 0.05)  # a rounding that fell the other way


def test_the_reference_pads_behind_the_text_and_cuts_back(tiny, params):
    """Departure (ii): one compiled program a layer kind, and what lies
    behind a text changes nothing in it."""
    x = _reference_states(tiny, _ids(9, tiny["vocab_size"]))[1]
    p = params["layer_1"]
    st = reference.layer_statics(tiny, 1)
    assert st["kw"]["max_len"] == tiny["max_seq_length"] == 64
    alone = _reference_layer(tiny, 1, p, x)
    longer = dict(st["kw"], max_len=80)
    with jax.default_matmul_precision("highest"):
        again = np.asarray(reference.layer_forward(p, jnp.asarray(x), st["freq"], **longer))
    assert alone.shape == again.shape == x.shape
    np.testing.assert_allclose(alone, again, rtol=1e-5, atol=1e-4)  # float32 sums, values of tens


# -- latent attention's parts ---------------------------------------------------


def test_interleaved_pairs_against_hand_computed_values():
    """``rope_interleave``: dimension 2i turns with 2i+1 at theta^(-2i/rot);
    the result is laid out [evens | odds]."""
    spec = cme.RotarySpec(theta=32_000_000.0, interleaved=True)
    x = jnp.asarray(np.arange(1, 9, dtype=np.float32).reshape(1, 1, 8).repeat(2, axis=0))
    got = np.asarray(cme._rotate(x, jnp.asarray([0, 5]), spec))
    np.testing.assert_allclose(got[0, 0], [1, 3, 5, 7, 2, 4, 6, 8], atol=1e-6)  # position 0
    want = np.zeros(8)
    for i in range(4):
        angle = 5 * 32_000_000.0 ** (-2 * i / 8)
        a, b = 2 * i + 1, 2 * i + 2  # the values at dimensions 2i and 2i+1
        want[i] = a * math.cos(angle) - b * math.sin(angle)
        want[4 + i] = b * math.cos(angle) + a * math.sin(angle)
    np.testing.assert_allclose(got[1, 0], want, rtol=1e-5, atol=1e-5)
    # the half-split pairing (Laguna's, Falcon-H1's) is another rotation
    halves = np.asarray(cme._rotate(x, jnp.asarray([0, 5]), cme.RotarySpec(theta=32_000_000.0)))
    assert np.abs(halves[1, 0] - want).max() > 1.0


def test_a_heads_score_is_its_rotary_free_part_plus_the_one_rotary_key(tiny, params):
    """``q_i . k_j = q_nope . k_nope_h + q_pe . k_pe`` with ``k_pe`` the SAME
    vector under every head, and the weighted sum runs over values of their
    own size: the attention's addition recomputed by hand from the layer's
    five matrices."""
    cfg = _cfg(tiny, dtype=jnp.float32)
    p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), params["layer_1"])
    nope, rope, rank = cfg.latent_nope_dim, cfg.latent_rope_dim, cfg.latent_kv_rank
    t = 12
    a = jax.random.normal(jax.random.PRNGKey(3), (t, cfg.hidden_dim), jnp.float32)
    pos = jnp.arange(t)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(cme._attend_latent(cfg, p, a, pos, None, None))
        norm = lambda x: x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.rms_eps)
        q = jnp.einsum("tr,rhe->the", norm(a @ p["wq_a"]), p["wq_b"])
        ckv = a @ p["wkv_a"]
        kv = jnp.einsum("tr,rhe->the", norm(ckv[:, :rank]), p["wkv_b"])
        q_pe = cme._rotate(q[..., nope:], pos, cfg.latent_rotary)
        k_pe = cme._rotate(ckv[:, None, rank:], pos, cfg.latent_rotary)[:, 0]  # [T, rope]
        assert k_pe.shape == (t, rope) and kv.shape == (t, 4, nope + cfg.latent_v_dim)
        scores = (jnp.einsum("qhd,khd->hqk", q[..., :nope], kv[..., :nope])
                  + jnp.einsum("qhd,kd->hqk", q_pe, k_pe)) / math.sqrt(nope + rope)
        causal = jnp.tril(jnp.ones((t, t), bool))
        w = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", w, kv[..., nope:])
        want = np.asarray(jnp.einsum("the,hed->td", o, p["wo"]))
    assert _rel(got, want) < F32_TOL


@pytest.mark.parametrize("wrong", ["nope", "rope"])
def test_the_scale_is_the_whole_score_s_size_and_no_part_s(tiny, params, wrong):
    """1/sqrt(24) here, 1/sqrt(192) published: a control scaled by the
    rotary-free part (1/sqrt(128)) or the rotary part (1/sqrt(64)) alone,
    made by multiplying ``W_uq``, fails the tolerance the layer meets."""
    cfg = _cfg(tiny, dtype=jnp.float32)
    states = _reference_states(tiny, _ids(40, tiny["vocab_size"]))
    x, want = states[1], states[2]
    p = params["layer_1"]
    assert _rel(_program_layer(cfg, p, 1, x) - x, want - x) < F32_TOL
    part = {"nope": cfg.latent_nope_dim, "rope": cfg.latent_rope_dim}[wrong]
    factor = math.sqrt((cfg.latent_nope_dim + cfg.latent_rope_dim) / part)
    control = dict(p, wq_b=p["wq_b"].astype(jnp.float32) * factor)
    assert _rel(_program_layer(cfg, control, 1, x) - x, want - x) > 1000 * F32_TOL


def test_a_value_of_its_own_size_goes_through_the_shared_attention():
    """``_attention`` takes the score's size from ``q`` and the result's from
    ``v`` (192 and 128 published; 24 and 16 here), a group of one."""
    t, h, hd, vd = 40, 4, 24, 16
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(kq, (t, h, hd), jnp.float32)
    k = jax.random.normal(kk, (t, h, hd), jnp.float32)
    v = jax.random.normal(kv_, (t, h, vd), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(cme._attention(q, k, v, jnp.arange(t), None, None, window=None,
                                        q_block=16))
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf), -1)
        want = np.asarray(jnp.einsum("hqk,khd->qhd", w, v))
    assert got.shape == (t, h, vd)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# -- the router -----------------------------------------------------------------


def _one_token_router(logits):
    """``x`` and ``router`` whose product is ``logits`` [E]."""
    e = len(logits)
    return jnp.asarray(logits, jnp.float32)[None], jnp.eye(e, dtype=jnp.float32)


def test_a_bias_changes_which_experts_run_and_not_their_weights():
    logits = 2.0 - 0.1 * np.arange(16)
    s = 1.0 / (1.0 + np.exp(-logits))
    x, router = _one_token_router(logits)
    plain_e, plain_w = rx.route(x, router, top_k=8, scaling=2.5, scoring="sigmoid")
    assert np.asarray(plain_e)[0].tolist() == list(range(8))
    np.testing.assert_allclose(np.asarray(plain_w)[0], 2.5 * s[:8] / s[:8].sum(), rtol=1e-6)
    # the ninth expert lifted into the choice: it runs, the eighth does not
    bias = jnp.zeros((16,), jnp.float32).at[8].set(0.05)
    experts, weights = rx.route(x, router, top_k=8, scaling=2.5, scoring="sigmoid", bias=bias)
    chosen = np.asarray(experts)[0].tolist()
    assert sorted(chosen) == [0, 1, 2, 3, 4, 5, 6, 8]
    np.testing.assert_allclose(float(np.asarray(weights).sum()), 2.5, rtol=1e-6)
    # the weights are the UNBIASED scores of the chosen, renormalised
    np.testing.assert_allclose(np.asarray(weights)[0], 2.5 * s[chosen] / s[chosen].sum(),
                               rtol=1e-6)
    # the control whose weights come from s + bias is another function
    biased = s + np.asarray(bias)
    control = 2.5 * biased[chosen] / biased[chosen].sum()
    assert np.abs(np.asarray(weights)[0] - control).max() > 1e-3


def test_softmax_scoring_with_no_bias_is_the_softmax_router_bit_for_bit():
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 64), jnp.float32)
    router = jax.random.normal(jax.random.PRNGKey(2), (64, 16), jnp.float32) * 0.2
    want_e, want_w = rx.route(x, router, top_k=4, scaling=2.5)
    for kw in (dict(scoring="softmax"), dict(scoring="softmax", bias=jnp.zeros((16,)))):
        experts, weights = rx.route(x, router, top_k=4, scaling=2.5, **kw)
        np.testing.assert_array_equal(np.asarray(experts), np.asarray(want_e))
        np.testing.assert_array_equal(np.asarray(weights), np.asarray(want_w))
    scores = np.asarray(jax.nn.softmax(jnp.dot(x, router, precision="highest"), axis=-1))
    np.testing.assert_array_equal(np.asarray(want_e), np.argsort(-scores, axis=1)[:, :4])
    with pytest.raises(ValueError):
        rx.route(x, router, top_k=4, scaling=2.5, scoring="tanh")


def test_choosing_without_the_bias_fails_the_tolerance(tiny, params):
    """A layer that drops the bias from the choice routes some tokens to
    other experts and is far outside the tolerance the whole layer meets;
    the seeded bias changes the choice for a share of the tokens and not all."""
    cfg = _cfg(tiny, dtype=jnp.float32)
    states = _reference_states(tiny, _ids(64, tiny["vocab_size"]))
    x, want = states[1], states[2]
    p = params["layer_1"]
    assert _rel(_program_layer(cfg, p, 1, x) - x, want - x) < F32_TOL
    unbiased = dict(p, moe=dict(p["moe"], bias=jnp.zeros_like(p["moe"]["bias"])))
    errors = _token_errors(_program_layer(cfg, unbiased, 1, x), want, x)
    assert _rel(_program_layer(cfg, unbiased, 1, x) - x, want - x) > 100 * F32_TOL  # read: 0.014
    moved = np.mean(errors > 1e-3)
    assert 0.05 < moved < 0.95, moved


def test_the_seeded_bias_changes_the_choice_for_three_tokens_in_ten(published):
    """At the PUBLISHED router's size (2,048 -> 256 experts, top 8) with the
    builder's gain and bias scale, over unit-RMS inputs: the share the
    configuration file's ``assumed`` states."""
    d, e, k = published["hidden_size"], published["n_routed_experts"], 8
    p = builder.layer_params(dict(
        published, hidden_size=d, intermediate_size=8, moe_intermediate_size=8, q_lora_rank=8,
        kv_lora_rank=8, num_attention_heads=1, n_routed_experts=e), SEED, 1)["moe"]
    x = jax.random.normal(jax.random.PRNGKey(7), (2048, d), jnp.float32)
    with_bias, _ = rx.route(x, p["router"], top_k=k, scaling=2.5, scoring="sigmoid",
                            bias=p["bias"])
    without, _ = rx.route(x, p["router"], top_k=k, scaling=2.5, scoring="sigmoid")
    changed = np.mean(np.sort(np.asarray(with_bias), 1) != np.sort(np.asarray(without), 1),
                      axis=1) > 0
    assert 0.15 < changed.mean() < 0.5, changed.mean()


# -- packing --------------------------------------------------------------------


@pytest.mark.parametrize("lengths", [(5, 40, 19, 16), (40, 16, 5, 19, 3)])
def test_documents_packed_together_get_the_vectors_they_get_alone(tiny, params, lengths):
    """q_block is 16: a document shorter than a block, one over several, one
    that ends on a block's edge, a whole block, then blocks of padding."""
    cfg = _cfg(tiny, dtype=jnp.float32, token_buckets=(32, 128))
    rows = [_ids(n, tiny["vocab_size"], seed=n) for n in lengths]
    chunk = _packed(cfg, rows)
    assert chunk.ids.shape == (128,) and chunk.starts.shape == cfg.packed_row_buckets
    forward = jax.jit(lambda *args: cme.CausalMoeEmbedder(cfg, packed=True).apply(
        {"params": params}, *args))
    together, counters = forward(chunk.ids, chunk.pos, chunk.seg, chunk.starts)
    alone = np.stack([_program_rows(cfg, params, [r])[0] for r in rows])
    np.testing.assert_allclose(np.asarray(together)[: len(rows)], alone, rtol=0, atol=5e-5)
    # padding is routed nowhere, and the launch's counters are its documents' sums
    routed, _touched, _fullest_sum, _fullest, documents, tokens, bucket, pairs = (
        np.asarray(counters).tolist())
    sparse = sum(1 for m in cfg.mlp_types if m == "sparse")
    assert routed == sum(lengths) * cfg.top_k * sparse
    assert (documents, tokens, bucket) == (len(lengths), sum(lengths), 128)
    assert pairs == sum(n * (n + 1) // 2 for n in lengths)
    each = [np.asarray(cme.CausalMoeEmbedder(cfg).apply({"params": params}, *_padded([r]))[1])
            for r in rows]
    assert [int(sum(c[i] for c in each)) for i in (0, 4, 5, 7)] == [routed, documents, tokens,
                                                                   pairs]


def test_the_launch_s_counters_reach_the_recorder_without_a_sync(tiny, params):
    before_moe, before = flight_recorder.moe_stats(), flight_recorder.mla_stats()
    enc = SentenceEncoder(cfg=_cfg(tiny, token_buckets=(64, 128)),
                          max_length=tiny["max_seq_length"], params=params)
    warmed = flight_recorder.mla_stats()  # the first dispatch launches each bucket on padding
    lengths = [6, 40, 14]
    enc.encode([" ".join(f"t{i:03d}" for i in range(n)) for n in lengths])
    after_moe, after = flight_recorder.moe_stats(), flight_recorder.mla_stats()
    tokens = [n + 2 for n in lengths]  # [CLS] words [SEP]
    assert set(after_moe) == set(before_moe)  # pathway_moe_* is what it was
    launches = after["launches_total"] - before["launches_total"]
    assert launches == after_moe["launches_total"] - before_moe["launches_total"] >= 1
    assert after["documents_total"] - before["documents_total"] == len(lengths)
    assert after["tokens_total"] - before["tokens_total"] == sum(tokens)
    assert after["attention_pairs_total"] - before["attention_pairs_total"] == sum(
        n * (n + 1) // 2 for n in tokens)
    assert after["bucket_tokens_total"] - warmed["bucket_tokens_total"] >= sum(tokens)
    lines = flight_recorder.observability_metrics_lines()
    from pathway_tpu.internals.metrics_names import METRICS

    for name in ("launches_total", "documents_total", "tokens_total", "bucket_tokens_total",
                 "attention_pairs_total"):
        assert any(line.startswith(f"pathway_mla_{name} ") for line in lines)
        assert f"pathway_mla_{name}" in METRICS


# -- the config -----------------------------------------------------------------


def test_the_config_checks_a_field_only_for_the_kinds_present(tiny):
    cfg = builder.model_config(tiny)
    assert set(cfg.layer_types) == {"latent"} and cfg.router_scoring == "sigmoid"
    assert cfg.program_name == "pw_moe_embedder_forward"  # a cell is a process
    # no grouped-query layer: num_kv_heads (8 by default) need not divide 4 heads
    assert cfg.heads_per_layer == (4,) * 5 and cfg.num_kv_heads == 8
    with pytest.raises(ValueError):  # but it must where such a layer is present
        dataclasses.replace(cfg, layer_types=("full",) + ("latent",) * 4)
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, latent_rope_dim=7)
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, layer_types=("linear",) * 5)
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, router_scoring="tanh")
    shapes = jax.eval_shape(lambda: cme.init_params(cfg, jax.random.PRNGKey(0)))
    mine = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), builder.params(tiny, SEED))
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), shapes) == mine


def test_costs_count_the_published_cut(published):
    sizes = builder.sizes(published)
    counted = costs_joyai.params(sizes)
    shapes = jax.eval_shape(
        lambda: cme.init_params(builder.model_config(published), jax.random.PRNGKey(0)))
    assert counted["total"] == cme.count_params(shapes) == 5_293_376_512
    assert counted["embedding"] == 264_765_440 and counted["experts"] == 4 * 1_207_959_552
    assert costs_joyai.attention_params(sizes) == 26_345_472
    assert costs_joyai.layer_params(sizes, "dense") == 70_391_808
    assert costs_joyai.layer_params(sizes, "sparse") == 1_239_554_304
    assert costs_joyai.expert_params(sizes) == costs_laguna.expert_params(sizes) == 4_718_592
    # a sparse layer's 69.3M multiply-adds a token outside attention's pairs
    per_sparse = 26_345_472 + 524_288 + 9 * 4_718_592
    assert costs_joyai.active_params(sizes) == 26_345_472 + 3 * 2048 * 7168 + 4 * per_sparse
    # attention in the prefill form: 192 + 128 multiply-adds a pair and head
    assert costs_joyai.attention_flops(2048, sizes) == 5 * 2 * (2048 * 2049 // 2) * 32 * 320
    assert costs_joyai.forward_flops(96, sizes) == (
        2 * 96 * costs_joyai.active_params(sizes) + costs_joyai.attention_flops(96, sizes))
    # the grouped product's readers serve the cell unedited
    assert costs_laguna.grouped_matmul_flops(8, sizes) == 2 * 8 * 4_718_592
