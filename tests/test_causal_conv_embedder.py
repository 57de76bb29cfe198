"""The causal embedder's conv layers (LFM2's gated short convolution),
grouped-query attention with per-head q/k norms and no output gate, and
sparse layers with no shared expert, against their plain reference,
``perfbench/checks/lfm2.py`` (one source: the benchmark's own reference is
imported, not copied).

Everything runs the configuration file's tiny ``rehearse`` preset on the CPU:
5 layers (conv/dense, full/sparse, three conv/sparse), hidden 64, 4 query
heads on 2 KV heads of 16, a 3-tap convolution, 16 experts top-4 of width 32
and no shared expert, dense width 128, vocabulary 512.

Tolerances, and why (``tests/test_causal_moe_embedder.py`` has the argument
in full; the readings here are this model's).  A ``float32`` program
(``dtype=float32``) must match the float32 reference to ``F32_TOL``: what is
left is the order of float32 sums (read: 1.5e-7 to 3.7e-7 a layer).  That
pins the mathematics: the order ``B | C | h``, the taps and their reset at a
document's start, the q and k norms, the missing gate, the bias in the choice
and not in the weights, the normaliser's eps; each control below breaks one
of them and has to FAIL it.  The program as deployed (bfloat16 operands) is
held per layer, fed the reference's own input, to ``BF16_LAYER_TOL`` of the
layer's addition for the MEDIAN token (read: 0.0034-0.0052); against the
reference at the STATED precision it differs by the order of float32 sums
alone (read: 6e-8 to 2.3e-7 at the median, one token of 40 at 1.5e-5 where
a router near-tie fell the other way; ``STATED_LAYER_TOL``), and the reference
one step of precision down (``lowered``) lies 0.010-0.032 from the stated one
and has to FAIL that tolerance five hundredfold.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import costs_laguna  # noqa: E402
import costs_lfm2  # noqa: E402
from checks import lfm2 as reference  # noqa: E402
from encoders import lfm2 as builder  # noqa: E402

from pathway_tpu.internals import flight_recorder  # noqa: E402
from pathway_tpu.models import causal_moe_embedder as cme  # noqa: E402
from pathway_tpu.models.encoder import SentenceEncoder, ragged_prepare  # noqa: E402
from pathway_tpu.ops import routed_experts as rx  # noqa: E402

SEED = 2147483659
CELL = "ingest-docs-lfm2"
CONFIG = "vs-lfm2-24b-a2b-bf16-marcodoc"
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
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
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
    return np.asarray(cme.CausalMoeEmbedder(cfg).layer(p, layer, jnp.asarray(x)))


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


@functools.lru_cache(maxsize=None)
def _dense_program(cfg):
    model = cme.CausalMoeEmbedder(cfg)
    return jax.jit(lambda p, ids, mask: model.apply({"params": p}, ids, mask))


def _program_rows(cfg, params, rows):
    """The dense forward over token rows padded to one width: [n, D]."""
    out, _counters = _dense_program(cfg)(params, *_padded(rows))
    return np.asarray(out)


@functools.lru_cache(maxsize=None)
def _packed_program(cfg):
    model = cme.CausalMoeEmbedder(cfg, packed=True)
    return jax.jit(lambda p, *a: model.apply({"params": p}, *a))


def _packed_forward(cfg, params, rows):
    """``rows`` (token id arrays) as ONE packed launch of ``cfg``: the
    vectors [n, D] and the launch's counters."""
    ids, mask = _padded(rows)
    prepared, _stats = ragged_prepare(ids, mask, ids.shape[1], vocab_size=cfg.vocab_size, cfg=cfg)
    assert len(prepared) == 1
    chunk = prepared[0][0]
    vectors, counters = _packed_program(cfg)(params, chunk.ids, chunk.pos, chunk.seg,
                                             chunk.starts)
    return np.asarray(vectors)[: len(rows)], np.asarray(counters).tolist(), chunk


# -- each layer kind, and the whole forward -------------------------------------


@pytest.mark.parametrize("layer,kind", [(0, "conv, dense MLP"),
                                        (1, "full attention with q/k norms, routed experts"),
                                        (2, "conv, routed experts"),
                                        (4, "conv, routed experts, last")])
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
    # the same layer with the gates, taps, router and norms in bfloat16 is five
    # hundred times further
    stated, low = (_reference_layer(tiny, layer, p, x, precision)
                   for precision in ("stated", "lowered"))
    assert np.median(_token_errors(got, stated, x)) < STATED_LAYER_TOL
    assert np.sum(_token_errors(got, stated, x) > STATED_LAYER_TOL) <= 2
    assert np.median(_token_errors(low, stated, x)) > 500 * STATED_LAYER_TOL
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


def test_the_reference_runs_its_mlp_over_blocks_of_real_tokens(tiny, params, monkeypatch):
    """Departure (ii): blocks of any size give the same layer, and padding
    behind a text changes nothing in it."""
    x = _reference_states(tiny, _ids(37, tiny["vocab_size"]))[2]
    p = params["layer_2"]
    whole = _reference_layer(tiny, 2, p, x)
    monkeypatch.setattr(reference, "TOKEN_BLOCK", 8)  # five blocks, the last one padded
    blocks = _reference_layer(tiny, 2, p, x)
    assert whole.shape == blocks.shape == x.shape
    np.testing.assert_allclose(blocks, whole, rtol=1e-5, atol=1e-4)  # float32 sums, values of tens
    st = reference.layer_statics(tiny, 2)
    with jax.default_matmul_precision("highest"):
        longer = np.asarray(reference.layer_forward(
            p, jnp.asarray(x), st["freq"], **dict(st["kw"], max_len=80)))
    np.testing.assert_allclose(longer, whole, rtol=1e-5, atol=1e-4)


# -- the gated short convolution ------------------------------------------------


def test_the_conv_mixer_is_the_gated_short_convolution_by_hand(tiny, params):
    """``(C * conv(B * h)) W_out`` with ``[B | C | h] = a W_in`` in that
    order and tap ``k`` on ``u[t - (K - 1 - k)]``, recomputed from the
    layer's matrices with numpy."""
    cfg = _cfg(tiny, dtype=jnp.float32)
    p = jax.tree_util.tree_map(lambda w: np.asarray(w, np.float64), params["layer_2"])
    t, d = 11, cfg.hidden_dim
    a = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (t, d), jnp.float32))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(cme._conv_mixer(cfg, params["layer_2"], jnp.asarray(a), jnp.arange(t)))
    bch = a @ p["w_in"]
    b, c, h = bch[:, :d], bch[:, d: 2 * d], bch[:, 2 * d:]
    u = b * h
    taps = p["conv"].shape[0]
    assert taps == cfg.conv_taps == 3
    v = np.stack([sum(p["conv"][k] * u[i - (taps - 1 - k)] for k in range(taps)
                      if i - (taps - 1 - k) >= 0) for i in range(t)])
    want = (c * v) @ p["w_out"]
    assert _rel(got, want) < F32_TOL
    # the control that splits W_in as C | B | h is another function
    swapped = dict(params["layer_2"], w_in=jnp.concatenate(
        [params["layer_2"]["w_in"][:, d: 2 * d], params["layer_2"]["w_in"][:, :d],
         params["layer_2"]["w_in"][:, 2 * d:]], axis=1))
    with jax.default_matmul_precision("highest"):
        control = np.asarray(cme._conv_mixer(cfg, swapped, jnp.asarray(a), jnp.arange(t)))
    assert _rel(control, want) > 1000 * F32_TOL


@pytest.mark.parametrize("before", [3, 6, 7, 14, 30, 62])
def test_a_documents_vector_is_the_same_alone_and_packed_after_any_length(
        tiny, params, before):
    """The conv's taps reset at a document's start on the packed axis: a
    document packed after one of each length of the cycle (words + 2 tokens)
    gets the vector it gets alone, and so does the one before it."""
    cfg = _cfg(tiny, dtype=jnp.float32, token_buckets=(128,))
    target = _ids(24, tiny["vocab_size"], seed=99)
    first = _ids(before + 2, tiny["vocab_size"], seed=before)
    together, counters, chunk = _packed_forward(cfg, params, [first, target])
    assert chunk.ids.shape == (128,)
    alone = _program_rows(cfg, params, [first])[0], _program_rows(cfg, params, [target])[0]
    np.testing.assert_allclose(together[0], alone[0], rtol=0, atol=5e-5)
    np.testing.assert_allclose(together[1], alone[1], rtol=0, atol=5e-5)
    # the control whose taps read across the border (positions of one document)
    leaky = np.asarray(cme._conv_mixer(
        cfg, params["layer_0"], jnp.asarray(np.ones((len(first) + 24, 64), np.float32)),
        jnp.arange(len(first) + 24)))
    reset = np.asarray(cme._conv_mixer(
        cfg, params["layer_0"], jnp.asarray(np.ones((len(first) + 24, 64), np.float32)),
        jnp.concatenate([jnp.arange(len(first)), jnp.arange(24)])))
    assert np.abs(leaky[len(first)] - reset[len(first)]).max() > 1e-3
    np.testing.assert_array_equal(leaky[len(first) + 2:], reset[len(first) + 2:])


@pytest.mark.parametrize("lengths", [(5, 64, 9, 16), (32, 8, 5, 64, 3)])
def test_documents_packed_together_get_the_vectors_they_get_alone(tiny, params, lengths):
    """q_block is 16: a document shorter than a block, one over several, one
    that ends on a block's edge, a whole block, then blocks of padding; the
    launch's counters are its documents' sums."""
    cfg = _cfg(tiny, dtype=jnp.float32, token_buckets=(32, 128))
    rows = [_ids(n, tiny["vocab_size"], seed=n) for n in lengths]
    together, counters, chunk = _packed_forward(cfg, params, rows)
    assert chunk.starts.shape == cfg.packed_row_buckets
    alone = np.stack([_program_rows(cfg, params, [r])[0] for r in rows])
    np.testing.assert_allclose(together, alone, rtol=0, atol=5e-5)
    # padding is routed nowhere; the conv model counts documents, tokens and its bucket
    assert len(counters) == 7
    routed, _touched, _fullest_sum, _fullest, documents, tokens, bucket = counters
    sparse = sum(1 for m in cfg.mlp_types if m == "sparse")
    assert routed == sum(lengths) * cfg.top_k * sparse
    assert (documents, tokens, bucket) == (len(lengths), sum(lengths), 128)


# -- grouped-query attention with q/k norms and no gate -------------------------


def test_the_q_and_k_norms_apply_and_no_gate_is_built(tiny, params):
    """A full layer of this model normalises each head of q and k (scales of
    ``head_dim``) and has no ``wg``; the same layer without the norms is far
    outside the tolerance the layer meets, and a gate put back is too."""
    cfg = _cfg(tiny, dtype=jnp.float32)
    assert cfg.qk_norm and not cfg.attention_gate and cfg.layer_types[1] == "full"
    p = params["layer_1"]
    assert "wg" not in p and p["q_norm"].shape == p["k_norm"].shape == (cfg.head_dim,)
    states = _reference_states(tiny, _ids(40, tiny["vocab_size"]))
    x, want = states[1], states[2]
    assert _rel(_program_layer(cfg, p, 1, x) - x, want - x) < F32_TOL
    no_norm = _program_layer(dataclasses.replace(cfg, qk_norm=False), p, 1, x)
    assert _rel(no_norm - x, want - x) > 1000 * F32_TOL
    gated = dict(p, wg=jnp.zeros((cfg.hidden_dim, cfg.heads_per_layer[1]), jnp.bfloat16))
    halved = _program_layer(dataclasses.replace(cfg, attention_gate=True), gated, 1, x)
    assert _rel(halved - x, want - x) > 1000 * F32_TOL  # sigmoid(0) halves the attention


def test_laguna_s_defaults_keep_the_gate_and_take_no_norms():
    """The new fields default to what the other configurations run."""
    cfg = cme.CausalMoeEmbedderConfig(
        hidden_dim=64, head_dim=16, num_kv_heads=2, heads_per_layer=(4,) * 5, num_experts=8,
        top_k=2, expert_dim=16, shared_expert_dim=16, dense_mlp_dim=32, vocab_size=64)
    assert cfg.attention_gate and not cfg.qk_norm and cfg.router_eps == 1e-20
    tree = jax.eval_shape(lambda: cme.init_params(cfg, jax.random.PRNGKey(0)))
    assert "wg" in tree["layer_0"] and "q_norm" not in tree["layer_0"]
    assert "shared" in tree["layer_1"]["moe"]


# -- the router and the experts -------------------------------------------------


def test_a_sparse_layer_with_no_shared_expert_adds_the_routed_experts_alone(tiny, params):
    cfg = _cfg(tiny, dtype=jnp.float32)
    assert cfg.shared_expert_dim == 0
    tree = jax.eval_shape(lambda: cme.init_params(cfg, jax.random.PRNGKey(0)))
    assert all("shared" not in tree[f"layer_{i}"].get("moe", {}) for i in range(5))
    p = params["layer_2"]
    t = 20
    x = jax.random.normal(jax.random.PRNGKey(4), (t, cfg.hidden_dim), jnp.float32)
    with jax.default_matmul_precision("highest"):
        h = x + cme._conv_mixer(cfg, p, cme._rms_norm(x, p["attn_norm"], cfg.rms_eps),
                                jnp.arange(t))
        b = cme._rms_norm(h, p["mlp_norm"], cfg.rms_eps)
        routed, _sizes = rx.routed_experts(
            b, jnp.ones((t,), bool), p["moe"]["router"], p["moe"]["w_gate_up"],
            p["moe"]["w_down"], top_k=cfg.top_k, scaling=cfg.routed_scaling,
            scoring="sigmoid", bias=p["moe"]["bias"], eps=cfg.router_eps)
        want = np.asarray(h + routed)
        got = _program_layer(cfg, p, 2, x)
    assert _rel(got, want) < F32_TOL


def test_the_router_s_normaliser_eps_is_the_configuration_s():
    """Sigmoid scores near 1e-9 (logits of -20): the eps of the sum decides
    the weights.  1e-20 renormalises them to 1/k each, 1e-6 leaves them near
    zero; at ordinary scores the two agree to the eps's order."""
    k = 4
    logits = np.full(16, -20.0) - 0.01 * np.arange(16)
    x, router = jnp.asarray(logits, jnp.float32)[None], jnp.eye(16, dtype=jnp.float32)
    s = 1.0 / (1.0 + np.exp(-logits[:k]))
    _e, deepseek = rx.route(x, router, top_k=k, scaling=1.0, scoring="sigmoid")
    _e, lfm2 = rx.route(x, router, top_k=k, scaling=1.0, scoring="sigmoid", eps=1e-6)
    np.testing.assert_allclose(np.asarray(deepseek)[0], s / s.sum(), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(lfm2)[0], s / (s.sum() + 1e-6), rtol=1e-5)
    assert float(np.asarray(lfm2).sum()) < 1e-2 < float(np.asarray(deepseek).sum())
    _e, plain = rx.route(x + 20.0, router, top_k=k, scaling=1.0, scoring="sigmoid")
    _e, eps6 = rx.route(x + 20.0, router, top_k=k, scaling=1.0, scoring="sigmoid", eps=1e-6)
    np.testing.assert_allclose(np.asarray(eps6), np.asarray(plain), rtol=1e-6)


def test_the_configuration_s_eps_reaches_the_layer(tiny, params, monkeypatch):
    """``topk_norm_eps`` of the file is the program's ``router_eps``, which
    every routed layer hands the router, and the reference's."""
    cfg = _cfg(tiny, dtype=jnp.float32)
    assert cfg.router_eps == tiny["topk_norm_eps"] == 1e-6
    assert reference.layer_statics(tiny, 2)["kw"]["norm_eps"] == 1e-6
    seen = []

    def spy(*args, **kw):
        seen.append(kw["eps"])
        return rx.routed_experts(*args, **kw)

    monkeypatch.setattr(cme, "routed_experts", spy)
    _program_layer(cfg, params["layer_2"], 2, np.ones((8, cfg.hidden_dim), np.float32))
    assert seen == [1e-6]


def test_the_seeded_bias_changes_the_choice_for_some_tokens_in_ten(published):
    """At the PUBLISHED router's size (2,048 -> 64 experts, top 4) with the
    encoder's gain and bias scale, over unit-RMS inputs: the share the
    configuration file's ``assumed`` states."""
    d, e, k = published["hidden_size"], published["num_experts"], 4
    small = dict(published, intermediate_size=8, moe_intermediate_size=8, num_attention_heads=1,
                 num_key_value_heads=1, hidden_size=d)
    p = builder.layer_params(small, SEED, 3)["moe"]
    assert p["router"].shape == (d, e) and p["bias"].dtype == jnp.float32
    x = jax.random.normal(jax.random.PRNGKey(7), (2048, d), jnp.float32)
    with_bias, _ = rx.route(x, p["router"], top_k=k, scaling=1.0, scoring="sigmoid",
                            bias=p["bias"], eps=1e-6)
    without, _ = rx.route(x, p["router"], top_k=k, scaling=1.0, scoring="sigmoid", eps=1e-6)
    changed = np.mean(np.sort(np.asarray(with_bias), 1) != np.sort(np.asarray(without), 1),
                      axis=1) > 0
    assert 0.15 < changed.mean() < 0.5, changed.mean()


# -- counters, config, costs ----------------------------------------------------


def test_the_launch_s_conv_counters_reach_the_recorder_without_a_sync(tiny, params):
    before_moe, before = flight_recorder.moe_stats(), flight_recorder.conv_stats()
    before_mla = flight_recorder.mla_stats()
    enc = SentenceEncoder(cfg=_cfg(tiny, token_buckets=(64, 128)),
                          max_length=tiny["max_seq_length"], params=params)
    warmed = flight_recorder.conv_stats()  # the first dispatch launches each bucket on padding
    lengths = [6, 40, 14]
    enc.encode([" ".join(f"t{i:03d}" for i in range(n)) for n in lengths])
    after_moe, after = flight_recorder.moe_stats(), flight_recorder.conv_stats()
    tokens = [n + 2 for n in lengths]  # [CLS] words [SEP]
    assert flight_recorder.mla_stats() == before_mla  # a conv model counts no latent launch
    launches = after["launches_total"] - before["launches_total"]
    assert launches == after_moe["launches_total"] - before_moe["launches_total"] >= 1
    assert after["documents_total"] - before["documents_total"] == len(lengths)
    assert after["tokens_total"] - before["tokens_total"] == sum(tokens)
    assert after["bucket_tokens_total"] - warmed["bucket_tokens_total"] >= sum(tokens)
    lines = flight_recorder.observability_metrics_lines()
    from pathway_tpu.internals.metrics_names import METRICS

    for name in ("launches_total", "documents_total", "tokens_total", "bucket_tokens_total"):
        assert any(line.startswith(f"pathway_conv_{name} ") for line in lines)
        assert f"pathway_conv_{name}" in METRICS


def test_the_config_checks_the_new_kind(tiny):
    cfg = builder.model_config(tiny)
    assert cfg.layer_types == ("conv", "full", "conv", "conv", "conv")
    assert cfg.mlp_types == ("dense",) + ("sparse",) * 4 and cfg.conv_taps == 3
    assert cfg.program_name == "pw_moe_embedder_forward"  # a cell is a process
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, conv_taps=0)
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, layer_types=("short_conv",) * 5)
    with pytest.raises(ValueError):  # a grouped-query layer still needs whole groups
        dataclasses.replace(cfg, num_kv_heads=3)
    with pytest.raises(ValueError):  # what the program does not build is refused
        builder.model_config(dict(tiny, conv_bias=True))
    shapes = jax.eval_shape(lambda: cme.init_params(cfg, jax.random.PRNGKey(0)))
    mine = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), builder.params(tiny, SEED))
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), shapes) == mine


def test_costs_count_the_published_cut(published):
    sizes = builder.sizes(published)
    assert sizes["layer_types"] == ["conv", "conv", "full", "conv", "conv", "conv",
                                    "full", "conv", "conv", "conv"]
    assert sizes["mlp_types"] == ["dense"] * 2 + ["sparse"] * 8
    counted = costs_lfm2.params(sizes)
    shapes = jax.eval_shape(
        lambda: cme.init_params(builder.model_config(published), jax.random.PRNGKey(0)))
    # 5,267,089,664 without the eight sparse layers' selection biases of 64
    assert counted["total"] == cme.count_params(shapes) == 5_267_089_664 + 8 * 64
    assert counted["embedding"] == 134_217_728 and counted["experts"] == 8 * 603_979_776
    assert costs_lfm2.conv_params(sizes) == 16_783_360
    assert costs_lfm2.attention_params(sizes) == 10_485_888
    assert costs_lfm2.layer_params(sizes, "conv", "dense") == 16_783_360 + 4096 + 72_351_744
    assert costs_lfm2.expert_params(sizes) == costs_laguna.expert_params(sizes) == 9_437_184
    per_sparse = 131_072 + 4 * 9_437_184
    assert costs_lfm2.active_params(sizes) == (
        8 * 16_783_360 + 2 * (10_485_888 - 128) + 2 * 72_351_744 + 8 * per_sparse)
    # attention on the two full layers: two products of 64 a pair and query head
    assert costs_lfm2.attention_flops(2048, sizes) == 2 * 2 * 2 * (2048 * 2049 // 2) * 32 * 64
    # about 1.2 GFLOP a token at the cycle's mean of 731 causal pairs a token
    flops = costs_lfm2.forward_flops(837, sizes) / 837
    assert 1.1e9 < flops < 1.3e9
    assert costs_laguna.grouped_matmul_flops(4, sizes) == 2 * 4 * 9_437_184


# -- the cell -------------------------------------------------------------------


def test_the_cell_is_declared_with_its_configuration_traffic_and_metrics(published):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 and cell["config"] == CONFIG
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "rows"] and len(entry["why"]) <= 200
    assert entry["source"] == "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    # the published widths, uncut, and the cut that is stated
    want = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
    assert {k: published[k] for k in want} == want
    assert published["layer_types"] == ["conv", "conv"] + [
        "full_attention", "conv", "conv", "conv"] * 9 + ["full_attention", "conv"]
    assert published["layer_types"].count("full_attention") == 10  # 30 conv, 10 attention
    assert published["num_hidden_layers"] == 10 and published["published_num_hidden_layers"] == 40
    assert published["reduced"] == entry["reduced"] and "5,267,090,176" in published["why_reduced"]
    assert published["rows"] == 61440 and published["index"]["capacity"] == 65536
    assert published["index"]["dim"] == published["hidden_size"]
    assert (published["server"], published["check"]) == ("vector_store_lfm2", "ingest_laguna")
    assert published["embedder"] == {"builder": "lfm2", "reference": "lfm2"}
    assert "four pipeline stages" in published["deployment"]
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["generator"] == "file_drop_docs" and traffic["poll_ms"] == 10
    assert traffic["rate_per_s"] == int(traffic["rate_per_s"]) > 0
    reported = {m["name"] for group in ("end_to_end", "per_layer") for m in bench[group]
                if "workloads" not in m or CELL in m["workloads"]}
    assert {"fresh_p95_ms", "setup_s", "ingest_conv_step.mfu", "conv.padding_share",
            "moe.grouped_matmul_roofline", "moe.tokens_per_expert", "moe.load_max_over_mean",
            "embed.docs_per_launch", "embed_moe.device_ms_per_launch",
            "idle.attributed", "index.apply_ms", "ingest.step_to_index_ms"} <= reported
    assert not {"ingest_moe_step.mfu", "ingest_mla_step.mfu", "mla.padding_share"} & reported
    for name in reported - {"setup_s"}:  # every metric the cell reports has its reader
        kind = "end_to_end" if name == "fresh_p95_ms" else "layer_metrics"
        assert os.path.exists(os.path.join(BENCH, kind, name + ".py")), name
    for name in ("ingest_conv_step.mfu", "conv.padding_share"):
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "fresh_p95_ms"


def test_a_traced_rehearsal_of_the_cell_is_correct_and_prints_its_counters():
    """``perfbench/run.py --rehearse`` end to end on the CPU: the conv
    embedder behind ``SentenceEncoder`` -> ``SentenceTransformerEmbedder`` ->
    ``VectorStoreServer`` over a watched directory, the packed dispatch, the
    staged upsert, and the check that decides ``correct``."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(SEED), "--seconds", "3", "--trace", "1", "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 12
    assert line["device"]["platform"] == "cpu"  # a CPU run cannot pass for a chip run
    compared = line["compared"]
    for name in ("files_not_counted", "files_lost", "files_doubled", "own_text_not_first_once"):
        assert compared[name] == {"value": 0, "limit": 0}
    assert compared["answers_compared"]["value"] == 6
    assert compared["layer_gap"]["value"] < 1e-5 < compared["layer_gap"]["limit"]
    metrics = line["metrics"]
    # counters are counts on any platform; times, shares of a peak and rooflines
    # come from a chip alone, and their readers return nothing here
    assert metrics["embed.docs_per_launch"]["value"] >= 1.0
    assert 0.0 <= metrics["conv.padding_share"]["value"] < 100.0
    assert metrics["moe.tokens_per_expert"]["value"] > 0
    for name in ("embed_moe.device_ms_per_launch", "moe.grouped_matmul_roofline",
                 "ingest_conv_step.mfu", "idle.attributed", "mla.padding_share",
                 "ingest_mla_step.mfu"):
        assert name not in metrics
    assert math.isfinite(metrics["ingest.fresh_p50_ms"]["value"])
