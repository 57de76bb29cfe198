"""The hybrid language-model embedder (attention and a Mamba-2 mixer in
parallel on one norm, scalar multipliers throughout) against its plain
reference, ``perfbench/checks/falcon_h1.py`` (one source: the benchmark's own
reference is imported, not copied), which computes the recurrence token by
token and imports nothing of the program.

Everything runs the configuration file's tiny ``rehearse`` preset on the CPU:
4 layers, hidden 64, 4 query heads on 2 KV heads of 16, 4 mixer heads of 16
channels over 2 groups with a state of 16, 4 taps, MLP width 96, vocabulary
512, a chunk of 3 tokens (smaller than any document and dividing none), the
published multipliers.

Tolerances, and why.  A ``float32`` program (``dtype=float32``: the same
code, products not rounded) must match the float32 reference to ``F32_TOL``:
what is left is the order of float32 sums (read here: 2e-7 to 9e-7 of the
largest state).  That is what pins the mathematics: the zones of ``W_in``,
each multiplier's place, the convolution's taps, the resets, the grouped
norm, rotary.  The program as it is deployed (bfloat16 operands in the
matrices, float32 everything else) is held per layer, fed the reference's
own input, to ``STATED_LAYER_TOL`` of the layer's addition for the median
token against the reference computed AT the stated precision
(``precision="stated"``): it differs by the order of float32 sums alone
(read here: 8e-8 to 1.1e-7; a sum that falls the other side of a bfloat16
rounding moves a single token by up to 1e-3, hence the median).  The
reference one step down (``precision="lowered"``: state, decays,
convolution, gate, norms, softmax and residual stream in bfloat16 too) lies
0.008-0.011 from the stated one, a thousand times past that tolerance, and
has to FAIL it.
"""

from __future__ import annotations

import dataclasses
import json
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

import costs_falcon_h1  # noqa: E402
from checks import falcon_h1 as reference  # noqa: E402
from encoders import falcon_h1 as builder  # noqa: E402

from pathway_tpu.internals import flight_recorder  # noqa: E402
from pathway_tpu.models import causal_hybrid_embedder as che  # noqa: E402
from pathway_tpu.models.encoder import SentenceEncoder, ragged_prepare  # noqa: E402

SEED = 2147483659
F32_TOL = 5e-6  # relative to the largest state; float32 sums in another order
STATED_LAYER_TOL = 1e-5  # the median token against the stated-precision reference


def _merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merged(base[k], v) if isinstance(v, dict) and isinstance(base.get(k), dict) else v
    return out


@pytest.fixture(scope="module")
def published() -> dict:
    with open(os.path.join(BENCH, "configs", "vs-falcon-h1-34b-bf16-marcodoc.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny(published) -> dict:
    return _merged(published, published["rehearse"])


@pytest.fixture(scope="module")
def params(tiny):
    return builder.params(tiny, SEED)


def _ids(n: int, vocab: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.concatenate([[1], rng.integers(4, vocab, n - 2), [2]]).astype(np.int32)


def _reference_states(config, ids, precision="float32", weights=None):
    """The reference's residual stream before each layer and after the last
    (one document); ``weights``: the configuration the weights are made from,
    where it is not ``config``."""
    weights = weights or config
    emb = builder.embedding_params(weights, SEED)
    x = np.asarray(emb["tok_emb"][jnp.asarray(ids)].astype(jnp.float32)
                   * float(config["embedding_multiplier"]))
    states = [x]
    with jax.default_matmul_precision("highest"):
        for layer in range(int(config["num_hidden_layers"])):
            st = reference.layer_statics(config, layer)
            x = np.asarray(reference.layer_forward(
                builder.layer_params(weights, SEED, layer), jnp.asarray(x), st["freq"],
                precision=precision, **st["kw"]).astype(jnp.float32))
            states.append(x)
    return states


def _reference_vector(config, ids, weights=None):
    x = _reference_states(config, ids, weights=weights)[-1][-1]
    return x / np.sqrt(np.mean(x * x) + float(config["rms_norm_eps"]))


def _dense(cfg, params, rows):
    """The dense forward over ``rows`` (lists of ids), padded behind."""
    s = max(len(r) for r in rows)
    ids = np.zeros((len(rows), s), np.int32)
    mask = np.zeros((len(rows), s), np.int32)
    for i, r in enumerate(rows):
        ids[i, : len(r)], mask[i, : len(r)] = r, 1
    out, _counters = jax.jit(che.CausalHybridEmbedder(cfg).apply)({"params": params}, ids, mask)
    return np.asarray(out)


def _packed(cfg, params, rows, t: int):
    """The packed forward over ``rows`` end to end on an axis of ``t``."""
    n = 8
    ids, pos, seg = np.zeros(t, np.int32), np.zeros(t, np.int32), np.full(t, n, np.int32)
    starts, off = np.zeros(n, np.int32), 0
    for j, r in enumerate(rows):
        ids[off: off + len(r)], pos[off: off + len(r)], seg[off: off + len(r)] = r, np.arange(len(r)), j
        starts[j] = off
        off += len(r)
    out, counters = jax.jit(che.CausalHybridEmbedder(cfg, packed=True).apply)(
        {"params": params}, ids, pos, seg, starts)
    return np.asarray(out)[: len(rows)], np.asarray(counters)


def _layer(cfg, layer_params, layer: int, x):
    return np.asarray(jax.jit(che.CausalHybridEmbedder(cfg).layer, static_argnums=1)(
        layer_params, layer, x))


def _median_layer_error(got, want, x):
    return float(np.median(np.linalg.norm(got - want, axis=1) / np.linalg.norm(want - x, axis=1)))


def test_the_float32_program_matches_the_reference_layer_by_layer_and_end_to_end(tiny, params):
    cfg = dataclasses.replace(builder.model_config(tiny), dtype=jnp.float32)
    ids = _ids(40, cfg.vocab_size)
    states = _reference_states(tiny, ids)
    for layer in range(cfg.num_layers):  # ``layer`` fed the reference's own input
        got = _layer(cfg, params[f"layer_{layer}"], layer, states[layer])
        assert np.abs(got - states[layer + 1]).max() <= F32_TOL * np.abs(states[layer + 1]).max()
    want = _reference_vector(tiny, ids)
    dense = _dense(cfg, params, [ids])[0]
    packed, _ = _packed(cfg, params, [ids], 64)
    for got in (dense, packed[0]):
        assert np.abs(got - want).max() <= 4 * F32_TOL * np.abs(want).max()


@pytest.mark.parametrize("length", [5, 11, 64])
def test_the_deployed_precision_is_told_from_the_one_below_it(tiny, params, length):
    """Document lengths of the preset (the chunk of 3 divides none):
    the bfloat16 program within ``STATED_LAYER_TOL`` of the stated-precision
    reference layer by layer, the lowered reference a thousand times out."""
    cfg = builder.model_config(tiny)
    ids = _ids(length, cfg.vocab_size, seed=length)
    states = _reference_states(tiny, ids, "stated")
    for layer in range(cfg.num_layers):
        p, x = params[f"layer_{layer}"], states[layer]
        got = _layer(cfg, p, layer, x)
        assert _median_layer_error(got, states[layer + 1], x) <= STATED_LAYER_TOL
        # what the cell's ``layer_gap`` calls: the text behind a neighbour on a packed axis
        flushed = np.asarray(builder.program_layer(tiny, layer, p, jnp.asarray(x)))
        assert _median_layer_error(flushed, states[layer + 1], x) <= STATED_LAYER_TOL
        st = reference.layer_statics(tiny, layer)
        with jax.default_matmul_precision("highest"):
            low = np.asarray(reference.layer_forward(
                p, jnp.asarray(x), st["freq"], precision="lowered", **st["kw"]
            ).astype(jnp.float32))
        assert _median_layer_error(low, states[layer + 1], x) > 100 * STATED_LAYER_TOL


@pytest.mark.parametrize("length,before,behind", [(5, 4, 2), (11, 7, 0), (40, 17, 5)])
def test_a_layer_over_a_packed_axis_gives_a_text_what_it_gets_alone(
        tiny, params, length, before, behind):
    """``layer`` with ``seg`` and ``pos``: a neighbour before the text (its
    border inside a chunk of 3 and a query block of 16) and padding behind
    leave no trace in the text's rows."""
    cfg = dataclasses.replace(builder.model_config(tiny), dtype=jnp.float32)
    x = _reference_states(tiny, _ids(length, cfg.vocab_size, seed=length))[0]
    other = _reference_states(tiny, _ids(before, cfg.vocab_size, seed=99))[0] * 3.0
    axis = np.concatenate([other, x, np.ones((behind, x.shape[1]), np.float32)])
    seg = np.concatenate([np.zeros(before), np.ones(length), -np.ones(behind)]).astype(np.int32)
    pos = np.concatenate([np.arange(before), np.arange(length), np.zeros(behind)]).astype(np.int32)
    model = che.CausalHybridEmbedder(cfg)
    for layer in (0, cfg.num_layers - 1):
        p = params[f"layer_{layer}"]
        alone = _layer(cfg, p, layer, x)
        packed = np.asarray(jax.jit(model.layer, static_argnums=1)(p, layer, axis, seg, pos))
        assert np.abs(packed[before: before + length] - alone).max() <= F32_TOL * np.abs(alone).max()
        assert np.abs(packed[:before] - _layer(cfg, p, layer, other)).max() \
            <= F32_TOL * np.abs(packed[:before]).max()


@pytest.mark.parametrize("lengths,t", [((5, 32, 11, 16), 64)])
def test_documents_packed_together_get_the_vectors_of_the_dense_forward_alone(
        tiny, params, lengths, t):
    """State, convolution and attention all kept apart: borders fall inside
    chunks of 3 and inside attention's query blocks of 16."""
    cfg = dataclasses.replace(builder.model_config(tiny), dtype=jnp.float32)
    rows = [_ids(n, cfg.vocab_size, seed=i) for i, n in enumerate(lengths)]
    packed, counters = _packed(cfg, params, rows, t)
    for got, row in zip(packed, rows):
        alone = _dense(cfg, params, [row])[0]
        assert np.abs(got - alone).max() <= 4 * F32_TOL * np.abs(alone).max()
    assert counters.tolist() == [1, len(lengths), sum(lengths), t]
    together = _dense(cfg, params, rows)  # the dense layout, padding behind each row
    assert np.abs(together - packed).max() <= 4 * F32_TOL * np.abs(packed).max()


def test_a_flush_of_mixed_lengths_through_the_encoder_equals_the_documents_one_at_a_time(
        tiny, params):
    from generators import file_drop_docs

    cfg = builder.model_config(tiny)
    enc = SentenceEncoder(cfg=cfg, max_length=tiny["max_seq_length"], params=params)
    texts = [file_drop_docs.document(i, SEED, tiny["document_words"]) for i in range(7)]
    before = flight_recorder.ssm_stats()
    together = enc.encode(texts)
    after = flight_recorder.ssm_stats()
    # 5 + 7 + 11 + 16 + 32 + 64 + 5 tokens: one launch of 256, beside the three
    # launches of padding that warm the token buckets
    assert after["launches_total"] - before["launches_total"] == 1 + len(cfg.token_buckets)
    assert after["documents_total"] - before["documents_total"] == 7
    assert after["tokens_total"] - before["tokens_total"] == 140
    assert after["bucket_tokens_total"] - before["bucket_tokens_total"] == 256 + sum(cfg.token_buckets)
    alone = np.concatenate([enc.encode([t]) for t in texts])
    # bfloat16 operands: a float32 sum taken in another order (another offset
    # in a chunk, another query block) can fall the other side of a rounding
    # (2^-8 of an operand; at hidden 64 one such flip is a few thousandths of a vector)
    assert np.abs(together - alone).max() < 0.02 * np.abs(alone).max()
    exact = SentenceEncoder(cfg=dataclasses.replace(cfg, dtype=jnp.float32),
                            max_length=tiny["max_seq_length"], params=params)
    assert np.abs(exact.encode(texts) - np.concatenate([exact.encode([t]) for t in texts])
                  ).max() < 2e-5
    want = np.stack([_reference_vector(tiny, reference.tokenize(
        t, cfg.vocab_size, tiny["max_seq_length"])) for t in texts])
    want /= np.linalg.norm(want, axis=1, keepdims=True)
    got = exact.encode(texts)
    assert np.abs(got / np.linalg.norm(got, axis=1, keepdims=True) - want).max() < 2e-5
    lines = flight_recorder.observability_metrics_lines()
    for name in ("launches_total", "documents_total", "tokens_total", "bucket_tokens_total"):
        assert any(line.startswith(f"pathway_ssm_{name} ") for line in lines)


MULTIPLIERS = ["embedding_multiplier", "attention_in_multiplier", "attention_out_multiplier",
               "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
               "mlp_multipliers:0", "mlp_multipliers:1"] + [f"ssm_multipliers:{z}" for z in range(5)]


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_moves_the_output_and_sits_where_the_reference_has_it(
        tiny, params, name):
    """Another value of one multiplier, in the configuration the program and
    the reference both read: they still agree, and the vector has moved (a
    multiplier dropped from the program would leave it where it was and
    break the agreement)."""
    key, _, index = name.partition(":")
    tiny = dict(tiny, num_hidden_layers=1)  # one block holds every multiplier
    params = {k: v for k, v in params.items() if k in ("tok_emb", "final_norm", "layer_0")}
    config = json.loads(json.dumps(tiny))
    if index:
        config[key][int(index)] *= 1.7
    else:
        config[key] *= 1.7
    ids = _ids(21, int(tiny["vocab_size"]), seed=3)
    base = _reference_vector(tiny, ids)
    want = _reference_vector(config, ids, weights=tiny)  # the same weights, another multiplier
    assert np.abs(want - base).max() > 1e-3 * np.abs(base).max()
    cfg = dataclasses.replace(builder.model_config(config), dtype=jnp.float32)
    got, _ = _packed(cfg, params, [ids], 64)
    assert np.abs(got[0] - want).max() <= 4 * F32_TOL * np.abs(want).max()


def test_the_embedder_brings_its_own_buckets_dtype_and_program_name(published, tiny, params):
    cfg = builder.model_config(published)
    assert cfg == che.CausalHybridEmbedderConfig()  # the defaults are the published cut
    assert cfg.param_dtype == jnp.bfloat16 and cfg.program_name == "pw_hybrid_embedder_forward"
    assert cfg.attention_impl == "ragged" and cfg.warm_packed and not cfg.packed_unpacks_rows
    assert all(b % 128 == 0 for b in cfg.token_buckets) and cfg.token_buckets[-1] >= cfg.max_len
    small = builder.model_config(tiny)
    rows = np.ones((5, 64), np.int64)
    prepared, stats = ragged_prepare(rows, rows, 64, cfg=small)
    assert [tokens for _c, _r, tokens in prepared] == [256, 64]  # 4 x 64, then the fifth
    assert stats["real_tokens"] == 320


def test_costs_count_the_published_cut(published):
    sizes = builder.sizes(published)
    layer = costs_falcon_h1.layer_params(sizes)
    assert layer == {"attention": 31_457_280, "mixer": 68_351_072, "mixer_matrices": 68_321_280,
                     "mlp": 330_301_440, "norms": 10_240}
    assert costs_falcon_h1.params(sizes)["total"] == 3_057_419_648
    shapes = jax.eval_shape(lambda: che.init_params(builder.model_config(published),
                                                    jax.random.PRNGKey(0)))
    held = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert held == 3_057_419_648
    # the mean document of the cycle: 2.9 TFLOP, under 1% of it the scan
    flops = costs_falcon_h1.forward_flops(837, sizes)
    assert flops == pytest.approx(2.91e12, rel=0.01)
    scan = sizes["layers"] * costs_falcon_h1.scan_flops(837, sizes)
    assert 0.002 < scan / flops < 0.01
    assert costs_falcon_h1.scan_least_bytes(1, sizes) == 4 * (2 * 4096 + 32 + 1024)
