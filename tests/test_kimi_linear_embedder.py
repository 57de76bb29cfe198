"""The causal embedder's kda layers (Kimi Delta Attention: the gated delta
rule with a decay a channel), latent attention with a direct query and no
rotary, and routed layers that hold a share of their experts, against their
plain reference, ``perfbench/checks/kimi_linear.py`` (one source: the
benchmark's own reference is imported, not copied).

Everything runs the configuration file's tiny ``rehearse`` preset on the CPU:
5 layers (kda/dense, kda, kda, latent, kda, the last four routed), hidden 64,
4 delta-rule heads of 16 and a 4-tap convolution, 4 latent heads of 16 + 8
with a latent of 16, 16 experts top 4 of width 32 of which 8 are held, one
shared expert, dense width 128, vocabulary 512.

Tolerances, and why (``tests/test_causal_moe_embedder.py`` has the argument in
full; the readings here are this model's).  A ``float32`` program
(``dtype=float32``) must match the float32 reference to ``F32_TOL``: what is
left is the order of float32 sums, the chunked scan's among them (read:
1e-7 to 6e-7 a layer).  That pins the mathematics: the delta rule and its
decay, the convolution's taps and their reset at a document's start, the L2
norms and the query's scale, the head norm and the output gate, the direct
query and the unrotated key, the share of experts.  The program as deployed
(bfloat16 operands) is held per layer, fed the reference's own input, against
the reference at the STATED precision, where only the order of float32 sums
is left (``STATED_LAYER_TOL`` for the median token; read: 7e-8 to 2e-7), and
the reference one step of precision down (``lowered``) lies 0.009-0.02 from
the stated one and has to FAIL that tolerance five hundredfold.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
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

import costs_kimi_linear  # noqa: E402
import costs_laguna  # noqa: E402
from checks import kimi_linear as reference  # noqa: E402
from encoders import kimi_linear as builder  # noqa: E402

from pathway_tpu.internals import flight_recorder  # noqa: E402
from pathway_tpu.models import causal_moe_embedder as cme  # noqa: E402
from pathway_tpu.models.encoder import SentenceEncoder, ragged_prepare  # noqa: E402
from pathway_tpu.ops import routed_experts as rx  # noqa: E402

SEED = 2147483671
CELL = "ingest-docs-kimi-linear"
CONFIG = "vs-kimi-linear-48b-a3b-bf16-marcodoc"
F32_TOL = 2e-5  # relative; float32 sums in another order
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


def _reference_layer(config, layer: int, p, x, precision="float32"):
    st = reference.layer_statics(config, layer)
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


@pytest.mark.parametrize("layer,kind", [(0, "kda, dense MLP"),
                                        (1, "kda, a share of the routed experts"),
                                        (3, "latent with a direct query and no rotary"),
                                        (4, "kda, last")])
def test_each_layer_kind_matches_the_reference_fed_the_references_input(
        tiny, params, layer, kind):
    states = _reference_states(tiny, _ids(40, tiny["vocab_size"]))
    x, want = states[layer], states[layer + 1]
    p = params[f"layer_{layer}"]
    got32 = _program_layer(_cfg(tiny, dtype=jnp.float32), p, layer, x)
    assert _rel(got32 - x, want - x) < F32_TOL
    # the program as deployed against the reference at the stated precision:
    # the order of sums alone; the same layer one step of precision down is
    # five hundred times further
    got = _program_layer(_cfg(tiny), p, layer, x)
    stated, low = (_reference_layer(tiny, layer, p, x, precision)
                   for precision in ("stated", "lowered"))
    assert np.median(_token_errors(got, stated, x)) < STATED_LAYER_TOL
    assert np.sum(_token_errors(got, stated, x) > STATED_LAYER_TOL) <= 2  # a router near-tie
    assert np.median(_token_errors(low, stated, x)) > 500 * STATED_LAYER_TOL


@pytest.mark.parametrize("length", [3, 16, 17, 40, 64])
def test_whole_forward_at_several_lengths(tiny, params, length):
    """Under, at and over a sub-block and a query block (16), and the
    longest row (64, a whole chunk: the reference pads every shorter one
    behind its text up to it)."""
    row = _ids(length, tiny["vocab_size"], seed=length)
    want = _encode_reference(tiny, [row])[0]
    got32 = _program_rows(_cfg(tiny, dtype=jnp.float32), params, [row])[0]
    assert _rel(got32, want) < 5 * F32_TOL  # five layers
    stated = _encode_reference(tiny, [row], precision="stated")[0]
    got = _program_rows(_cfg(tiny), params, [row])[0]
    assert _rel(got, stated) < (1e-5 if length <= 3 else 0.05)  # a rounding that fell the other way


@pytest.mark.parametrize("lengths", [(5, 64, 9, 16), (32, 8, 5, 64, 3), (50, 40, 30)])
def test_documents_packed_together_get_the_vectors_they_get_alone(tiny, params, lengths):
    """The delta rule's state and the convolution's taps reset at each
    document's start on the packed axis, wherever it falls in a chunk of 64;
    the launch's counters are its documents' sums in the latent family's
    layout (the scan walks the same bucket, so they are its counts too)."""
    cfg = _cfg(tiny, dtype=jnp.float32, token_buckets=(32, 128, 256))
    rows = [_ids(n, tiny["vocab_size"], seed=n) for n in lengths]
    together, counters, chunk = _packed_forward(cfg, params, rows)
    alone = np.stack([_program_rows(cfg, params, [r])[0] for r in rows])
    np.testing.assert_allclose(together, alone, rtol=0, atol=5e-5)
    assert len(counters) == 4 + 4
    bucket = chunk.ids.shape[0]
    routed, _touched, _fullest_sum, _fullest, documents, tokens, mla_bucket, pairs = counters
    assert (documents, tokens, mla_bucket) == (len(lengths), sum(lengths), bucket)
    assert pairs == sum(n * (n + 1) // 2 for n in lengths)
    # a share of 8 of 16 experts: fewer pairs than every token's four a layer
    sparse = sum(1 for m in cfg.mlp_types if m == "sparse")
    assert 0 < routed < sum(lengths) * cfg.top_k * sparse


def test_the_kda_mixer_is_the_delta_rule_by_hand(tiny, params):
    """The mixer of layer 0 recomputed from its matrices with numpy in
    float64, the recurrence token by token: the projections and their taps,
    the L2 norms and the query's 1/sqrt(dk), the decay, beta, the head norm
    and the gate."""
    cfg = _cfg(tiny, dtype=jnp.float32)
    p64 = jax.tree_util.tree_map(lambda w: np.asarray(w, np.float64), params["layer_0"])
    t, h, dk = 23, cfg.kda_heads, cfg.kda_head_dim
    a = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (t, cfg.hidden_dim), jnp.float32))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(cme._kda_mixer(cfg, params["layer_0"], jnp.asarray(a), jnp.arange(t),
                                        None, None))
    proj = h * dk
    u = a @ p64["w_qkv"]
    taps = p64["conv"].shape[0]
    c = np.stack([sum(p64["conv"][n] * u[i - (taps - 1 - n)] for n in range(taps)
                      if i - (taps - 1 - n) >= 0) for i in range(t)])
    c = c / (1 + np.exp(-c))
    q, k, v = (c[:, n * proj:(n + 1) * proj].reshape(t, h, dk) for n in range(3))
    q = q / np.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) / math.sqrt(dk)
    k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    f = ((a @ p64["w_fa"]) @ p64["w_fb"]).reshape(t, h, dk) + p64["dt_bias"].reshape(h, dk)
    g = -np.exp(p64["A_log"])[:, None] * np.log1p(np.exp(f))
    beta = 1 / (1 + np.exp(-(a @ p64["w_b"])))
    o = np.zeros((t, h, dk))
    for head in range(h):
        s = np.zeros((dk, dk))
        for i in range(t):
            s = np.exp(g[i, head])[:, None] * s
            s = s + beta[i, head] * np.outer(k[i, head], v[i, head] - k[i, head] @ s)
            o[i, head] = s.T @ q[i, head]
    o = o / np.sqrt((o * o).mean(-1, keepdims=True) + cfg.rms_eps) * p64["o_norm"]
    o = o / (1 + np.exp(-((a @ p64["w_ga"]) @ p64["w_gb"]).reshape(t, h, dk)))
    want = o.reshape(t, proj) @ p64["w_o"]
    assert _rel(got, want) < F32_TOL


def test_the_latent_layer_takes_a_direct_query_and_turns_no_dimension(tiny, params):
    """``q = a W_q`` with no low-rank path, and the 8 "rope" dimensions kept
    in the product unrotated: the same layer with rotary put back is far
    outside the tolerance the layer meets."""
    cfg = _cfg(tiny, dtype=jnp.float32)
    assert cfg.latent_q_rank == 0 and cfg.latent_rotary is None
    p = params["layer_3"]
    assert "wq" in p and not {"wq_a", "wq_b", "q_norm"} & set(p)
    assert p["wq"].shape == (cfg.hidden_dim, cfg.heads_per_layer[3],
                             cfg.latent_nope_dim + cfg.latent_rope_dim)
    states = _reference_states(tiny, _ids(40, tiny["vocab_size"]))
    x, want = states[3], states[4]
    assert _rel(_program_layer(cfg, p, 3, x) - x, want - x) < F32_TOL
    turned = _program_layer(dataclasses.replace(cfg, latent_rotary=cme.RotarySpec(theta=10.0)),
                            p, 3, x)
    assert _rel(turned - x, want - x) > 1000 * F32_TOL


# -- the expert share -----------------------------------------------------------


def _uncut(tiny):
    """The configuration with every one of its 16 experts held."""
    return dict(tiny, num_experts=tiny["published_num_experts"], first_expert=0)


@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_routed_parts_add_up_to_the_uncut_layer(tiny, shares):
    """Each of ``shares`` chips holds its slice of the 16 experts and
    computes its own part for the tokens routed to them; the parts, with
    what every chip computes alike (the mixer, the residual, the shared
    expert) counted once, add up to what the uncut reference gives for the
    whole layer."""
    uncut = _uncut(tiny)
    layer, routed = 1, int(uncut["num_experts"])
    p = builder.layer_params(uncut, SEED, layer)
    x = _reference_states(tiny, _ids(40, tiny["vocab_size"]))[layer]
    whole = _reference_layer(uncut, layer, p, x)
    held = routed // shares
    parts = []
    for n in range(shares):
        config = dict(uncut, num_experts=held, first_expert=n * held)
        moe = dict(p["moe"], **{k: p["moe"][k][n * held:(n + 1) * held]
                                for k in ("w_gate_up", "w_down")})
        parts.append(_program_layer(
            _cfg(config, dtype=jnp.float32), dict(p, moe=moe), layer, x))
    # what every chip computes alike: the layer with no routed expert at all
    moe = dict(p["moe"], **{k: p["moe"][k][:0] for k in ("w_gate_up", "w_down")})
    alike = _reference_layer(dict(uncut, num_experts=0), layer, dict(p, moe=moe), x)
    np.testing.assert_allclose(sum(parts) - (shares - 1) * alike, whole,
                               rtol=0, atol=F32_TOL * np.abs(whole).max())
    # and the program's share is the reference's share
    np.testing.assert_allclose(parts[1], _reference_layer(
        dict(uncut, num_experts=held, first_expert=held), layer,
        dict(p, moe=dict(p["moe"], **{k: p["moe"][k][held: 2 * held]
                                      for k in ("w_gate_up", "w_down")})), x),
        rtol=0, atol=F32_TOL * np.abs(whole).max())


def test_a_share_routes_over_every_expert_and_counts_only_its_own():
    """The router chooses and weights over all E experts (normalised over
    all ``top_k`` chosen); a pair of an expert held elsewhere reads no
    weights, adds 0 and is not counted; the shares' group sizes tile the
    uncut layer's."""
    rng = np.random.default_rng(0)
    t, d, f, e, k = 24, 32, 16, 8, 3
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    valid = jnp.asarray(np.arange(t) < 21)
    router = jnp.asarray(rng.normal(size=(d, e)), jnp.float32)
    wgu = jnp.asarray(rng.normal(size=(e, d, 2 * f)) / math.sqrt(d), jnp.float32)
    wd = jnp.asarray(rng.normal(size=(e, f, d)) / math.sqrt(f), jnp.float32)
    kw = dict(top_k=k, scaling=2.446, scoring="sigmoid",
              bias=jnp.asarray(rng.normal(size=e) * 0.01, jnp.float32))
    with jax.default_matmul_precision("highest"):
        whole, sizes = rx.routed_experts(x, valid, router, wgu, wd, **kw)
        parts = [rx.routed_experts(x, valid, router, wgu[s:s + 2], wd[s:s + 2],
                                   first_expert=s, **kw) for s in range(0, e, 2)]
    np.testing.assert_allclose(sum(out for out, _ in parts), whole, rtol=0, atol=1e-5)
    np.testing.assert_array_equal(np.concatenate([np.asarray(s) for _, s in parts]),
                                  np.asarray(sizes))
    assert int(np.asarray(sizes).sum()) == 21 * k
    assert np.abs(np.asarray(whole)[21:]).max() == 0  # padding gets nothing


def test_the_launch_s_latent_counters_reach_the_recorder_without_a_sync(tiny, params):
    """The forward has a latent layer, so its launches fill ``pathway_mla_*``
    beside ``pathway_moe_*``, and no conv family."""
    before_moe, before = flight_recorder.moe_stats(), flight_recorder.mla_stats()
    before_conv = flight_recorder.conv_stats()
    enc = SentenceEncoder(cfg=_cfg(tiny, token_buckets=(64, 128)),
                          max_length=tiny["max_seq_length"], params=params)
    warmed = flight_recorder.mla_stats()  # the first dispatch launches each bucket on padding
    lengths = [6, 40, 14]
    enc.encode([" ".join(f"t{i:03d}" for i in range(n)) for n in lengths])
    after_moe, after = flight_recorder.moe_stats(), flight_recorder.mla_stats()
    tokens = [n + 2 for n in lengths]  # [CLS] words [SEP]
    assert flight_recorder.conv_stats() == before_conv  # no conv layer, no conv launch
    launches = after["launches_total"] - before["launches_total"]
    assert launches == after_moe["launches_total"] - before_moe["launches_total"] >= 1
    assert after["documents_total"] - before["documents_total"] == len(lengths)
    assert after["tokens_total"] - before["tokens_total"] == sum(tokens)
    assert after["attention_pairs_total"] - before["attention_pairs_total"] == sum(
        n * (n + 1) // 2 for n in tokens)
    slots = after["bucket_tokens_total"] - warmed["bucket_tokens_total"]
    assert slots >= sum(tokens) and slots % 64 == 0  # whole chunks of the scan
    lines = flight_recorder.observability_metrics_lines()
    for name in ("launches_total", "documents_total", "tokens_total", "bucket_tokens_total"):
        assert any(line.startswith(f"pathway_mla_{name} ") for line in lines)


def test_the_counters_add_up_over_a_window_of_launches():
    """Launches recorded as the forward returns them, some still waiting on
    the device: the totals are the sums of their arrays, the routed four and
    the latent family behind them."""
    recorder = flight_recorder._LaunchCounters(
        flight_recorder._moe_launches._totals.keys(), flight_recorder._add_moe)
    arrays = [[10, 3, 4, 4, 2, 50, 64, 900], [20, 5, 6, 5, 1, 30, 128, 465]]
    for a in arrays:
        recorder.record(jnp.asarray(a, jnp.int32))
    totals = recorder.stats(wait=True)
    assert (totals["launches_total"], totals["routed_tokens_total"]) == (2, 30)
    assert (totals["experts_touched_total"], totals["max_expert_tokens_sum"],
            totals["max_expert_tokens"]) == (8, 10, 5)
    assert (totals["mla_launches_total"], totals["mla_documents_total"],
            totals["mla_tokens_total"], totals["mla_bucket_tokens_total"],
            totals["mla_attention_pairs_total"]) == (2, 3, 80, 192, 1365)
    assert totals["conv_launches_total"] == 0


def _reader(name: str):
    """``perfbench/layer_metrics/<name>.py`` as a module (its name holds dots)."""
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_scan_roofline_reads_the_latent_family_s_bucket_tokens(published):
    """``kda.scan_roofline`` on a made-up trace: the scan's least time at the
    mean launch's bucket (``mla.bucket_tokens_total`` / ``mla.launches_total``)
    over the four KDA layers and the slice's launches, against the kernel's
    device time; nothing without the counters or the kernel's name."""
    reader = _reader("kda.scan_roofline")
    sizes = builder.sizes(published)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    trace = {"device_ops": [("pw_kda_scan.1", 0.05), ("fusion", 0.3)],
             "programs": {"jit_pw_moe_embedder_forward_ragged": 0.4},
             "launches": {"jit_pw_moe_embedder_forward_ragged": 2}}
    facts = {"encoder": sizes, "kda_scan_ops": ["pw_kda_scan"],
             "encoder_programs": ["jit_pw_moe_embedder_forward"]}
    delta = {"mla.launches_total": 10, "mla.bucket_tokens_total": 10 * 4608}
    ctx = {"trace": trace, "facts": facts, "delta": delta, "peaks": peaks}
    least = 4 * costs_kimi_linear.least_seconds(
        costs_kimi_linear.scan_flops(4608, sizes), costs_kimi_linear.scan_least_bytes(4608, sizes),
        peaks)
    assert reader.read(ctx) == pytest.approx(100.0 * least * 2 / 0.05)
    assert 0.0 < reader.read(ctx) < 100.0
    assert reader.read(dict(ctx, delta={})) is None
    assert reader.read(dict(ctx, facts=dict(facts, kda_scan_ops=[]))) is None


def test_the_load_reader_divides_by_the_experts_held_here(tiny, params):
    """``moe.load_max_over_mean`` takes the mean over ``sizes()["experts"]``:
    the pairs the counters count go to the experts this chip holds, so an
    even load over them reads 1, and a launch's reading lies between 1 and
    the held count."""
    reader = _reader("moe.load_max_over_mean")
    sizes = builder.sizes(tiny)
    held = tiny["num_experts"]
    assert (sizes["experts"], sizes["routed_experts"]) == (held, tiny["published_num_experts"])
    even = {"moe.routed_tokens_total": 10 * held, "moe.max_expert_tokens_sum": 10}
    assert reader.read({"delta": even, "facts": {"encoder": sizes}}) == 1.0
    cfg = _cfg(tiny, dtype=jnp.float32, token_buckets=(128,))
    rows = [_ids(n, tiny["vocab_size"], seed=n) for n in (50, 40, 30)]
    _vectors, counters, _chunk = _packed_forward(cfg, params, rows)
    routed, _touched, fullest_sum = counters[:3]
    read = reader.read({"delta": {"moe.routed_tokens_total": routed,
                                  "moe.max_expert_tokens_sum": fullest_sum},
                        "facts": {"encoder": sizes}})
    assert 1.0 <= read <= held


def test_the_config_checks_the_new_kind(tiny):
    cfg = builder.model_config(tiny)
    assert cfg.layer_types == ("kda", "kda", "kda", "latent", "kda")
    assert cfg.mlp_types == ("dense",) + ("sparse",) * 4 and cfg.conv_taps == 4
    assert (cfg.num_experts, cfg.experts_here, cfg.first_expert) == (16, 8, 0)
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, conv_taps=0)
    with pytest.raises(ValueError):  # a share past the router's experts
        dataclasses.replace(cfg, first_expert=12)
    with pytest.raises(ValueError):  # what the program does not build is refused
        builder.model_config(dict(tiny, mla_use_nope=False))
    with pytest.raises(ValueError):
        builder.model_config(dict(tiny, q_lora_rank=1536))
    shapes = jax.eval_shape(lambda: cme.init_params(cfg, jax.random.PRNGKey(0)))
    mine = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), builder.params(tiny, SEED))
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), shapes) == mine


def test_the_decay_is_seeded_as_fla_draws_it(published):
    p = builder.layer_params(published, SEED, 0)
    a = np.exp(np.asarray(p["A_log"]))
    assert p["A_log"].shape == (32,) and 1.0 <= a.min() and a.max() <= 16.0
    dt = np.log1p(np.exp(np.asarray(p["dt_bias"], np.float64)))  # softplus(dt_bias) = dt
    assert p["dt_bias"].shape == (4096,) and p["dt_bias"].dtype == jnp.float32
    assert 1e-4 <= dt.min() and dt.max() <= 0.1 + 1e-6


def test_costs_count_the_published_cut(published):
    sizes = builder.sizes(published)
    assert sizes["layer_types"] == ["kda", "kda", "kda", "latent", "kda"]
    assert sizes["mlp_types"] == ["dense"] + ["sparse"] * 4
    counted = costs_kimi_linear.params(sizes)
    shapes = jax.eval_shape(
        lambda: cme.init_params(builder.model_config(published), jax.random.PRNGKey(0)))
    assert counted["total"] == cme.count_params(shapes) == 4_282_936_192
    assert counted["embedding"] == 377_487_360 and counted["experts"] == 4 * 128 * 7_077_888
    assert costs_kimi_linear.kda_params(sizes) == 39_514_272
    assert costs_kimi_linear.mla_params(sizes) + 512 == 29_114_880
    assert costs_kimi_linear.expert_params(sizes) == costs_laguna.expert_params(sizes) == 7_077_888
    # about 413 M multiply-adds a token at the cycle's mean: four of eight
    # routed experts held here, the scan a tenth of a KDA layer's matrices
    assert 4.0e8 < costs_kimi_linear.forward_flops(837, sizes) / 837 / 2 < 4.3e8
    assert costs_kimi_linear.scan_least_bytes(64, sizes) == 4 * 64 * (5 * 4096 + 32)


# -- the cell -------------------------------------------------------------------


def test_the_cell_is_declared_with_its_configuration_traffic_and_metrics(published):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 and cell["config"] == CONFIG
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "rows"]
    assert len(entry["why"]) <= 200
    assert entry["source"] == (
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    # the published widths, uncut, and the cut that is stated
    want = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear",
        "moe_intermediate_size": 1024, "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts_per_token": 8, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 0, "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "routed_scaling_factor": 2.446,
        "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True,
        "v_head_dim": 128, "vocab_size": 163840}
    assert {k: published[k] for k in want} == want
    assert (published["num_hidden_layers"], published["published_num_hidden_layers"]) == (5, 27)
    assert (published["num_experts"], published["published_num_experts"]) == (128, 256)
    assert published["first_expert"] == 0 and "expert parallelism" in published["deployment"]
    assert published["reduced"] == entry["reduced"] and "4,282,936,192" in published["why_reduced"]
    assert published["rows"] == 61440 and published["index"]["capacity"] == 65536
    assert published["index"]["dim"] == published["hidden_size"]
    assert (published["server"], published["check"]) == ("vector_store_kimi_linear",
                                                         "ingest_laguna")
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["generator"] == "file_drop_docs" and traffic["poll_ms"] == 10
    assert traffic["rate_per_s"] == int(traffic["rate_per_s"]) > 0
    reported = {m["name"] for group in ("end_to_end", "per_layer") for m in bench[group]
                if "workloads" not in m or CELL in m["workloads"]}
    assert {"fresh_p95_ms", "setup_s", "kda.scan_roofline", "ingest_kda_step.mfu",
            "mla.padding_share", "mla.keys_per_query",
            "moe.tokens_per_expert", "moe.load_max_over_mean",
            "embed.docs_per_launch", "embed_moe.device_ms_per_launch",
            "idle.attributed", "index.apply_ms", "ingest.step_to_index_ms"} <= reported
    assert not {"moe.grouped_matmul_roofline", "ingest_conv_step.mfu", "conv.padding_share",
                "ingest_mla_step.mfu"} & reported
    for name in reported - {"setup_s"}:  # every metric the cell reports has its reader
        kind = "end_to_end" if name == "fresh_p95_ms" else "layer_metrics"
        assert os.path.exists(os.path.join(BENCH, kind, name + ".py")), name
    for name in ("kda.scan_roofline", "ingest_kda_step.mfu"):
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "fresh_p95_ms"


def test_a_traced_rehearsal_of_the_cell_is_correct_and_prints_its_counters():
    """``perfbench/run.py --rehearse`` end to end on the CPU: the delta-rule
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
    assert 0.0 <= metrics["mla.padding_share"]["value"] < 100.0
    assert metrics["mla.keys_per_query"]["value"] >= 1.0
    assert metrics["moe.tokens_per_expert"]["value"] > 0
    for name in ("embed_moe.device_ms_per_launch", "kda.scan_roofline", "ingest_kda_step.mfu",
                 "idle.attributed", "moe.grouped_matmul_roofline"):
        assert name not in metrics
    assert math.isfinite(metrics["ingest.fresh_p50_ms"]["value"])
