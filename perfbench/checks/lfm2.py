"""Plain reference of the LFM2-24B-A2B embedder: the forward of layers
``0..num_hidden_layers-1`` of https://huggingface.co/LiquidAI/LFM2-24B-A2B
(config.json, ``model_type`` ``lfm2_moe``) in ``jax.numpy`` and float32 at
``highest`` precision, one document at a time, no kernel, no bucket, no
packing, no ``ragged_dot``; the document's vector is the final-norm state of
its last token.  Imports nothing of the program.

Layer ``l`` (``x`` [T, 2048] float32 residual; RMS norms ``w * x / rms`` with
eps ``norm_eps`` 1e-5; no bias anywhere), its kind ``layer_types[l]``:

1. ``a = rmsnorm(x)`` (the operator norm).
2. ``conv``: ``[B | C | h] = a W_in`` [T, 3 x 2048], split in that order;
   ``u = B * h``; ``v_t = sum_k w_k u_{t - (K - 1 - k)}`` over ``K =
   conv_L_cache`` = 3 taps, ``u`` zero before the document's first token (a
   depthwise ``conv1d`` padded by ``K - 1`` on the left, no bias);
   ``x = x + (C * v) W_out``.
   ``full_attention``: ``q = a W_q`` [T, 32, 64], ``k = a W_k``, ``v = a W_v``
   [T, 8, 64]; each head of ``q`` and of ``k`` RMS-normed over its 64 values
   with a scale of its own (``q_layernorm``, ``k_layernorm``); rotary at theta
   1,000,000 over all 64 dimensions, dimension ``i`` paired with ``i + 32``;
   query head ``h`` reads KV head ``h // 4``; scores ``q_i . k_j / sqrt(64)``
   for ``j <= i``, softmax, ``x = x + concat_h(softmax . v) W_o``.  No gate.
3. ``b = rmsnorm(x)`` (the feed-forward norm).  Layers below
   ``num_dense_layers`` (2): ``x = x + (silu(b W1) * (b W3)) W2`` at 11,776.
   Later layers: ``s = sigmoid(b W_r)`` [T, 64]; ``S`` = the 4 largest of
   ``s + bias`` (``use_expert_bias``); ``w_e = s_e / (sum_S s + eps)``
   (``norm_topk_prob``; ``eps`` is the configuration's ``topk_norm_eps``)
   times ``routed_scaling_factor`` 1: the weights come from ``s``, not from
   ``s + bias``; ``x = x + sum_{e in S} w_e E_e(b)``, each ``E`` a gated MLP of
   1,536, computed densely over ALL experts with ``w`` zero outside ``S``.  No
   shared expert.
4. after the last kept layer ``rmsnorm`` (``embedding_norm``); the vector is
   row ``T - 1``.

Departures, each for a reason:

* the weights are those of ``encoders/lfm2.py`` (bfloat16, made from the
  seed; the bias float32) read as float32: what is compared is the
  computation, not the rounding of the parameters;
* the program keeps gate and up projections side by side in one matrix (gate
  columns first) and ``B | C | h`` in one ``W_in`` as the published
  checkpoint does; the reference splits them;
* (i) the experts come in blocks of ``EXPERT_BLOCK`` (16): a sparse layer is
  2.4 GB in float32;
* (ii) for step 2 every document is padded behind its text to
  ``max_seq_length`` under the causal mask and the causal convolution (no
  real token sees or reaches what lies behind it) and cut back; steps 1 and
  3 are a token's own, so step 3 runs over blocks of ``TOKEN_BLOCK`` (256)
  real tokens (the last block padded with zero rows and cut back), each
  layer kind and precision one compiled program either way: over the
  document cycle's lengths that costs 2.3 times less than step 3 over the
  padded 2,048, and the check embeds some 250 documents a run;
* ``assumed`` of the configuration file: the order ``B | C | h``, the norm as
  ``w * x / rms``, the half-split rotary pairing, the normaliser's eps, the
  hash tokenizer, last-token pooling; the output head is not built (an
  embedder generates nothing);
* layers come one at a time (``layer_params``), all documents through one
  layer before the next is made.

``precision`` (``checks/laguna.py`` has the three in full): ``"float32"`` the
yardstick; ``"stated"`` every product but the router's takes its operands
rounded to bfloat16 and sums in float32, while the conv's gates and taps, the
router (scores, bias, choice, weights), softmax, the norms (the per-head ones
too), rotary, the residual stream and the combine stay float32; ``"lowered"``
the control: all of that in bfloat16 too.
"""

from __future__ import annotations

import functools
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from checks.laguna import (  # noqa: F401
    PRECISIONS, _gated, _mm, _operand, _rmsnorm, _rotary, inv_freq, kept, tokenize)

EXPERT_BLOCK = 16
TOKEN_BLOCK = 256


def _cast(tree, dt):
    return jax.tree_util.tree_map(lambda w: w.astype(dt), tree)


@functools.partial(jax.jit, static_argnames=("kind", "eps", "precision"))
def _mixer_padded(p, x, freq, *, kind: str, eps: float, precision: str):
    """Steps 1-2 over ``x`` [T, D] (a document with its padding behind it)."""
    dt = jnp.bfloat16 if precision == "lowered" else jnp.float32
    p = _cast({k: w for k, w in p.items() if k not in ("mlp", "moe")}, dt)
    x = x.astype(dt)
    t, d = x.shape
    a = _rmsnorm(x, p["attn_norm"], eps)
    if kind == "conv":
        bch = _mm("td,de->te", a, p["w_in"], precision)
        b, c, h = bch[:, :d], bch[:, d: 2 * d], bch[:, 2 * d:]
        u = b * h
        taps = p["conv"].shape[0]
        before = jnp.pad(u, ((taps - 1, 0), (0, 0)))  # zeros before the first token
        v = sum(p["conv"][k] * before[k: k + t] for k in range(taps))
        return x + _mm("td,de->te", c * v, p["w_out"], precision)
    hd, kv = p["wk"].shape[2], p["wk"].shape[1]
    q = _rmsnorm(_mm("td,dhe->the", a, p["wq"], precision), p["q_norm"], eps)
    k = _rmsnorm(_mm("td,dhe->the", a, p["wk"], precision), p["k_norm"], eps)
    v = _mm("td,dhe->the", a, p["wv"], precision)
    q, k = _rotary(q, freq, 1.0), _rotary(k, freq, 1.0)
    group = q.shape[1] // kv
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    s = _mm("qhd,khd->hqk", q, k, precision) / math.sqrt(hd)
    w = jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), axis=-1)
    o = _mm("hqk,khd->qhd", w, v, precision).reshape(t, -1)  # concat_h(o_h)
    return x + _mm("qc,cm->qm", o, p["wo"].reshape(o.shape[1], -1), precision)


@functools.partial(jax.jit, static_argnames=(
    "mlp", "top_k", "scaling", "eps", "norm_eps", "precision"))
def _mlp_block(p, x, *, mlp: str, top_k: int, scaling: float, eps: float, norm_eps: float,
               precision: str):
    """Step 3 over ``x`` [TOKEN_BLOCK, D], a block of a document's tokens."""
    dt = jnp.bfloat16 if precision == "lowered" else jnp.float32
    x = x.astype(dt)
    t = x.shape[0]
    b = _rmsnorm(x, p["mlp_norm"].astype(dt), eps)
    if mlp == "dense":
        m = _cast(p["mlp"], dt)
        return x + _gated(b, m["w_gate_up"], m["w_down"], precision)
    moe = p["moe"]
    # the router's product is none of the bfloat16 products the configuration states
    router, bias = moe["router"].astype(dt), moe["bias"].astype(dt)
    scores = jax.nn.sigmoid(
        jnp.einsum("td,de->te", b, router, precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    top = jnp.take_along_axis(scores, chosen, axis=-1)  # the weights: the scores WITHOUT the bias
    weights = jnp.zeros_like(scores).at[jnp.arange(t)[:, None], chosen].set(
        scaling * top / (jnp.sum(top, axis=-1, keepdims=True) + norm_eps))
    f = moe["w_gate_up"].shape[-1] // 2
    routed = jnp.zeros_like(x)
    for e in range(0, scores.shape[1], EXPERT_BLOCK):
        block = _cast({k: moe[k][e: e + EXPERT_BLOCK] for k in ("w_gate_up", "w_down")}, dt)
        gate = _mm("td,edf->tef", b, block["w_gate_up"][..., :f], precision)
        up = _mm("td,edf->tef", b, block["w_gate_up"][..., f:], precision)
        # w_e E_e(b) = (w_e act_e) W_d: the down product's operand is act_e, which is
        # what ``stated`` rounds; the weight rides on it and the product carries it out
        act = (_operand(jax.nn.silu(gate) * up, precision)
               * weights[:, e: e + EXPERT_BLOCK, None])
        routed = routed + jnp.einsum("tef,efd->td", act, _operand(block["w_down"], precision),
                                     precision=jax.lax.Precision.HIGHEST)
    return x + routed


def layer_forward(p, x, freq, *, kind: str, mlp: str, top_k: int, scaling: float,
                  eps: float, norm_eps: float, max_len: int, precision: str = "float32"):
    """Steps 1-3 for one document: ``x`` [T, D] -> [T, D].  ``p`` is the
    layer's tree as ``encoders/lfm2.py`` makes it.  Step 2 over the document
    padded to ``max_len``, step 3 over blocks of its real tokens (departure
    (ii))."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}")
    t = x.shape[0]
    padded = jnp.pad(jnp.asarray(x), ((0, max_len - t), (0, 0)))
    mixed = _mixer_padded(p, padded, freq, kind=kind, eps=eps, precision=precision)
    blocks = -(-t // TOKEN_BLOCK)
    mixed = jnp.pad(mixed[:t], ((0, blocks * TOKEN_BLOCK - t), (0, 0)))
    out = [_mlp_block(p, mixed[n * TOKEN_BLOCK: (n + 1) * TOKEN_BLOCK], mlp=mlp, top_k=top_k,
                      scaling=scaling, eps=eps, norm_eps=norm_eps, precision=precision)
           for n in range(blocks)]
    return jnp.concatenate(out)[:t]


def layer_statics(config: dict, layer: int) -> dict:
    """The keyword arguments of ``layer_forward`` for layer ``layer`` and the
    rotary frequencies (read by full layers alone)."""
    hd = int(config["hidden_size"]) // int(config["num_attention_heads"])
    freq, _factor = inv_freq(config["rope_parameters"], hd)
    kind = kept(config, "layer_types")[layer]
    return {"freq": jnp.asarray(freq, jnp.float32),
            "kw": dict(kind="conv" if kind == "conv" else "full",
                       mlp="dense" if layer < int(config["num_dense_layers"]) else "sparse",
                       top_k=int(config["num_experts_per_tok"]),
                       scaling=float(config["routed_scaling_factor"]),
                       eps=float(config["norm_eps"]),
                       norm_eps=float(config["topk_norm_eps"]),
                       max_len=int(config["max_seq_length"]))}


def encode(config: dict, texts: list[str], embedding_params, layer_params,
           precision: str = "float32") -> np.ndarray:
    """Vectors [n, D] (float32, not normalised) of ``texts``.
    ``embedding_params()`` and ``layer_params(l)`` make the weights; all
    documents go through one layer before the next is made."""
    dt = jnp.bfloat16 if precision == "lowered" else jnp.float32
    eps = float(config["norm_eps"])
    rows = [tokenize(t, int(config["vocab_size"]), int(config["max_seq_length"]))
            for t in texts]
    with jax.default_matmul_precision("highest"):
        emb = embedding_params()
        states = [np.asarray(emb["tok_emb"][jnp.asarray(r)].astype(jnp.float32)) for r in rows]
        final_norm = emb["final_norm"].astype(dt)
        del emb
        for layer in range(int(config["num_hidden_layers"])):
            t0 = time.monotonic()
            p, st = layer_params(layer), layer_statics(config, layer)
            for n, x in enumerate(states):
                states[n] = np.asarray(layer_forward(
                    p, x, st["freq"], precision=precision, **st["kw"]).astype(jnp.float32))
            del p
            print(f"perfbench-reference layer {layer}: {len(states)} documents in "
                  f"{time.monotonic() - t0:.1f}s", file=sys.stderr, flush=True)
        out = [np.asarray(_rmsnorm(jnp.asarray(x[-1]).astype(dt), final_norm, eps)
                          .astype(jnp.float32)) for x in states]
    return np.stack(out)
