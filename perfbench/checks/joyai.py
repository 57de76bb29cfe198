"""Plain reference of the JoyAI-LLM-Flash embedder: the forward of layers
``0..num_hidden_layers-1`` of
https://huggingface.co/jdopensource/JoyAI-LLM-Flash (config.json, the
DeepSeek-V3 lineage's keys) in ``jax.numpy`` and float32 at ``highest``
precision, one document at a time, no kernel, no bucket, no packing; the
document's vector is the final-norm state of its last token.  Imports nothing
of the program.

Layer ``l`` (``x`` [T, 2048] float32 residual; 32 heads; RMS norms with eps
1e-6; no bias anywhere):

1. ``a = rmsnorm(x)``.
2. ``c_q = rmsnorm(a W_dq)`` [T, 1536]; ``q = c_q W_uq`` [T, 32, 192], a head
   split into ``q_nope`` [128] and ``q_pe`` [64].
3. ``ckv = a W_dkv`` [T, 576], split into ``c_kv`` [512] and ``k_pe`` [64]
   (ONE head); ``kv = rmsnorm(c_kv) W_ukv`` [T, 32, 256], a head split into
   ``k_nope`` [128] and ``v`` [128].
4. rotary at theta 32,000,000 on ``q_pe`` and ``k_pe`` only, all 64
   dimensions, no scaling; ``rope_interleave``: the pairs are ``(2i, 2i+1)``.
   As the published model does, each is first gathered to ``[evens | odds]``
   and then rotated as halves; ``q_pe`` and ``k_pe`` are permuted alike, so a
   score is what the interleaved rotation gives.
5. ``q = [q_nope | q_pe]``, ``k_h = [k_nope_h | k_pe]`` (the one rotary key
   under every head); scores ``q_i . k_j / sqrt(192)`` for ``j <= i``, softmax,
   ``o_h = softmax . v_h`` [T, 32, 128]; ``x = x + concat_h(o_h) W_o``.  No
   gate, no window, no norm on q or k beyond the two latent norms.
6. ``b = rmsnorm(x)``.  Layer 0 (``first_k_dense_replace`` 1): ``x = x +
   (silu(b W_g) * (b W_u)) W_d`` at 7,168.  Layers 1..: ``s = sigmoid(b W_r)``
   [T, 256]; ``S`` = the 8 largest of ``s + bias`` (``e_score_correction_bias``;
   with ``n_group`` 1 and ``topk_group`` 1 the group limit keeps every
   expert); ``w_e = 2.5 s_e / (sum_S s + 1e-20)``: the weights come from
   ``s``, not from ``s + bias``; ``x = x + sum_{e in S} w_e E_e(b) +
   E_shared(b)``, each ``E`` a gated MLP of 768, computed densely over ALL
   experts with ``w`` zero outside ``S``.
7. after the last kept layer ``rmsnorm``; the vector is row ``T - 1``.

This is the prefill ("expanded") form of latent attention; the absorbed form
is the same function and is what a cache of latents would be read with.

Departures, each for a reason:

* the weights are those of ``encoders/joyai.py`` (bfloat16, made from the
  seed; the bias float32) read as float32: what is compared is the
  computation, not the rounding of the parameters;
* the program keeps gate and up projections side by side in one matrix (gate
  columns first), and ``k_nope | v`` and ``c_kv | k_pe`` as the published
  checkpoint does; the reference splits them;
* (i) the experts come in blocks of ``EXPERT_BLOCK`` (64): a sparse layer is
  4.8 GB in float32 and ``[2048, 256, 768]`` float32 three times over is 4.8
  GB more;
* (ii) every document is padded behind its text to ``max_seq_length`` under
  the causal mask (no real token sees what lies behind it) and cut back, so
  that a layer kind and a precision are ONE compiled program and not one a
  document length: the builders' machine caps the compile cache, and the
  references' programs were over half of what a run of the other cells
  leaves in it (PERF.md 7 (e));
* ``assumed`` of the configuration file: the hash tokenizer, last-token
  pooling; the multi-token-prediction module and the output head are not
  built (an embedder generates nothing);
* layers come one at a time (``layer_params``), all documents through one
  layer before the next is made.

``precision`` (``checks/laguna.py`` has the three in full): ``"float32"`` the
yardstick; ``"stated"`` every product but the router's takes its operands
rounded to bfloat16 and sums in float32, while the router (scores, bias,
choice, weights), softmax, the norms (the two latent ones too), rotary, the
residual stream and the combine stay float32; ``"lowered"`` the control: all
of that in bfloat16 too.
"""

from __future__ import annotations

import functools
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from checks.laguna import PRECISIONS, _gated, _mm, _operand, _rmsnorm, tokenize  # noqa: F401

EXPERT_BLOCK = 64


def _rotary(x, freq):
    """``x`` [T, H, 2 len(freq)], interleaved pairs -> ``[evens | odds]``
    rotated as halves."""
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.cos(angles)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "mlp", "nope", "top_k", "scaling", "eps", "precision"))
def _layer_padded(p, x, freq, *, mlp: str, nope: int, top_k: int, scaling: float,
                  eps: float, precision: str):
    """Steps 1-6 over ``x`` [T, D] (a document with its padding behind it)."""
    dt = jnp.bfloat16 if precision == "lowered" else jnp.float32
    cast = lambda tree: jax.tree_util.tree_map(lambda w: w.astype(dt), tree)
    moe = p.get("moe")
    p = cast({k: w for k, w in p.items() if k != "moe"})
    x = x.astype(dt)
    t = x.shape[0]
    rank = p["kv_norm"].shape[0]
    a = _rmsnorm(x, p["attn_norm"], eps)
    c_q = _rmsnorm(_mm("td,dr->tr", a, p["wq_a"], precision), p["q_norm"], eps)
    q = _mm("tr,rhe->the", c_q, p["wq_b"], precision)
    ckv = _mm("td,dr->tr", a, p["wkv_a"], precision)
    kv = _mm("tr,rhe->the", _rmsnorm(ckv[:, :rank], p["kv_norm"], eps), p["wkv_b"], precision)
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], freq)], axis=-1)
    k_pe = _rotary(ckv[:, None, rank:], freq)  # [T, 1, rope]: one head
    k = jnp.concatenate([kv[..., :nope], jnp.repeat(k_pe, q.shape[1], axis=1)], axis=-1)
    v = kv[..., nope:]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    s = _mm("qhd,khd->hqk", q, k, precision) / math.sqrt(q.shape[-1])
    w = jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), axis=-1)
    o = _mm("hqk,khd->qhd", w, v, precision).reshape(t, -1)  # concat_h(o_h)
    x = x + _mm("qc,cm->qm", o, p["wo"].reshape(o.shape[1], -1), precision)
    b = _rmsnorm(x, p["mlp_norm"], eps)
    if mlp == "dense":
        return x + _gated(b, p["mlp"]["w_gate_up"], p["mlp"]["w_down"], precision)
    # the router's product is none of the bfloat16 products the configuration states
    router, bias = moe["router"].astype(dt), moe["bias"].astype(dt)
    scores = jax.nn.sigmoid(
        jnp.einsum("td,de->te", b, router, precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    top = jnp.take_along_axis(scores, chosen, axis=-1)  # the weights: the scores WITHOUT the bias
    weights = jnp.zeros_like(scores).at[jnp.arange(t)[:, None], chosen].set(
        scaling * top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20))
    f = moe["w_gate_up"].shape[-1] // 2
    routed = jnp.zeros_like(x)
    for e in range(0, scores.shape[1], EXPERT_BLOCK):
        block = cast({k: moe[k][e: e + EXPERT_BLOCK] for k in ("w_gate_up", "w_down")})
        gate = _mm("td,edf->tef", b, block["w_gate_up"][..., :f], precision)
        up = _mm("td,edf->tef", b, block["w_gate_up"][..., f:], precision)
        # w_e E_e(b) = (w_e act_e) W_d: the down product's operand is act_e, which is
        # what ``stated`` rounds; the weight rides on it and the product carries it out
        act = (_operand(jax.nn.silu(gate) * up, precision)
               * weights[:, e: e + EXPERT_BLOCK, None])
        routed = routed + jnp.einsum("tef,efd->td", act, _operand(block["w_down"], precision),
                                     precision=jax.lax.Precision.HIGHEST)
    shared = cast(moe["shared"])
    return x + routed + _gated(b, shared["w_gate_up"], shared["w_down"], precision)


def layer_forward(p, x, freq, *, max_len: int, precision: str = "float32", **kw):
    """Steps 1-6 for one document: ``x`` [T, D] -> [T, D].  ``p`` is the
    layer's tree as ``encoders/joyai.py`` makes it.  The document is padded
    behind its text to ``max_len`` and cut back (departure (ii))."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}")
    t = x.shape[0]
    padded = jnp.pad(jnp.asarray(x), ((0, max_len - t), (0, 0)))
    return _layer_padded(p, padded, freq, precision=precision, **kw)[:t]


def layer_statics(config: dict, layer: int) -> dict:
    """The keyword arguments of ``layer_forward`` for layer ``layer`` and the
    rotary part's frequencies."""
    rope = int(config["qk_rope_head_dim"])
    freq = 1.0 / float(config["rope_theta"]) ** (np.arange(0, rope, 2, dtype=np.float64) / rope)
    dense = layer < int(config["first_k_dense_replace"])
    return {"freq": jnp.asarray(freq, jnp.float32),
            "kw": dict(mlp="dense" if dense else "sparse",
                       nope=int(config["qk_nope_head_dim"]),
                       top_k=int(config["num_experts_per_tok"]),
                       scaling=float(config["routed_scaling_factor"]),
                       eps=float(config["rms_norm_eps"]),
                       max_len=int(config["max_seq_length"]))}


def encode(config: dict, texts: list[str], embedding_params, layer_params,
           precision: str = "float32") -> np.ndarray:
    """Vectors [n, D] (float32, not normalised) of ``texts``.
    ``embedding_params()`` and ``layer_params(l)`` make the weights; all
    documents go through one layer before the next is made."""
    dt = jnp.bfloat16 if precision == "lowered" else jnp.float32
    eps = float(config["rms_norm_eps"])
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
