"""Plain reference of the Laguna-XS.2 embedder: the forward of layers
``0..num_hidden_layers-1`` of https://huggingface.co/poolside/Laguna-XS.2
(config.json) in ``jax.numpy`` and float32 at ``highest`` precision, one
document at a time, no kernel, no bucket, no batching; the document's vector
is the final-norm state of its last token.  Imports nothing of the program.

Layer ``l`` (``x`` [T, 2048]; ``H_l`` = ``num_attention_heads_per_layer[l]``,
8 KV heads of size 128, no bias, RMS norms with eps 1e-6):

1. ``a = rmsnorm(x)``; ``q = a Wq`` [T, H_l, 128]; ``k = a Wk``, ``v = a Wv``
   [T, 8, 128].
2. rotary on ``q`` and ``k``: window layers all 128 dimensions at theta
   10,000; full layers the first 64 (``partial_rotary_factor`` 0.5), YaRN
   (factor 64, original 4,096, beta 64 / 1, theta 500,000) with cosine and
   sine times ``attention_factor``, the frequencies blended as
   ``transformers`` ``_compute_yarn_parameters`` blends them.
3. query head ``h`` reads KV head ``h // (H_l / 8)``; scores
   ``q_i . k_j / sqrt(128)`` for ``j <= i`` and, on window layers,
   ``i - j < 512``; softmax; ``o_h = softmax . v``.
4. ``g = sigmoid(a Wg)`` [T, H_l]; ``x = x + concat_h(g_h o_h) Wo``.
5. ``b = rmsnorm(x)``; layer 0 (dense): ``x = x + (silu(b W1) * (b W3)) W2``;
   sparse layers: ``p = softmax(b Wr)`` over all experts, ``S`` the 8 largest,
   ``w_e = 2.5 p_e / sum_S p``; ``x = x + sum_e w_e E_e(b) + E_shared(b)``,
   computed densely over ALL experts with ``w`` zero outside ``S``.
6. after the last kept layer ``rmsnorm``; the vector is row ``T - 1``.

Departures, each for a reason:

* the weights are those of ``encoders/laguna.py`` (bfloat16, made from the
  seed) read as float32: what is compared is the computation, not the
  rounding of the parameters;
* the program keeps gate and up projections side by side in one matrix
  (gate columns first); the reference splits it;
* rotary pairs dimension ``i`` with ``i + rotary_dim / 2`` (the half-split of
  ``transformers`` ``rotate_half``); the config does not say, and with
  weights from a seed the other pairing is a permutation of columns;
* ``assumed`` of the configuration file: silu, a per-head sigmoid gate on the
  layer's normed input, softmax scoring without a correction bias, the
  shared expert added ungated, no norm on q and k, last-token pooling;
* layers come one at a time (``layer_params``), all documents through one
  layer before the next is made: a sparse layer is 3.4 GB in float32.

``precision`` says in what the forward is computed:

``"float32"``  the yardstick: every array and product float32 at ``highest``.
``"stated"``   what the configuration's ``precision`` group states: every
               product but the router's takes its two operands rounded to
               bfloat16 and sums in float32; the router, softmax, norms,
               rotary, the residual stream and the combine stay float32.
               A program at the stated precision differs from this forward
               by the order of float32 sums alone, so what tells the stated
               precision from the one below it is the distance from HERE.
``"lowered"``  the control, one step down: what ``stated`` keeps in float32
               (router, softmax, norms, rotary, the residual stream, the
               combine) is bfloat16 too.  Sums inside one product are left to
               the device, which adds bfloat16 products in float32 and rounds
               the result once.
"""

from __future__ import annotations

import functools
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from checks import minilm

tokenize = minilm.tokenize  # the hash tokenizer: [CLS] words [SEP]

PRECISIONS = ("float32", "stated", "lowered")


def _operand(x, precision: str):
    """``x`` as a product takes it.  ``stated`` rounds it to bfloat16
    (``reduce_precision``: a rounding no compiler pass may take out) and
    keeps it in float32, where a product of two such numbers is exact."""
    if precision == "stated":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def _mm(spec: str, a, b, precision: str):
    """``jnp.einsum(spec, a, b)`` at ``highest`` over operands as
    ``precision`` has them: float32, rounded to bfloat16 with a float32 sum
    (``stated``), or bfloat16 in and out (``lowered``, whose arrays are
    bfloat16 already)."""
    return jnp.einsum(spec, _operand(a, precision), _operand(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def kept(config: dict, key: str) -> list:
    return config[key][: int(config["num_hidden_layers"])]


def inv_freq(rope: dict, head_dim: int) -> tuple[np.ndarray, float]:
    """Inverse frequencies [rotary_dim / 2] (float64) of one entry of
    ``rope_parameters`` and the factor on cosine and sine."""
    dim = int(head_dim * float(rope.get("partial_rotary_factor", 1.0)))
    theta = float(rope["rope_theta"])
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope["rope_type"] == "default":
        return 1.0 / pos_freqs, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    factor, original = float(rope["factor"]), float(rope["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    blend = 1.0 - ramp  # 1: extrapolated (the frequency as it is), 0: interpolated
    freq = (1.0 / (factor * pos_freqs)) * (1.0 - blend) + (1.0 / pos_freqs) * blend
    return freq, float(rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rotary(x, freq, factor):
    """``x`` [T, H, hd] -> the first ``2 len(freq)`` dimensions rotated."""
    t = x.shape[0]
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :] * factor, jnp.sin(angles)[:, None, :] * factor
    cos, sin = cos.astype(x.dtype), sin.astype(x.dtype)
    half = freq.shape[0]
    x1, x2, rest = x[..., :half], x[..., half: 2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _gated(b, w_gate_up, w_down, precision):
    f = w_gate_up.shape[-1] // 2
    gate = _mm("td,df->tf", b, w_gate_up[..., :f], precision)
    up = _mm("td,df->tf", b, w_gate_up[..., f:], precision)
    return _mm("tf,fd->td", jax.nn.silu(gate) * up, w_down, precision)


@functools.partial(jax.jit, static_argnames=(
    "kind", "mlp", "window", "top_k", "scaling", "eps", "factor", "precision"))
def layer_forward(p, x, freq, *, kind: str, mlp: str, window: int, top_k: int,
                  scaling: float, eps: float, factor: float, precision: str = "float32"):
    """Steps 1-5 for one document: ``x`` [T, D] -> [T, D].  ``p`` is the
    layer's tree as ``encoders/laguna.py`` makes it (bfloat16)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}")
    dt = jnp.bfloat16 if precision == "lowered" else jnp.float32
    p = jax.tree_util.tree_map(lambda w: w.astype(dt), p)
    x = x.astype(dt)
    t = x.shape[0]
    hd, kv = p["wk"].shape[2], p["wk"].shape[1]
    a = _rmsnorm(x, p["attn_norm"], eps)
    q = _mm("td,dhe->the", a, p["wq"], precision)
    k = _mm("td,dhe->the", a, p["wk"], precision)
    v = _mm("td,dhe->the", a, p["wv"], precision)
    q, k = _rotary(q, freq, factor), _rotary(k, freq, factor)
    group = q.shape[1] // kv
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    see = j <= i
    if kind == "sliding_attention":
        see &= i - j < window
    s = _mm("qhd,khd->hqk", q, k, precision) / math.sqrt(hd)
    w = jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1)
    o = _mm("hqk,khd->qhd", w, v, precision)
    g = jax.nn.sigmoid(_mm("td,dh->th", a, p["wg"], precision))
    gated = (o * g[:, :, None]).reshape(t, -1)  # concat_h(g_h o_h)
    x = x + _mm("qc,cm->qm", gated, p["wo"].reshape(gated.shape[1], -1), precision)
    b = _rmsnorm(x, p["mlp_norm"], eps)
    if mlp == "dense":
        return x + _gated(b, p["mlp"]["w_gate_up"], p["mlp"]["w_down"], precision)
    m = p["moe"]
    # the router's product is none of the bfloat16 products the configuration states
    logits = jnp.einsum("td,de->te", b, m["router"], precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1)
    top, chosen = jax.lax.top_k(scores, top_k)
    weights = jnp.zeros_like(scores).at[jnp.arange(t)[:, None], chosen].set(
        scaling * top / jnp.sum(top, axis=-1, keepdims=True))
    f = m["w_gate_up"].shape[-1] // 2
    gate = _mm("td,edf->tef", b, m["w_gate_up"][..., :f], precision)
    up = _mm("td,edf->tef", b, m["w_gate_up"][..., f:], precision)
    # w_e E_e(b) = (w_e act_e) W2_e: one product over all experts.  The down
    # product's operand is act_e, so that is what ``stated`` rounds; the
    # weight rides on it in float32 and the float32 product carries it out
    act = _operand(jax.nn.silu(gate) * up, precision) * weights[:, :, None]
    routed = jnp.einsum("tef,efd->td", act, _operand(m["w_down"], precision),
                        precision=jax.lax.Precision.HIGHEST)
    return x + routed + _gated(b, m["shared"]["w_gate_up"], m["shared"]["w_down"], precision)


def layer_statics(config: dict, layer: int) -> dict:
    """The keyword arguments of ``layer_forward`` for layer ``layer`` and
    the layer's frequencies."""
    kind = kept(config, "layer_types")[layer]
    freq, factor = inv_freq(config["rope_parameters"][kind], int(config["head_dim"]))
    return {"freq": jnp.asarray(freq, jnp.float32),
            "kw": dict(kind=kind, mlp=kept(config, "mlp_layer_types")[layer],
                       window=int(config["sliding_window"]),
                       top_k=int(config["num_experts_per_tok"]),
                       scaling=float(config["moe_routed_scaling_factor"]),
                       eps=float(config["rms_norm_eps"]), factor=factor)}


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
                    p, jnp.asarray(x), st["freq"], precision=precision, **st["kw"]
                ).astype(jnp.float32))
            del p
            print(f"perfbench-reference layer {layer}: {len(states)} documents in "
                  f"{time.monotonic() - t0:.1f}s", file=sys.stderr, flush=True)
        out = [np.asarray(_rmsnorm(jnp.asarray(x[-1]).astype(dt), final_norm, eps)
                          .astype(jnp.float32)) for x in states]
    return np.stack(out)
