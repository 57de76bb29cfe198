"""Plain reference of the Kimi-Linear-48B-A3B embedder: the forward of layers
``0..num_hidden_layers-1`` of
https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct (config.json,
``model_type`` ``kimi_linear``; the Kimi Linear report, arXiv:2510.26692) in
``jax.numpy`` and float32 at ``highest`` precision, one document at a time,
no kernel, no bucket, no packing, no ``ragged_dot``; the document's vector
is the final-norm state of its last token.  Imports nothing of the program.

Layer ``l`` (``x`` [T, 2304] float32 residual; RMS norms ``w * x / rms`` with
eps ``rms_norm_eps`` 1e-5; no bias anywhere), its kind from
``linear_attn_config`` (whose lists count layers from 1):

1. ``a = rmsnorm(x)``.
2. KDA (``kda_layers``; 32 heads of 128, ``P`` = 4,096):
   ``q, k, v = silu(conv4(a W_q)), ...``: each a causal depthwise
   convolution over 4 taps with no bias (``u`` zero before the document's
   first token); ``q`` and ``k`` L2-normed a head (eps 1e-6), ``q`` times
   ``1/sqrt(128)``; ``g = -exp(A_log_h) softplus((a W_fa) W_fb + dt_bias)``
   [T, 32, 128]; ``beta = sigmoid(a W_b)`` [T, 32]; per head, token by
   token, ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t
   v_t^T`` from ``S_0 = 0``, ``o_t = S_t^T q_t``;
   ``o = rmsnorm_head(o) w * sigmoid((a W_ga) W_gb)``; ``x = x + o W_o``.
   MLA (``full_attn_layers``, ``mla_use_nope``: no rotary anywhere; 32
   heads): ``q = a W_q`` [T, 32, 192] (``q_lora_rank`` null: no low-rank
   path, no norm); ``[c | k_pe] = a W_kva`` [T, 512 + 64]; ``[k_nope | v] =
   rmsnorm(c) W_kvb`` [T, 32, 128 + 128]; ``k_h = [k_nope_h | k_pe]`` (the
   one 64-wide key under every head, unrotated); scores ``q_i . k_j /
   sqrt(192)`` for ``j <= i``, softmax, ``x = x + concat_h(softmax . v_h)
   W_o``.
3. ``b = rmsnorm(x)``.  Layer 0 (``first_k_dense_replace`` 1): ``x = x +
   (silu(b W1) * (b W3)) W2`` at 9,216.  Later layers: ``s = sigmoid(b W_r)``
   [T, 256]; ``S`` = the 8 largest of ``s + bias``; ``w_e = 2.446 s_e /
   (sum_S s + 1e-20)`` (the weights come from ``s``, not ``s + bias``); ``x = x
   + sum_{e in S, e held} w_e E_e(b) + E_shared(b)``, each ``E`` a gated MLP
   of 1,024, computed densely over the HELD experts (``first_expert`` ..
   ``first_expert + num_experts - 1``) with ``w`` zero outside ``S``.
4. after the last kept layer ``rmsnorm``; the vector is row ``T - 1``.

Departures, each for a reason:

* the expert share is the program's (the configuration's ``deployment``):
  what the experts held on the other chip would add is left out here too, so
  the comparison is of what this chip computes;
* the weights are those of ``encoders/kimi_linear.py`` (bfloat16, made from
  the seed; the bias, ``A_log`` and ``dt_bias`` float32) read as float32:
  what is compared is the computation, not the rounding of the parameters;
* the program keeps ``W_q | W_k | W_v`` in one matrix (and their taps in
  one), gate and up side by side (gate columns first), ``k_nope | v`` and
  ``c | k_pe`` as the published checkpoint does; the reference splits them;
* (i) the experts come in blocks of ``EXPERT_BLOCK`` (16);
* (ii) for step 2 every document is padded behind its text to
  ``max_seq_length`` under the causal mask, the causal convolution and the
  recurrence (no real token sees or reaches what lies behind it) and cut
  back; step 3 is a token's own and runs over blocks of ``TOKEN_BLOCK``
  (256) real tokens: each layer kind and precision one compiled program;
* ``assumed`` of the configuration file: the norm as ``w * x / rms``, the L2
  norm's eps, the router's eps, the hash tokenizer, last-token pooling; the
  output head is not built (an embedder generates nothing);
* layers come one at a time (``layer_params``), all documents through one
  layer before the next is made.

``precision`` (``checks/laguna.py`` has the three in full): ``"float32"`` the
yardstick; ``"stated"`` every product but the router's and the recurrence's
takes its operands rounded to bfloat16 and sums in float32, while the
convolutions, the L2 and RMS norms, the decay, ``beta``, the recurrence, the
output gate, the router, softmax, the residual stream and the combine stay
float32; ``"lowered"`` the control: all of that in bfloat16 too, the
recurrence's state among it.
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

EXPERT_BLOCK = 16
TOKEN_BLOCK = 256
_HIGHEST = jax.lax.Precision.HIGHEST


def _cast(tree, dt):
    return jax.tree_util.tree_map(lambda w: w.astype(dt), tree)


def _kda(p, a, *, heads: int, eps: float, precision: str):
    """Step 2 of a KDA layer over ``a`` [T, D] (normed): the addition to
    the residual."""
    t = a.shape[0]
    proj = p["w_o"].shape[0]
    dk = proj // heads
    qkv = _mm("td,de->te", a, p["w_qkv"], precision)
    taps = p["conv"].shape[0]
    before = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))  # zeros before the first token
    qkv = jax.nn.silu(sum(p["conv"][n] * before[n: n + t] for n in range(taps)))
    q, k, v = (qkv[:, n * proj:(n + 1) * proj].reshape(t, heads, dk) for n in range(3))
    l2 = lambda x: x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    q, k = l2(q) / math.sqrt(dk), l2(k)
    low = lambda wa, wb: _mm("tr,re->te", _mm("td,dr->tr", a, p[wa], precision), p[wb], precision)
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        low("w_fa", "w_fb").reshape(t, heads, dk) + p["dt_bias"].reshape(heads, dk))
    beta = jax.nn.sigmoid(_mm("td,dh->th", a, p["w_b"], precision))

    def step(s, token):  # s [H, dk, dv]: S_t = (I - b k k^T) Diag(exp(g)) S + b k v^T
        q_t, k_t, v_t, g_t, b_t = token
        s = jnp.exp(g_t)[:, :, None] * s
        k_s = jnp.einsum("hc,hcv->hv", k_t, s, precision=_HIGHEST)
        s = s + b_t[:, None, None] * k_t[:, :, None] * (v_t - k_s)[:, None, :]
        return s, jnp.einsum("hcv,hc->hv", s, q_t, precision=_HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, dk), a.dtype), (q, k, v, g, beta))
    o = _rmsnorm(o, p["o_norm"], eps) * jax.nn.sigmoid(low("w_ga", "w_gb").reshape(t, heads, dk))
    return _mm("te,ed->td", o.reshape(t, proj), p["w_o"], precision)


def _mla(p, a, *, eps: float, precision: str):
    """Step 2 of an MLA layer (no rotary, a direct query) over ``a`` [T, D]."""
    t = a.shape[0]
    rank = p["kv_norm"].shape[0]
    nope = p["wkv_b"].shape[2] - p["wo"].shape[1]
    q = _mm("td,dhe->the", a, p["wq"], precision)
    ckv = _mm("td,dr->tr", a, p["wkv_a"], precision)
    kv = _mm("tr,rhe->the", _rmsnorm(ckv[:, :rank], p["kv_norm"], eps),
             p["wkv_b"], precision)
    k = jnp.concatenate([kv[..., :nope], jnp.repeat(ckv[:, None, rank:], q.shape[1], axis=1)],
                        axis=-1)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    s = _mm("qhd,khd->hqk", q, k, precision) / math.sqrt(q.shape[-1])
    w = jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), axis=-1)
    o = _mm("hqk,khd->qhd", w, kv[..., nope:], precision).reshape(t, -1)  # concat_h(o_h)
    return _mm("qc,cm->qm", o, p["wo"].reshape(o.shape[1], -1), precision)


@functools.partial(jax.jit, static_argnames=("kind", "heads", "eps", "precision"))
def _mixer_padded(p, x, *, kind: str, heads: int, eps: float, precision: str):
    """Steps 1-2 over ``x`` [T, D] (a document with its padding behind it)."""
    dt = jnp.bfloat16 if precision == "lowered" else jnp.float32
    p = _cast({k: w for k, w in p.items() if k not in ("mlp", "moe")}, dt)
    x = x.astype(dt)
    a = _rmsnorm(x, p["attn_norm"], eps)
    if kind == "kda":
        return x + _kda(p, a, heads=heads, eps=eps, precision=precision)
    return x + _mla(p, a, eps=eps, precision=precision)


@functools.partial(jax.jit, static_argnames=(
    "mlp", "top_k", "scaling", "first", "eps", "precision"))
def _mlp_block(p, x, *, mlp: str, top_k: int, scaling: float, first: int, eps: float,
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
    scores = jax.nn.sigmoid(jnp.einsum("td,de->te", b, router, precision=_HIGHEST))
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    top = jnp.take_along_axis(scores, chosen, axis=-1)  # the weights: the scores WITHOUT the bias
    weights = jnp.zeros_like(scores).at[jnp.arange(t)[:, None], chosen].set(
        scaling * top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20))
    held = moe["w_gate_up"].shape[0]
    weights = weights[:, first: first + held]  # the experts this chip holds
    f = moe["w_gate_up"].shape[-1] // 2
    routed = jnp.zeros_like(x)
    for e in range(0, held, EXPERT_BLOCK):
        block = _cast({k: moe[k][e: e + EXPERT_BLOCK] for k in ("w_gate_up", "w_down")}, dt)
        gate = _mm("td,edf->tef", b, block["w_gate_up"][..., :f], precision)
        up = _mm("td,edf->tef", b, block["w_gate_up"][..., f:], precision)
        # w_e E_e(b) = (w_e act_e) W_d: the down product's operand is act_e, which is
        # what ``stated`` rounds; the weight rides on it and the product carries it out
        act = (_operand(jax.nn.silu(gate) * up, precision)
               * weights[:, e: e + EXPERT_BLOCK, None])
        routed = routed + jnp.einsum("tef,efd->td", act, _operand(block["w_down"], precision),
                                     precision=_HIGHEST)
    shared = _cast(moe["shared"], dt)
    return x + routed + _gated(b, shared["w_gate_up"], shared["w_down"], precision)


def layer_forward(p, x, _freq=None, *, kind: str, heads: int, mlp: str, top_k: int,
                  scaling: float, first: int, eps: float, max_len: int,
                  precision: str = "float32"):
    """Steps 1-3 for one document: ``x`` [T, D] -> [T, D].  ``p`` is the
    layer's tree as ``encoders/kimi_linear.py`` makes it.  Step 2 over the
    document padded to ``max_len``, step 3 over blocks of its real tokens
    (departure (ii)).  ``_freq`` is nothing: no layer here turns a
    dimension."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}")
    t = x.shape[0]
    padded = jnp.pad(jnp.asarray(x), ((0, max_len - t), (0, 0)))
    mixed = _mixer_padded(p, padded, kind=kind, heads=heads, eps=eps, precision=precision)
    blocks = -(-t // TOKEN_BLOCK)
    mixed = jnp.pad(mixed[:t], ((0, blocks * TOKEN_BLOCK - t), (0, 0)))
    out = [_mlp_block(p, mixed[n * TOKEN_BLOCK: (n + 1) * TOKEN_BLOCK], mlp=mlp, top_k=top_k,
                      scaling=scaling, first=first, eps=eps, precision=precision)
           for n in range(blocks)]
    return jnp.concatenate(out)[:t]


def layer_statics(config: dict, layer: int) -> dict:
    """The keyword arguments of ``layer_forward`` for layer ``layer`` (and no
    rotary frequencies)."""
    lac = config["linear_attn_config"]
    kind = "latent" if layer + 1 in lac["full_attn_layers"] else "kda"
    return {"freq": None,
            "kw": dict(kind=kind, heads=int(lac["num_heads"] if kind == "kda"
                                            else config["num_attention_heads"]),
                       mlp="dense" if layer < int(config["first_k_dense_replace"]) else "sparse",
                       top_k=int(config["num_experts_per_token"]),
                       scaling=float(config["routed_scaling_factor"]),
                       first=int(config["first_expert"]),
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
