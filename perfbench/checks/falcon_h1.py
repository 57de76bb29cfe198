"""Plain reference of the Falcon-H1 embedder: the forward of layers
``0..num_hidden_layers-1`` of
https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct (config.json) in
``jax.numpy`` and float32 at ``highest`` precision, one document at a time,
the state-space recurrence TOKEN BY TOKEN (``lax.scan`` over the tokens: no
chunk, no packing, no kernel, no bucket); the document's vector is the
final-norm state of its last token.  Imports nothing of the program.

The layer (``x`` [T, hidden] float32; RMS norms with ``rms_norm_eps``; no
bias but the convolution's; the widths in brackets are the published ones):

1. ``x0 = tok_emb[ids] * embedding_multiplier``.
2. ``a = rmsnorm(x)``.  Attention: ``u = a * attention_in_multiplier``;
   ``q = u Wq`` [T, 20, 128], ``k = (u Wk) * key_multiplier`` [T, 4, 128],
   ``v = u Wv``; rotary over the whole head (``rope_theta``, no scaling);
   query head ``h`` reads KV head ``h // 5``; softmax(``q_i . k_j /
   sqrt(128)``) over ``j <= i``; ``attn = (o Wo) * attention_out_multiplier``.
3. The mixer, on the same ``a``: ``u = a * ssm_in_multiplier``;
   ``[z | xBC | dt] = (u W_in) * mup_vector`` (``ssm_multipliers[0..4]`` over
   the zones z [4096], x [4096], B [512], C [512], dt [32]); ``xBC =
   silu(conv(xBC))``: ``conv_t = bias + sum_k w[k] xBC[t - 3 + k]``, nothing
   before the document's first token; ``x`` [T, 32, 128], ``B``, ``C``
   [T, 2, 256]; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head
   ``h`` of group ``h // 16``: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``
   (``S_{-1} = 0``), ``y_t = S_t C_t + D x_t``; ``y = y * silu(z)``, then
   RMS-normed over each group's 2,048 channels, times the norm's weight;
   ``ssm = (y W_out) * ssm_out_multiplier``.
4. ``x = x + attn + ssm``.
5. ``b = rmsnorm(x)``; ``x = x + ((b W_up) * silu((b W_gate) *
   mlp_multipliers[0])) W_down * mlp_multipliers[1]``.
6. after the last kept layer ``rmsnorm``; the vector is row ``T - 1``.

Departures, each for a reason:

* the weights are those of ``encoders/falcon_h1.py`` (bfloat16, made from the
  seed) read as float32: what is compared is the computation, not the
  rounding of the parameters;
* the program keeps gate and up projections side by side in one matrix (gate
  columns first); the reference splits it;
* ``assumed`` of the configuration file: the order z | x | B | C | dt of
  ``W_in``'s columns, the gate before the grouped norm
  (``mamba_norm_before_gate`` false), rotary pairing dimension ``i`` with
  ``i + 64``, no clamp on ``dt``, last-token pooling: Mamba-2's and
  ``transformers``' conventions;
* layers come one at a time (``layer_params``), all documents through one
  layer before the next is made: a layer is 1.7 GB in float32.

``precision`` says in what the forward is computed:

``"float32"``  the yardstick: every array and product float32 at ``highest``.
``"stated"``   what the configuration's ``precision`` group states: ``W_in``,
               ``W_out``, ``Wq/Wk/Wv/Wo``, attention's two products and the
               MLP's take their two operands rounded to bfloat16 and sum in
               float32; the residual stream, norms, rotary, softmax, the
               convolution, softplus, the recurrence (decays, state, the
               products with ``B`` and ``C``, ``D x``), the gate and the
               gated norm stay float32.  A program at the stated precision
               differs from this forward by the order of float32 sums alone.
``"lowered"``  the control, one step down: what ``stated`` keeps in float32
               is bfloat16 too (the state and its decays among it).
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

#: the configuration's scalar multipliers, in the order ``layer_statics``
#: hands them to ``layer_forward``
MULTIPLIERS = ("attention_in_multiplier", "attention_out_multiplier", "key_multiplier",
               "ssm_in_multiplier", "ssm_out_multiplier")


def _operand(x, precision: str):
    """``x`` as a product takes it.  ``stated`` rounds it to bfloat16
    (``reduce_precision``: a rounding no compiler pass may take out) and
    keeps it in float32, where a product of two such numbers is exact."""
    if precision == "stated":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def _mm(spec: str, a, b, precision: str):
    """``jnp.einsum(spec, a, b)`` at ``highest`` over operands as
    ``precision`` has them."""
    return jnp.einsum(spec, _operand(a, precision), _operand(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rotary(x, freq):
    """``x`` [T, H, hd] -> every dimension rotated, ``i`` with ``i + hd/2``."""
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :].astype(x.dtype), jnp.sin(angles)[:, None, :].astype(x.dtype)
    half = freq.shape[0]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _conv(x, weight, bias):
    """``out_t = bias + sum_k weight[k] x[t - (K - 1) + k]`` over one
    document, zeros before its first token."""
    taps, t = weight.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    out = jnp.broadcast_to(bias, x.shape)
    for k in range(taps):
        out = out + weight[k] * padded[k: k + t]
    return out


def _recurrence(x, dt, a, b, c, d):
    """Token by token: ``x`` [T, H, P], ``dt`` [T, H], ``a`` [H], ``b``/``c``
    [T, G, N], ``d`` [H] -> ``y`` [T, H, P], all in ``x``'s dtype."""
    per_group = x.shape[1] // b.shape[1]

    def step(state, token):
        x_t, dt_t, b_t, c_t = token
        b_t, c_t = jnp.repeat(b_t, per_group, axis=0), jnp.repeat(c_t, per_group, axis=0)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1) + d[:, None] * x_t

    zero = jnp.zeros(x.shape[1:] + (b.shape[2],), x.dtype)
    return jax.lax.scan(step, zero, (x, dt, b, c))[1]


@functools.partial(jax.jit, static_argnames=("groups", "state", "eps", "precision"))
def layer_forward(p, x, freq, *, groups: int, state: int, eps: float, multipliers: tuple,
                  ssm_multipliers: tuple, mlp_multipliers: tuple, precision: str = "float32"):
    """Steps 2-5 for one document: ``x`` [T, D] -> [T, D].  ``p`` is the
    layer's tree as ``encoders/falcon_h1.py`` makes it (bfloat16)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}")
    attn_in, attn_out, key_mult, ssm_in, ssm_out = multipliers
    dtype = jnp.bfloat16 if precision == "lowered" else jnp.float32
    p = jax.tree_util.tree_map(lambda w: w.astype(dtype), p)
    x = x.astype(dtype)
    t = x.shape[0]
    a = _rmsnorm(x, p["attn_norm"], eps)

    # attention
    hd, kv = p["wk"].shape[2], p["wk"].shape[1]
    u = a * attn_in
    q = _mm("td,dhe->the", u, p["wq"], precision)
    k = _mm("td,dhe->the", u, p["wk"], precision) * key_mult
    v = _mm("td,dhe->the", u, p["wv"], precision)
    q, k = _rotary(q, freq), _rotary(k, freq)
    group = q.shape[1] // kv
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    s = _mm("qhd,khd->hqk", q, k, precision) / math.sqrt(hd)
    w = jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), axis=-1)
    o = _mm("hqk,khd->qhd", w, v, precision).reshape(t, -1)
    attn = _mm("qc,cm->qm", o, p["wo"].reshape(o.shape[1], -1), precision) * attn_out

    # the mixer
    heads, d_ssm, bc = p["dt_bias"].shape[0], p["norm"].shape[0], groups * state
    zones = (d_ssm, d_ssm, bc, bc, heads)
    mup = jnp.concatenate([jnp.full((width,), m, dtype)
                           for width, m in zip(zones, ssm_multipliers)])
    proj = _mm("td,dc->tc", a * ssm_in, p["w_in"], precision) * mup
    z, xbc, step = proj[:, :d_ssm], proj[:, d_ssm: 2 * d_ssm + 2 * bc], proj[:, 2 * d_ssm + 2 * bc:]
    xbc = jax.nn.silu(_conv(xbc, p["conv_w"], p["conv_b"]))
    y = _recurrence(
        xbc[:, :d_ssm].reshape(t, heads, d_ssm // heads),
        jax.nn.softplus(step + p["dt_bias"]), -jnp.exp(p["a_log"]),
        xbc[:, d_ssm: d_ssm + bc].reshape(t, groups, state),
        xbc[:, d_ssm + bc:].reshape(t, groups, state), p["d"])
    y = (y.reshape(t, d_ssm) * jax.nn.silu(z)).reshape(t, groups, d_ssm // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + eps)
    ssm = _mm("tc,cd->td", y.reshape(t, d_ssm) * p["norm"], p["w_out"], precision) * ssm_out

    x = x + attn + ssm
    b = _rmsnorm(x, p["mlp_norm"], eps)
    f = p["w_gate_up"].shape[1] // 2
    gate = _mm("td,df->tf", b, p["w_gate_up"][:, :f], precision) * mlp_multipliers[0]
    up = _mm("td,df->tf", b, p["w_gate_up"][:, f:], precision)
    return x + _mm("tf,fd->td", up * jax.nn.silu(gate), p["w_down"], precision) * mlp_multipliers[1]


def layer_statics(config: dict, layer: int) -> dict:
    """The keyword arguments of ``layer_forward`` (every layer is of one
    kind) and the rotary frequencies."""
    hd = int(config["head_dim"])
    freq = 1.0 / float(config["rope_theta"]) ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    return {"freq": jnp.asarray(freq, jnp.float32),
            "kw": dict(groups=int(config["mamba_n_groups"]), state=int(config["mamba_d_state"]),
                       eps=float(config["rms_norm_eps"]),
                       multipliers=tuple(float(config[m]) for m in MULTIPLIERS),
                       ssm_multipliers=tuple(float(m) for m in config["ssm_multipliers"]),
                       mlp_multipliers=tuple(float(m) for m in config["mlp_multipliers"]))}


def encode(config: dict, texts: list[str], embedding_params, layer_params,
           precision: str = "float32") -> np.ndarray:
    """Vectors [n, D] (float32, not normalised) of ``texts``.
    ``embedding_params()`` and ``layer_params(l)`` make the weights; all
    documents go through one layer before the next is made."""
    dtype = jnp.bfloat16 if precision == "lowered" else jnp.float32
    eps = float(config["rms_norm_eps"])
    rows = [tokenize(t, int(config["vocab_size"]), int(config["max_seq_length"]))
            for t in texts]
    with jax.default_matmul_precision("highest"):
        emb = embedding_params()
        scale = jnp.asarray(float(config["embedding_multiplier"]), dtype)
        states = [np.asarray((emb["tok_emb"][jnp.asarray(r)].astype(dtype) * scale)
                             .astype(jnp.float32)) for r in rows]
        final_norm = emb["final_norm"].astype(dtype)
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
        out = [np.asarray(_rmsnorm(jnp.asarray(x[-1]).astype(dtype), final_norm, eps)
                          .astype(jnp.float32)) for x in states]
    return np.stack(out)
