"""Plain reference of the GPT-2 decoder: a full-sequence forward pass in
``jax.numpy`` and float32 (pre-LN blocks, fused QKV, tanh-approximate GELU,
tied output head) as ``openai-community/gpt2`` defines it.  No cache, no
paging, no kernels; imports nothing of the program.

``lowered=True`` is the control: every matrix product takes its operands
rounded to float8 (e4m3), the nearest precision below the bfloat16 the
configuration states for the decoder.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from checks import minilm


def prompt_ids(prompt: str, decoder: dict, max_new_tokens: int) -> list[int]:
    """``[CLS] tokens [SEP]`` of the prompt by the hash tokenizer, cut to the
    model's positions, of which the tail that leaves room for the new tokens
    is kept (as the decode session keeps it).  ``decoder`` is the
    configuration's group of that name."""
    max_len = decoder["n_positions"]
    ids = minilm.tokenize(prompt, decoder["vocab_size"], max_len)
    return ids[-max(1, max_len - max_new_tokens):]


def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def forward(params, ids, *, heads: int, eps: float, lowered: bool = False):
    """[T] ids -> [T, V] logits."""
    low = _fp8 if lowered else (lambda x: x)

    def mm(spec, a, b):
        return jnp.einsum(spec, low(a), low(b))

    n_layers = sum(1 for k in params if k.startswith("h_"))
    t = ids.shape[0]
    x = params["wte"]["embedding"][ids] + params["wpe"]["embedding"][jnp.arange(t)]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(n_layers):
        p = params[f"h_{i}"]
        h = _layer_norm(x, p["ln_1"], eps)
        qkv = mm("td,de->te", h, p["c_attn"]["kernel"]) + p["c_attn"]["bias"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        hd = q.shape[-1] // heads
        q, k, v = (a.reshape(t, heads, hd) for a in (q, k, v))
        s = mm("qhd,khd->hqk", q, k) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
        ctx = mm("hqk,khd->qhd", w, v).reshape(t, heads * hd)
        x = x + mm("td,de->te", ctx, p["attn_proj"]["kernel"]) + p["attn_proj"]["bias"]
        h = _layer_norm(x, p["ln_2"], eps)
        m = mm("td,df->tf", h, p["c_fc"]["kernel"]) + p["c_fc"]["bias"]
        m = jax.nn.gelu(m, approximate=True)
        x = x + mm("tf,fd->td", m, p["mlp_proj"]["kernel"]) + p["mlp_proj"]["bias"]
    x = _layer_norm(x, params["ln_f"], eps)
    return mm("td,vd->tv", x, params["wte"]["embedding"])


def logits(params, ids: list[int], decoder: dict, lowered: bool = False, pad_to: int = 128):
    """Logits of every position of ``ids``, computed at a length padded to
    a multiple of ``pad_to`` (causal, so the padding changes nothing before
    it) so that few shapes compile."""
    heads, eps = decoder["n_head"], decoder["layer_norm_epsilon"]
    n = len(ids)
    padded = ids + [0] * (-n % pad_to)
    fwd = jax.jit(forward, static_argnames=("heads", "eps", "lowered"))
    with jax.default_matmul_precision("highest"):
        return fwd(params, jnp.asarray(padded, jnp.int32), heads=heads, eps=eps,
                   lowered=lowered)[:n]
