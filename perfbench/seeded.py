"""Weights and index rows, made on the device from the seed in one jitted
call each.  The program is handed these arrays and the plain references read
the same arrays: neither side takes anything the other has made.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def key_of(seed: int, stream: int) -> jax.Array:
    """A PRNG key for ``seed`` (any whole number up to 2**63) and a stream
    number (0 weights of the encoder, 1 index rows, 2 weights of the
    decoder)."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


@functools.partial(jax.jit, static_argnames=(
    "vocab", "hidden", "layers", "heads", "ffn", "positions", "types"))
def minilm_params(key, *, vocab, hidden, layers, heads, ffn, positions, types):
    """The parameter tree of a BERT-style encoder in the layout of flax's
    ``MultiHeadDotProductAttention`` / ``Dense`` / ``LayerNorm`` / ``Embed``,
    float32.  Token embeddings are drawn at unit scale, position and type
    embeddings at 0.02 and matrices at 0.3/sqrt(fan-in), so that distinct
    texts embed apart as a trained encoder's do: passages that share no word
    lie near cosine 0.1, where 3M random unit rows reach 0.26, and a top-10
    then mixes ingested passages with prefilled rows.  (With every table
    alike and matrices at 1/sqrt(fan-in), mean pooling leaves every text
    within cosine 0.8-0.97 of every other and the prefilled rows never
    rank.)"""
    hd = hidden // heads
    keys = iter(jax.random.split(key, 3 + 6 * layers))

    def normal(shape, std):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def norm():
        return {"scale": jnp.ones((hidden,), jnp.float32),
                "bias": jnp.zeros((hidden,), jnp.float32)}

    params = {
        "tok_emb": {"embedding": normal((vocab, hidden), 1.0)},
        "pos_emb": {"embedding": normal((positions, hidden), 0.02)},
        "type_emb": {"embedding": normal((types, hidden), 0.02)},
        "ln_emb": norm(),
    }
    s_h = 0.3 * hidden ** -0.5
    for i in range(layers):
        attn = {}
        for name in ("query", "key", "value"):
            attn[name] = {"kernel": normal((hidden, heads, hd), s_h),
                          "bias": jnp.zeros((heads, hd), jnp.float32)}
        attn["out"] = {"kernel": normal((heads, hd, hidden), s_h),
                       "bias": jnp.zeros((hidden,), jnp.float32)}
        params[f"layer_{i}"] = {
            "attention": attn,
            "ln1": norm(),
            "mlp_in": {"kernel": normal((hidden, ffn), s_h),
                       "bias": jnp.zeros((ffn,), jnp.float32)},
            "mlp_out": {"kernel": normal((ffn, hidden), 0.3 * ffn ** -0.5),
                        "bias": jnp.zeros((hidden,), jnp.float32)},
            "ln2": norm(),
        }
    return params


@functools.partial(jax.jit, static_argnames=(
    "vocab", "hidden", "layers", "ffn", "positions"))
def gpt2_params(key, *, vocab, hidden, layers, ffn, positions):
    """The parameter tree of a GPT-2-style decoder in the layout of the
    program's ``Decoder`` (flax ``Embed`` / ``Dense`` / ``LayerNorm``; fused
    ``c_attn``, tied output head), float32.  GPT-2's own initialisation
    (normal 0.02, residual projections scaled by 1/sqrt(2 layers)) except
    the token table at 0.05, so that logits spread over a few units and a
    gap between two tokens is more than bfloat16's noise."""
    keys = iter(jax.random.split(key, 2 + 4 * layers))

    def normal(shape, std):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def norm():
        return {"scale": jnp.ones((hidden,), jnp.float32),
                "bias": jnp.zeros((hidden,), jnp.float32)}

    def dense(n_in, n_out, std):
        return {"kernel": normal((n_in, n_out), std),
                "bias": jnp.zeros((n_out,), jnp.float32)}

    params = {"wte": {"embedding": normal((vocab, hidden), 0.05)},
              "wpe": {"embedding": normal((positions, hidden), 0.02)},
              "ln_f": norm()}
    proj = 0.02 * (2 * layers) ** -0.5
    for i in range(layers):
        params[f"h_{i}"] = {
            "ln_1": norm(), "c_attn": dense(hidden, 3 * hidden, 0.02),
            "attn_proj": dense(hidden, hidden, proj),
            "ln_2": norm(), "c_fc": dense(hidden, ffn, 0.02),
            "mlp_proj": dense(ffn, hidden, proj),
        }
    return params


def encoder_params(config: dict, seed: int):
    """The encoder's weights for a configuration file's ``encoder`` group."""
    e = config["encoder"]
    return minilm_params(
        key_of(seed, 0), vocab=e["vocab_size"], hidden=e["hidden_size"],
        layers=e["num_hidden_layers"], heads=e["num_attention_heads"],
        ffn=e["intermediate_size"], positions=e["max_position_embeddings"],
        types=e["type_vocab_size"],
    )


@functools.partial(jax.jit, static_argnames=("rows", "dim"))
def row_block(key, block: jax.Array, *, rows: int, dim: int) -> jax.Array:
    """Block ``block`` of the index's prefilled rows: ``rows`` x ``dim``
    float32 normal draws (the index normalises them as it stores them)."""
    return jax.random.normal(jax.random.fold_in(key, block), (rows, dim), jnp.float32)
