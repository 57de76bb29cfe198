"""The Laguna-XS.2 embedder of ``vs-laguna-xs2-bf16-marcodoc``: the program's
config object from the configuration file's published keys, and its weights
made on the device from the seed, layer by layer (a sparse layer is 1.7 GB in
bfloat16), in one jitted call each.  The program is handed these arrays; the
plain reference (``checks/laguna.py``) makes each layer again and takes
nothing the program has made.

Scales (``assumed`` in the configuration file).  With last-token pooling
every document ends in the same token, so at 1/sqrt(fan-in) everywhere the
vectors of distinct documents lie within cosine 0.9 of each other and an
index cannot tell them apart.  Two readings on the chip set the scales (24
documents, two seeds a setting; PERF.md 6).  Sharp attention (keys and queries
at 2.5/sqrt(fan-in)) separates documents but makes the forward chaotic: a
layer multiplies a perturbation about fourfold, and bfloat16 products alone
put the program 0.2-0.4 from the float32 reference's unit vector, where no
limit can tell it from a lower precision.  So values and the attention's
output are drawn at 4/sqrt(fan-in) (what attention brings outweighs the
token's own embedding: distinct documents lie at cosine 0.07, at most 0.2),
keys and queries at 1.4/sqrt(fan-in) (a token still attends to a few others,
and the program stays within 0.02-0.04 of the reference), and the router at
4/sqrt(fan-in) (the eight chosen scores fall off steeply, as a trained
router's do: the eighth expert's weight is near 0.04 of 2.5, not 0.23, so a
near-tie at the boundary, which bfloat16 products flip in one token of five,
moves that token by a hundredth and not by a tenth); the rest at
1/sqrt(fan-in), token embeddings at unit scale, norms at one.

The draws are XLA's own bit generator (``rbg`` keys): the chip fills 7.3 GB
in seconds where threefry takes over a minute.  Its bits differ from one
backend to another, which harms nothing: the program and the reference draw
in the same process.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import seeded

#: the forward's jitted programs as a device trace names them (a prefix: the
#: packed ragged forward is ``..._ragged``), and the operations of the
#: grouped matrix product inside them: ``jax.lax.ragged_dot`` is lowered by
#: XLA to a TPU kernel of its own, shown as ``ragged-dot-metadata`` (the
#: tiles' bookkeeping) and ``ragged-dot-none`` (the product)
PROGRAMS = ("jit_pw_moe_embedder_forward",)
GROUPED_MATMUL_OPS = ("ragged-dot",)

QK_GAIN, VO_GAIN, ROUTER_GAIN = 1.4, 4.0, 4.0
_KINDS = {"full_attention": "full", "sliding_attention": "window"}


def kept(config: dict, key: str) -> list:
    """The kept layers' entries of a published per-layer list."""
    return config[key][: int(config["num_hidden_layers"])]


def model_config(config: dict):
    """``CausalMoeEmbedderConfig`` of the configuration file."""
    from pathway_tpu.models.causal_moe_embedder import CausalMoeEmbedderConfig, RotarySpec

    rope = config["rope_parameters"]
    full, window = rope["full_attention"], rope["sliding_attention"]
    longest = int(config["max_seq_length"])
    return CausalMoeEmbedderConfig(
        vocab_size=config["vocab_size"], hidden_dim=config["hidden_size"],
        head_dim=config["head_dim"], num_kv_heads=config["num_key_value_heads"],
        layer_types=tuple(_KINDS[t] for t in kept(config, "layer_types")),
        heads_per_layer=tuple(kept(config, "num_attention_heads_per_layer")),
        mlp_types=tuple(kept(config, "mlp_layer_types")),
        window=config["sliding_window"],
        full_rotary=RotarySpec(
            theta=float(full["rope_theta"]), rotary_factor=float(full["partial_rotary_factor"]),
            yarn_factor=float(full["factor"]),
            original_max_len=int(full["original_max_position_embeddings"]),
            beta_fast=float(full["beta_fast"]), beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"])),
        window_rotary=RotarySpec(theta=float(window["rope_theta"]),
                                 rotary_factor=float(window["partial_rotary_factor"])),
        dense_mlp_dim=config["intermediate_size"], num_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"], expert_dim=config["moe_intermediate_size"],
        shared_expert_dim=config["shared_expert_intermediate_size"],
        routed_scaling=float(config["moe_routed_scaling_factor"]),
        rms_eps=float(config["rms_norm_eps"]), max_len=longest,
        seq_buckets=tuple(b for b in (32, 64, 128, 256, 512, 1024)
                          if b < longest) + (longest,),
        q_block=int(config.get("attention_q_block", 512)),
    )


@functools.lru_cache(maxsize=None)
def _layer_program(config_json: str, layer: int):
    import json

    from pathway_tpu.models.causal_moe_embedder import CausalMoeEmbedder

    model = CausalMoeEmbedder(model_config(json.loads(config_json)))
    return jax.jit(lambda p, x: model.layer(p, layer, x))


def program_layer(config: dict, layer: int, layer_params: dict, x):
    """The PROGRAM's block ``layer`` over one text's states ``x`` [T, D]:
    ``checks/ingest_laguna.py`` feeds it the reference's own input."""
    import json

    return _layer_program(json.dumps(config, sort_keys=True), layer)(layer_params, x)


@functools.partial(jax.jit, static_argnames=("shape", "fan_in", "gain"))
def _matrix(key, *, shape, fan_in, gain=1.0):
    fast = jax.random.wrap_key_data(jnp.concatenate([key, key ^ 0x5EED]), impl="rbg")
    return (jax.random.normal(fast, shape, jnp.float32)
            * (gain * fan_in ** -0.5)).astype(jnp.bfloat16)


def embedding_params(config: dict, seed: int) -> dict:
    """Token embeddings (unit scale) and the final norm."""
    d = int(config["hidden_size"])
    key = jax.random.fold_in(seeded.key_of(seed, 0), 1_000_000)
    return {"tok_emb": _matrix(key, shape=(int(config["vocab_size"]), d), fan_in=1),
            "final_norm": jnp.ones((d,), jnp.bfloat16)}


def layer_params(config: dict, seed: int, layer: int) -> dict:
    """Layer ``layer`` in the program's layout, bfloat16."""
    d, hd = int(config["hidden_size"]), int(config["head_dim"])
    kv = int(config["num_key_value_heads"])
    h = int(kept(config, "num_attention_heads_per_layer")[layer])
    keys = iter(jax.random.split(jax.random.fold_in(seeded.key_of(seed, 0), layer), 12))

    def mat(shape, fan_in, gain=1.0):
        return _matrix(next(keys), shape=shape, fan_in=fan_in, gain=gain)

    def mlp(lead, width):
        return {"w_gate_up": mat(lead + (d, 2 * width), d),
                "w_down": mat(lead + (width, d), width)}

    ones = jnp.ones((d,), jnp.bfloat16)
    out = {
        "attn_norm": ones, "mlp_norm": ones,
        "wq": mat((d, h, hd), d, QK_GAIN), "wk": mat((d, kv, hd), d, QK_GAIN),
        "wv": mat((d, kv, hd), d, VO_GAIN), "wg": mat((d, h), d),
        "wo": mat((h, hd, d), h * hd, VO_GAIN),
    }
    if kept(config, "mlp_layer_types")[layer] == "dense":
        out["mlp"] = mlp((), int(config["intermediate_size"]))
    else:
        experts = int(config["num_experts"])
        out["moe"] = {
            "router": mat((d, experts), d, ROUTER_GAIN),
            **mlp((experts,), int(config["moe_intermediate_size"])),
            "shared": mlp((), int(config["shared_expert_intermediate_size"])),
        }
    return out


def params(config: dict, seed: int) -> dict:
    """The whole tree, layer by layer."""
    out = embedding_params(config, seed)
    for layer in range(int(config["num_hidden_layers"])):
        out[f"layer_{layer}"] = layer_params(config, seed, layer)
    return out


def sizes(config: dict) -> dict:
    """What ``costs_laguna`` needs, from the configuration file."""
    return {
        "hidden": int(config["hidden_size"]), "head_dim": int(config["head_dim"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "heads": [int(h) for h in kept(config, "num_attention_heads_per_layer")],
        "layer_types": [_KINDS[t] for t in kept(config, "layer_types")],
        "mlp_types": list(kept(config, "mlp_layer_types")),
        "window": int(config["sliding_window"]), "dense_ffn": int(config["intermediate_size"]),
        "experts": int(config["num_experts"]), "top_k": int(config["num_experts_per_tok"]),
        "expert_ffn": int(config["moe_intermediate_size"]),
        "shared_ffn": int(config["shared_expert_intermediate_size"]),
        "vocab": int(config["vocab_size"]),
    }
