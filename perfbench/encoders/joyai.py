"""The JoyAI-LLM-Flash embedder of ``vs-joyai-flash-bf16-marcodoc``: the
program's config object from the configuration file's published keys, and its
weights made on the device from the seed, layer by layer (a sparse layer is
2.48 GB in bfloat16), in one jitted call a matrix.  The program is handed
these arrays; the plain reference (``checks/joyai.py``) makes each layer again
and takes nothing the program has made.

What this directory's ``README.md`` would say of this builder if this PR could
edit it (PERF.md 7 #17): ``sizes`` feeds ``costs_joyai.py`` and, because it
carries ``hidden`` and ``expert_ffn``, ``costs_laguna.grouped_matmul_*`` too,
so the grouped product's readers serve this cell unedited; the deployment is
``servers/vector_store_joyai.py`` (``vector_store_laguna.py``'s with the
``mla.*`` counters beside ``moe.*``); the reference pads every document behind
its text to ``max_seq_length`` (one compiled program a layer kind and
precision).

Scales (``assumed`` in the configuration file), as ``encoders/laguna.py`` sets
them and for its reasons: with last-token pooling every document ends in the
same token, so values and the attention's output are drawn at 4/sqrt(fan-in)
(what attention brings outweighs the token's own embedding) and what makes a
score at 1.4/sqrt(fan-in).  In a latent layer that is: ``W_uq`` and the
``k_nope`` columns of ``W_ukv`` at 1.4 (their inputs are RMS-normed latents,
so a head's score has the spread Laguna's has), the ONE rotary key's 64 columns
of ``W_dkv`` at 1.4 (its input is the layer's normed state), the ``v`` columns
of ``W_ukv`` and ``W_o`` at 4; ``W_dq`` and the latent's 512 columns of
``W_dkv`` at 1 (a norm follows them).  The router at 1/sqrt(fan-in): its
logits then have deviation 1 and a sigmoid's scores spread over (0, 1) (a
sixth under 0.27, a sixth over 0.73) and do not sit at 0.5; the eight chosen
of 256 read 0.87 to 0.94, so their weights are near 2.5/8 each whatever the
gain, where a softmax's fall off: a near-tie at the boundary moves a token by
more than in Laguna's cell, which ``score_gap``'s readings show (PERF.md 6).
At 2/sqrt(fan-in) the chosen scores crowd into 0.977-0.996 and any bias picks
among them.  The selection bias is a normal draw of deviation ``BIAS_SCALE``:
at 0.004 it changes which eight experts run for three tokens in ten (the
eighth and ninth scores lie 0.0046 apart at the median) and leaves the others'
choice alone (``tests/test_causal_latent_embedder.py`` reads the share at the
published router's size); zero would leave the mechanism untested.  It is
float32, as the published checkpoint keeps it.

The draws are XLA's own bit generator (``encoders/laguna.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import seeded
from encoders.laguna import _matrix, embedding_params  # noqa: F401 - the same draw

#: the forward's jitted programs as a device trace names them (a prefix: the
#: packed forward is ``..._ragged``), and the operations of the grouped matrix
#: product inside them (``jax.lax.ragged_dot``: ``ragged-dot-*``)
PROGRAMS = ("jit_pw_moe_embedder_forward",)
GROUPED_MATMUL_OPS = ("ragged-dot",)

QK_GAIN, VO_GAIN, ROUTER_GAIN, BIAS_SCALE = 1.4, 4.0, 1.0, 0.004
TOKEN_BUCKETS = (1536, 3072, 4608, 6144)


def mlp_types(config: dict) -> list[str]:
    """"dense" for the ``first_k_dense_replace`` leading layers, then "sparse"
    (``moe_layer_freq`` 1: every further layer)."""
    if int(config["moe_layer_freq"]) != 1:
        raise ValueError("moe_layer_freq other than 1 is not built")
    dense = int(config["first_k_dense_replace"])
    return ["dense" if layer < dense else "sparse"
            for layer in range(int(config["num_hidden_layers"]))]


def model_config(config: dict):
    """``CausalMoeEmbedderConfig`` of the configuration file: every layer
    latent attention, the sigmoid router with its selection bias."""
    from pathway_tpu.models.causal_moe_embedder import CausalMoeEmbedderConfig, RotarySpec

    if (config["scoring_func"], config["topk_method"]) != ("sigmoid", "noaux_tc") \
            or (config["n_group"], config["topk_group"]) != (1, 1) \
            or not config["norm_topk_prob"] or config["rope_scaling"] is not None \
            or int(config["n_shared_experts"]) != 1:
        raise ValueError("a router, group limit, rotary scaling or shared-expert count "
                         "that the program does not build")
    layers, longest = int(config["num_hidden_layers"]), int(config["max_seq_length"])
    return CausalMoeEmbedderConfig(
        vocab_size=config["vocab_size"], hidden_dim=config["hidden_size"],
        layer_types=("latent",) * layers,
        heads_per_layer=(int(config["num_attention_heads"]),) * layers,
        mlp_types=tuple(mlp_types(config)),
        latent_q_rank=config["q_lora_rank"], latent_kv_rank=config["kv_lora_rank"],
        latent_nope_dim=config["qk_nope_head_dim"], latent_rope_dim=config["qk_rope_head_dim"],
        latent_v_dim=config["v_head_dim"],
        latent_rotary=RotarySpec(theta=float(config["rope_theta"]),
                                 interleaved=bool(config["rope_interleave"])),
        dense_mlp_dim=config["intermediate_size"], num_experts=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"], expert_dim=config["moe_intermediate_size"],
        shared_expert_dim=int(config["n_shared_experts"]) * config["moe_intermediate_size"],
        routed_scaling=float(config["routed_scaling_factor"]), router_scoring="sigmoid",
        rms_eps=float(config["rms_norm_eps"]), max_len=longest,
        seq_buckets=tuple(b for b in (32, 64, 128, 256, 512, 1024)
                          if b < longest) + (longest,),
        q_block=int(config.get("attention_q_block", 512)),
        token_buckets=tuple(config.get("token_buckets", TOKEN_BUCKETS)),
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


@functools.partial(jax.jit, static_argnames=("experts",))
def _bias(key, *, experts):
    fast = jax.random.wrap_key_data(jnp.concatenate([key, key ^ 0x5EED]), impl="rbg")
    return jax.random.normal(fast, (experts,), jnp.float32) * BIAS_SCALE


def layer_params(config: dict, seed: int, layer: int) -> dict:
    """Layer ``layer`` in the program's layout, bfloat16 (the bias float32)."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    qr, kvr = int(config["q_lora_rank"]), int(config["kv_lora_rank"])
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    vd = int(config["v_head_dim"])
    keys = iter(jax.random.split(jax.random.fold_in(seeded.key_of(seed, 0), layer), 16))

    def mat(shape, fan_in, gain=1.0):
        return _matrix(next(keys), shape=shape, fan_in=fan_in, gain=gain)

    def mlp(lead, width):
        return {"w_gate_up": mat(lead + (d, 2 * width), d),
                "w_down": mat(lead + (width, d), width)}

    ones = lambda n: jnp.ones((n,), jnp.bfloat16)
    out = {
        "attn_norm": ones(d), "mlp_norm": ones(d), "q_norm": ones(qr), "kv_norm": ones(kvr),
        "wq_a": mat((d, qr), d), "wq_b": mat((qr, h, nope + rope), qr, QK_GAIN),
        "wkv_a": jnp.concatenate([mat((d, kvr), d), mat((d, rope), d, QK_GAIN)], axis=1),
        "wkv_b": jnp.concatenate([mat((kvr, h, nope), kvr, QK_GAIN),
                                  mat((kvr, h, vd), kvr, VO_GAIN)], axis=2),
        "wo": mat((h, vd, d), h * vd, VO_GAIN),
    }
    if mlp_types(config)[layer] == "dense":
        out["mlp"] = mlp((), int(config["intermediate_size"]))
    else:
        experts, width = int(config["n_routed_experts"]), int(config["moe_intermediate_size"])
        out["moe"] = {
            "router": mat((d, experts), d, ROUTER_GAIN),
            "bias": _bias(next(keys), experts=experts),
            **mlp((experts,), width),
            "shared": mlp((), int(config["n_shared_experts"]) * width),
        }
    return out


def params(config: dict, seed: int) -> dict:
    """The whole tree, layer by layer."""
    out = embedding_params(config, seed)
    for layer in range(int(config["num_hidden_layers"])):
        out[f"layer_{layer}"] = layer_params(config, seed, layer)
    return out


def sizes(config: dict) -> dict:
    """What ``costs_joyai`` needs, from the configuration file; ``hidden`` and
    ``expert_ffn`` are also all that ``costs_laguna.grouped_matmul_*`` read."""
    width = int(config["moe_intermediate_size"])
    return {
        "hidden": int(config["hidden_size"]), "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]), "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]), "rope": int(config["qk_rope_head_dim"]),
        "v_dim": int(config["v_head_dim"]), "mlp_types": mlp_types(config),
        "dense_ffn": int(config["intermediate_size"]),
        "experts": int(config["n_routed_experts"]), "top_k": int(config["num_experts_per_tok"]),
        "expert_ffn": width, "shared_ffn": int(config["n_shared_experts"]) * width,
        "vocab": int(config["vocab_size"]),
    }
