"""The Kimi-Linear-48B-A3B embedder of ``vs-kimi-linear-48b-a3b-bf16-marcodoc``:
the program's config object from the configuration file's published keys,
and its weights made on the device from the seed, layer by layer (a routed
layer holds 0.9 B parameters here), in one jitted call a matrix.  The program
is handed these arrays; the plain reference (``checks/kimi_linear.py``) makes
each layer again and takes nothing the program has made.

What this directory's ``README.md`` does not say of this builder: ``sizes``
feeds ``costs_kimi_linear.py`` and, because it carries ``hidden`` and
``expert_ffn``, ``costs_laguna.grouped_matmul_*`` too; the deployment is
``servers/vector_store_kimi_linear.py`` (``vector_store_joyai.py``'s, with the
``mla.*`` counters beside ``moe.*``, and the scan's kernel name among the
facts); the reference pads a document behind its text to ``max_seq_length``
for the mixers and runs the MLPs over blocks of its real tokens, as
``checks/lfm2.py`` does.

The layer kinds come from ``linear_attn_config``, whose lists count layers
from 1: an index in ``full_attn_layers`` is latent attention with no rotary
(``mla_use_nope``), one in ``kda_layers`` Kimi Delta Attention.  Layers below
``first_k_dense_replace`` have a dense MLP, every other one routed experts
with one shared expert.  The router scores all ``published_num_experts``;
this chip holds ``num_experts`` of them from ``first_expert`` on.

Scales (``assumed`` in the configuration file), as ``encoders/joyai.py`` sets
them and for its reasons: with last-token pooling every document ends in the
same token, so values and the attention's output are drawn at 4/sqrt(fan-in)
and what makes an MLA score at 1.4/sqrt(fan-in): ``W_q``, the ``k_nope``
columns of ``W_kvb`` and the one key's ``k_pe`` columns of ``W_kva``.  A KDA
layer's ``q`` and ``k`` are L2-normed and its output head-normed, so
``W_qkv`` is at 1/sqrt(fan-in) and its ``W_o`` at 4.  ``A_log`` and
``dt_bias`` are drawn as fla initialises them (``A = U(1, 16)`` a head, ``dt
= exp(U(log 0.001, log 0.1))`` a channel, ``dt_bias`` its inverse softplus),
float32.  The router at 1/sqrt(fan-in), its selection bias a float32 normal
draw of deviation ``BIAS_SCALE`` (JoyAI's: top 8 of 256 alike).

The draws are XLA's own bit generator (``encoders/laguna.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import seeded
from encoders.laguna import _matrix, embedding_params  # noqa: F401 - the same draw

#: the forward's jitted programs as a device trace names them (a prefix: the
#: packed forward is ``..._ragged``), the operations of the grouped matrix
#: product inside them (``jax.lax.ragged_dot``: ``ragged-dot-*``) and the
#: delta rule's scan (``ops/kda_scan.py``)
PROGRAMS = ("jit_pw_moe_embedder_forward",)
GROUPED_MATMUL_OPS = ("ragged-dot",)
KDA_SCAN_OPS = ("pw_kda_scan",)

QK_GAIN, VO_GAIN, ROUTER_GAIN, BIAS_SCALE = 1.4, 4.0, 1.0, 0.004
TOKEN_BUCKETS = (2048, 3072, 4608, 6144)


def layer_kinds(config: dict) -> list[str]:
    """The kept layers' kinds in the program's names: ``linear_attn_config``
    counts layers from 1."""
    lac = config["linear_attn_config"]
    full, kda = set(lac["full_attn_layers"]), set(lac["kda_layers"])
    kinds = []
    for layer in range(1, int(config["num_hidden_layers"]) + 1):
        if (layer in full) == (layer in kda):
            raise ValueError(f"layer {layer} is in neither or both of linear_attn_config's lists")
        kinds.append("latent" if layer in full else "kda")
    return kinds


def mlp_types(config: dict) -> list[str]:
    """"dense" for the ``first_k_dense_replace`` leading layers, then "sparse"
    (``moe_layer_freq`` 1: every further layer)."""
    if int(config["moe_layer_freq"]) != 1:
        raise ValueError("moe_layer_freq other than 1 is not built")
    dense = int(config["first_k_dense_replace"])
    return ["dense" if layer < dense else "sparse"
            for layer in range(int(config["num_hidden_layers"]))]


def model_config(config: dict):
    """``CausalMoeEmbedderConfig`` of the configuration file: KDA and latent
    layers (no rotary, a direct query), the sigmoid router with its selection
    bias over all experts, a share of them held."""
    from pathway_tpu.models.causal_moe_embedder import CausalMoeEmbedderConfig

    lac = config["linear_attn_config"]
    if (config["moe_router_activation_func"] != "sigmoid" or not config["moe_renormalize"]
            or (config["num_expert_group"], config["topk_group"]) != (1, 1)
            or config["rope_scaling"] is not None or not config["mla_use_nope"]
            or config["q_lora_rank"] is not None or int(config["num_shared_experts"]) != 1):
        raise ValueError("a router, group limit, rotary, query path or shared-expert count "
                         "that the program does not build")
    layers, longest = int(config["num_hidden_layers"]), int(config["max_seq_length"])
    width = int(config["moe_intermediate_size"])
    return CausalMoeEmbedderConfig(
        vocab_size=config["vocab_size"], hidden_dim=config["hidden_size"],
        layer_types=tuple(layer_kinds(config)),
        heads_per_layer=(int(config["num_attention_heads"]),) * layers,
        mlp_types=tuple(mlp_types(config)),
        latent_q_rank=0, latent_kv_rank=config["kv_lora_rank"],
        latent_nope_dim=config["qk_nope_head_dim"], latent_rope_dim=config["qk_rope_head_dim"],
        latent_v_dim=config["v_head_dim"], latent_rotary=None,
        conv_taps=int(lac["short_conv_kernel_size"]), kda_heads=int(lac["num_heads"]),
        kda_head_dim=int(lac["head_dim"]),
        dense_mlp_dim=config["intermediate_size"],
        num_experts=int(config["published_num_experts"]),
        held_experts=int(config["num_experts"]), first_expert=int(config["first_expert"]),
        top_k=config["num_experts_per_token"], expert_dim=width,
        shared_expert_dim=int(config["num_shared_experts"]) * width,
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


def _fast(key):
    return jax.random.wrap_key_data(jnp.concatenate([key, key ^ 0x5EED]), impl="rbg")


@functools.partial(jax.jit, static_argnames=("experts",))
def _bias(key, *, experts):
    return jax.random.normal(_fast(key), (experts,), jnp.float32) * BIAS_SCALE


@functools.partial(jax.jit, static_argnames=("heads", "channels"))
def _decay_init(key, *, heads, channels):
    """``A_log`` [heads] and ``dt_bias`` [channels] as fla draws them."""
    ka, kd = jax.random.split(key)
    a_log = jnp.log(jax.random.uniform(_fast(ka), (heads,), jnp.float32, 1.0, 16.0))
    lo, hi = jnp.log(0.001), jnp.log(0.1)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(_fast(kd), (channels,), jnp.float32) * (hi - lo)
                             + lo), 1e-4)
    return a_log, dt + jnp.log(-jnp.expm1(-dt))


def layer_params(config: dict, seed: int, layer: int) -> dict:
    """Layer ``layer`` in the program's layout, bfloat16 (the router's bias,
    ``A_log`` and ``dt_bias`` float32)."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    kvr, vd = int(config["kv_lora_rank"]), int(config["v_head_dim"])
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    lac = config["linear_attn_config"]
    kh, dk, taps = int(lac["num_heads"]), int(lac["head_dim"]), int(lac["short_conv_kernel_size"])
    keys = iter(jax.random.split(jax.random.fold_in(seeded.key_of(seed, 0), layer), 20))

    def mat(shape, fan_in, gain=1.0):
        return _matrix(next(keys), shape=shape, fan_in=fan_in, gain=gain)

    def mlp(lead, width):
        return {"w_gate_up": mat(lead + (d, 2 * width), d),
                "w_down": mat(lead + (width, d), width)}

    ones = lambda n: jnp.ones((n,), jnp.bfloat16)
    out = {"attn_norm": ones(d), "mlp_norm": ones(d)}
    if layer_kinds(config)[layer] == "kda":
        a_log, dt_bias = _decay_init(next(keys), heads=kh, channels=kh * dk)
        out.update({
            "w_qkv": mat((d, 3 * kh * dk), d), "conv": mat((taps, 3 * kh * dk), taps),
            "w_fa": mat((d, dk), d), "w_fb": mat((dk, kh * dk), dk),
            "A_log": a_log, "dt_bias": dt_bias, "w_b": mat((d, kh), d),
            "w_ga": mat((d, dk), d), "w_gb": mat((dk, kh * dk), dk),
            "o_norm": ones(dk), "w_o": mat((kh * dk, d), kh * dk, VO_GAIN),
        })
    else:
        out.update({
            "kv_norm": ones(kvr), "wq": mat((d, h, nope + rope), d, QK_GAIN),
            "wkv_a": jnp.concatenate([mat((d, kvr), d), mat((d, rope), d, QK_GAIN)], axis=1),
            "wkv_b": jnp.concatenate([mat((kvr, h, nope), kvr, QK_GAIN),
                                      mat((kvr, h, vd), kvr, VO_GAIN)], axis=2),
            "wo": mat((h, vd, d), h * vd, VO_GAIN),
        })
    if mlp_types(config)[layer] == "dense":
        out["mlp"] = mlp((), int(config["intermediate_size"]))
    else:
        routed, width = int(config["published_num_experts"]), int(config["moe_intermediate_size"])
        out["moe"] = {
            "router": mat((d, routed), d, ROUTER_GAIN),
            "bias": _bias(next(keys), experts=routed),
            **mlp((int(config["num_experts"]),), width),
            "shared": mlp((), int(config["num_shared_experts"]) * width),
        }
    return out


def params(config: dict, seed: int) -> dict:
    """The whole tree, layer by layer."""
    out = embedding_params(config, seed)
    for layer in range(int(config["num_hidden_layers"])):
        out[f"layer_{layer}"] = layer_params(config, seed, layer)
    return out


def sizes(config: dict) -> dict:
    """What ``costs_kimi_linear`` needs, from the configuration file;
    ``hidden`` and ``expert_ffn`` are also all that
    ``costs_laguna.grouped_matmul_*`` read.  ``experts`` is the held count,
    the experts the ``pathway_moe_*`` counters run over (what
    ``moe.load_max_over_mean`` divides by); ``routed_experts`` is every
    expert the router scores."""
    from pathway_tpu.ops.kda_scan import CHUNK

    lac = config["linear_attn_config"]
    width = int(config["moe_intermediate_size"])
    return {
        "hidden": int(config["hidden_size"]), "heads": int(config["num_attention_heads"]),
        "kv_rank": int(config["kv_lora_rank"]), "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]), "v_dim": int(config["v_head_dim"]),
        "kda_heads": int(lac["num_heads"]), "kda_head_dim": int(lac["head_dim"]),
        "conv_taps": int(lac["short_conv_kernel_size"]), "chunk": CHUNK,
        "layer_types": layer_kinds(config), "mlp_types": mlp_types(config),
        "dense_ffn": int(config["intermediate_size"]),
        "experts": int(config["num_experts"]),
        "routed_experts": int(config["published_num_experts"]),
        "top_k": int(config["num_experts_per_token"]), "expert_ffn": width,
        "shared_ffn": int(config["num_shared_experts"]) * width,
        "vocab": int(config["vocab_size"]),
    }
