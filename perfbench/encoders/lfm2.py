"""The LFM2-24B-A2B embedder of ``vs-lfm2-24b-a2b-bf16-marcodoc``: the
program's config object from the configuration file's published keys, and its
weights made on the device from the seed, layer by layer (a sparse layer is
1.2 GB in bfloat16), in one jitted call a matrix.  The program is handed these
arrays; the plain reference (``checks/lfm2.py``) makes each layer again and
takes nothing the program has made.

What this directory's ``README.md`` does not say of this builder yet:
``sizes`` feeds ``costs_lfm2.py`` and, because it carries ``hidden`` and
``expert_ffn``, ``costs_laguna.grouped_matmul_*`` too, so the grouped
product's readers serve this cell unedited; the deployment is ``servers/vector_store_lfm2.py``
(``vector_store_laguna.py``'s with the ``conv.*`` counters beside ``moe.*``);
the reference pads a document behind its text to ``max_seq_length`` for the
mixers and runs the MLPs over blocks of its real tokens (one compiled program
a layer kind and precision either way).

The published layer list (``layer_types``, all 40 entries) is kept whole in
the configuration file and read up to ``num_hidden_layers``, as Laguna's is:
``conv`` is the gated short convolution, ``full_attention`` grouped-query
attention with per-head q and k norms and no output gate.  Layers below
``num_dense_layers`` have a dense MLP, every other one 64 routed experts and
no shared expert.

Scales (``assumed`` in the configuration file), as ``encoders/laguna.py`` and
``encoders/joyai.py`` set them and for their reasons: with last-token pooling
every document ends in the same token, so values and the attention's output
are drawn at 4/sqrt(fan-in) (what attention brings outweighs the token's own
embedding) and what makes a score at 1.4: here that is the scales of the
per-head q and k norms, because a gain on ``W_q`` or ``W_k`` is normed away;
with scales of 1.4 a score has the spread Laguna's has.  The conv layer's
three matrices and taps at 1/sqrt(fan-in) (``B``, ``C`` and ``h`` come out of
a normed input with deviation one, so ``C * conv(B * h)`` adds what the
residual holds).  The router at 1/sqrt(fan-in): its logits then have deviation
1 and a sigmoid's scores spread over (0, 1).  The selection bias is a normal
draw of deviation ``BIAS_SCALE``: at 64 experts the fourth and fifth scores lie
further apart than JoyAI's eighth and ninth of 256 do, so the draw is wider
(``tests/test_causal_conv_embedder.py`` reads the share of tokens whose choice
it changes at the published router's size); zero would leave the mechanism
untested.  It is float32.

The draws are XLA's own bit generator (``encoders/laguna.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import seeded
from encoders.laguna import _matrix, embedding_params, kept  # noqa: F401 - the same draw

#: the forward's jitted programs as a device trace names them (a prefix: the
#: packed forward is ``..._ragged``), and the operations of the grouped matrix
#: product inside them (``jax.lax.ragged_dot``: ``ragged-dot-*``)
PROGRAMS = ("jit_pw_moe_embedder_forward",)
GROUPED_MATMUL_OPS = ("ragged-dot",)

QK_GAIN, VO_GAIN, ROUTER_GAIN, BIAS_SCALE = 1.4, 4.0, 1.0, 0.012
TOKEN_BUCKETS = (1536, 3072, 4608, 6144)
_KINDS = {"conv": "conv", "full_attention": "full"}


def layer_kinds(config: dict) -> list[str]:
    """The kept layers' kinds in the program's names."""
    return [_KINDS[t] for t in kept(config, "layer_types")]


def mlp_types(config: dict) -> list[str]:
    """"dense" for the ``num_dense_layers`` leading layers, then "sparse"."""
    dense = int(config["num_dense_layers"])
    return ["dense" if layer < dense else "sparse"
            for layer in range(int(config["num_hidden_layers"]))]


def head_dim(config: dict) -> int:
    return int(config["hidden_size"]) // int(config["num_attention_heads"])


def model_config(config: dict):
    """``CausalMoeEmbedderConfig`` of the configuration file: conv and full
    layers, q/k norms and no gate on the full ones, the sigmoid router with
    its selection bias and no shared expert."""
    from pathway_tpu.models.causal_moe_embedder import CausalMoeEmbedderConfig, RotarySpec

    rope = config["rope_parameters"]
    if config["conv_bias"] or not config["use_expert_bias"] \
            or not config["norm_topk_prob"] or rope["rope_type"] != "default":
        raise ValueError("a convolution bias, a router or a rotary scaling that the "
                         "program does not build")
    layers, longest = int(config["num_hidden_layers"]), int(config["max_seq_length"])
    return CausalMoeEmbedderConfig(
        vocab_size=config["vocab_size"], hidden_dim=config["hidden_size"],
        head_dim=head_dim(config), num_kv_heads=config["num_key_value_heads"],
        layer_types=tuple(layer_kinds(config)),
        heads_per_layer=(int(config["num_attention_heads"]),) * layers,
        mlp_types=tuple(mlp_types(config)),
        full_rotary=RotarySpec(theta=float(rope["rope_theta"])),
        qk_norm=True, attention_gate=False, conv_taps=int(config["conv_L_cache"]),
        dense_mlp_dim=config["intermediate_size"], num_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"], expert_dim=config["moe_intermediate_size"],
        shared_expert_dim=0, routed_scaling=float(config["routed_scaling_factor"]),
        router_scoring="sigmoid", router_eps=float(config["topk_norm_eps"]),
        rms_eps=float(config["norm_eps"]), max_len=longest,
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
    d, hd = int(config["hidden_size"]), head_dim(config)
    h, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    taps = int(config["conv_L_cache"])
    keys = iter(jax.random.split(jax.random.fold_in(seeded.key_of(seed, 0), layer), 12))

    def mat(shape, fan_in, gain=1.0):
        return _matrix(next(keys), shape=shape, fan_in=fan_in, gain=gain)

    def mlp(lead, width):
        return {"w_gate_up": mat(lead + (d, 2 * width), d),
                "w_down": mat(lead + (width, d), width)}

    ones = lambda n, value=1.0: jnp.full((n,), value, jnp.bfloat16)
    out = {"attn_norm": ones(d), "mlp_norm": ones(d)}
    if layer_kinds(config)[layer] == "conv":
        out.update({"w_in": mat((d, 3 * d), d), "conv": mat((taps, d), taps),
                    "w_out": mat((d, d), d)})
    else:
        out.update({
            "wq": mat((d, h, hd), d), "wk": mat((d, kv, hd), d),
            "wv": mat((d, kv, hd), d, VO_GAIN), "wo": mat((h, hd, d), h * hd, VO_GAIN),
            "q_norm": ones(hd, QK_GAIN), "k_norm": ones(hd, QK_GAIN),
        })
    if mlp_types(config)[layer] == "dense":
        out["mlp"] = mlp((), int(config["intermediate_size"]))
    else:
        experts = int(config["num_experts"])
        out["moe"] = {
            "router": mat((d, experts), d, ROUTER_GAIN),
            "bias": _bias(next(keys), experts=experts),
            **mlp((experts,), int(config["moe_intermediate_size"])),
        }
    return out


def params(config: dict, seed: int) -> dict:
    """The whole tree, layer by layer."""
    out = embedding_params(config, seed)
    for layer in range(int(config["num_hidden_layers"])):
        out[f"layer_{layer}"] = layer_params(config, seed, layer)
    return out


def sizes(config: dict) -> dict:
    """What ``costs_lfm2`` needs, from the configuration file; ``hidden`` and
    ``expert_ffn`` are also all that ``costs_laguna.grouped_matmul_*`` read."""
    return {
        "hidden": int(config["hidden_size"]), "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]), "head_dim": head_dim(config),
        "conv_taps": int(config["conv_L_cache"]), "layer_types": layer_kinds(config),
        "mlp_types": mlp_types(config), "dense_ffn": int(config["intermediate_size"]),
        "experts": int(config["num_experts"]), "top_k": int(config["num_experts_per_tok"]),
        "expert_ffn": int(config["moe_intermediate_size"]), "shared_ffn": 0,
        "vocab": int(config["vocab_size"]),
    }
