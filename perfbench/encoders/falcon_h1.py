"""The Falcon-H1 embedder of ``vs-falcon-h1-34b-bf16-marcodoc``: the program's
config object from the configuration file's published keys, and its weights
made on the device from the seed, layer by layer (a layer is 0.86 GB in
bfloat16), in one jitted call a matrix.  The program is handed these arrays;
the plain reference (``checks/falcon_h1.py``) makes each layer again and takes
nothing the program has made.

Scales (``assumed`` in the configuration file).  The published multipliers
belong to trained weights: 0.011 on the keys, 0.0375 and 0.088 on the two
branches' outputs, 0.011 on the MLP's, 5.66 on the embedding.  Over matrices
drawn at 1/sqrt(fan-in) they would leave the residual stream the last token's
own embedding, which is the same token in every document.  So every matrix
is drawn at ``gain / (the multipliers applied to its product) /
sqrt(fan-in)``: the multipliers are applied where the model applies them and
the forward sees effective gains, which are the Laguna configuration's and
for its reasons (PERF.md 6, PR 29): keys and queries 1.4 (a token attends to
a few others and the forward stays well conditioned), values and both
branches' output projections 4 (what the mixers bring outweighs the token's
own embedding, so documents lie apart), everything else 1; token embeddings
at 1 / ``embedding_multiplier`` (a unit residual stream), norms and ``D`` at
one, the convolution's taps at 1/sqrt(4) and its bias at 0.1, the time
steps' bias and ``A_log`` as Mamba-2 draws them (steps log-uniform in 0.001
to 0.1, ``A`` uniform in -16 to -1).

The draws are XLA's own bit generator (``rbg`` keys), as
``encoders/laguna.py``'s.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

import seeded

#: the forward's jitted programs as a device trace names them (a prefix: the
#: packed forward is ``..._ragged``), and the chunked scan's kernel inside
#: them (``pathway_tpu/ops/ssd_scan.py`` ``KERNEL_NAME``).  A builder names
#: what its model has: ``SSM_SCAN_OPS`` here, ``GROUPED_MATMUL_OPS`` in
#: ``laguna.py``; either may be absent (``README.md`` lists the rest of the
#: interface), and ``sizes`` is what that model's cost module takes
PROGRAMS = ("jit_pw_hybrid_embedder_forward",)
SSM_SCAN_OPS = ("pw_ssd_scan",)

QK_GAIN, VO_GAIN, SSM_OUT_GAIN = 1.4, 4.0, 4.0


def model_config(config: dict):
    """``CausalHybridEmbedderConfig`` of the configuration file."""
    from pathway_tpu.models.causal_hybrid_embedder import CausalHybridEmbedderConfig
    from pathway_tpu.models.causal_moe_embedder import RotarySpec

    if (config["mamba_d_ssm"] != config["mamba_n_heads"] * config["mamba_d_head"]
            or config["mamba_norm_before_gate"] or not config["mamba_rms_norm"]
            or not config["mamba_conv_bias"] or config["rope_scaling"] is not None):
        raise ValueError("a mixer this embedder does not compute")
    longest = int(config["max_seq_length"])
    extra = {"token_buckets": tuple(config["token_buckets"])} if "token_buckets" in config else {}
    return CausalHybridEmbedderConfig(
        vocab_size=config["vocab_size"], hidden_dim=config["hidden_size"],
        num_layers=config["num_hidden_layers"], num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        rotary=RotarySpec(theta=float(config["rope_theta"])),
        mlp_dim=config["intermediate_size"], ssm_heads=config["mamba_n_heads"],
        ssm_head_dim=config["mamba_d_head"], ssm_state=config["mamba_d_state"],
        ssm_groups=config["mamba_n_groups"], conv_taps=config["mamba_d_conv"],
        chunk=config["mamba_chunk_size"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        attention_in_multiplier=float(config["attention_in_multiplier"]),
        attention_out_multiplier=float(config["attention_out_multiplier"]),
        key_multiplier=float(config["key_multiplier"]),
        ssm_in_multiplier=float(config["ssm_in_multiplier"]),
        ssm_multipliers=tuple(float(m) for m in config["ssm_multipliers"]),
        ssm_out_multiplier=float(config["ssm_out_multiplier"]),
        mlp_multipliers=tuple(float(m) for m in config["mlp_multipliers"]),
        rms_eps=float(config["rms_norm_eps"]), max_len=longest,
        seq_buckets=tuple(b for b in (32, 64, 128, 256, 512, 1024)
                          if b < longest) + (longest,),
        q_block=int(config.get("attention_q_block", 512)), **extra,
    )


@functools.lru_cache(maxsize=None)
def _layer_program(config_json: str, layer: int):
    import json

    from pathway_tpu.models.causal_hybrid_embedder import CausalHybridEmbedder

    cfg = model_config(json.loads(config_json))
    model = CausalHybridEmbedder(cfg)
    before, behind = cfg.chunk + cfg.chunk // 2, cfg.chunk // 3 + 1

    def packed(p, x):
        t = x.shape[0]
        neighbour = jnp.resize(x[::-1], (before, x.shape[1]))
        axis = jnp.concatenate([neighbour, x, jnp.zeros((behind, x.shape[1]), x.dtype)])
        seg = jnp.concatenate([jnp.zeros(before, jnp.int32), jnp.ones(t, jnp.int32),
                               jnp.full(behind, -1, jnp.int32)])
        pos = jnp.concatenate([jnp.arange(before), jnp.arange(t), jnp.zeros(behind, jnp.int32)])
        return model.layer(p, layer, axis, seg, pos)[before: before + t]

    return jax.jit(packed)


def program_layer(config: dict, layer: int, layer_params: dict, x):
    """The PROGRAM's block ``layer`` over one text's states ``x`` [T, D]:
    ``checks/ingest_laguna.py`` feeds it the reference's own input.  The text
    is computed AS A FLUSH PACKS IT: on one token axis behind a neighbour of
    one and a half chunks (the text's own states in reverse) and before a
    third of a chunk of padding, so its first token falls inside a chunk of
    the scan and inside a query block, and the state, the convolution and
    attention have a document to keep it apart from.  The reference computes
    the text alone: ``layer_gap`` holds the packed layout to it."""
    import json

    return _layer_program(json.dumps(config, sort_keys=True), layer)(layer_params, x)


def _fast(key):
    return jax.random.wrap_key_data(jnp.concatenate([key, key ^ 0x5EED]), impl="rbg")


@functools.partial(jax.jit, static_argnames=("shape", "scale"))
def _normal(key, *, shape, scale):
    return (jax.random.normal(_fast(key), shape, jnp.float32) * scale).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("shape", "lo", "hi"))
def _uniform(key, *, shape, lo, hi):
    return jax.random.uniform(_fast(key), shape, jnp.float32, lo, hi)


def embedding_params(config: dict, seed: int) -> dict:
    """Token embeddings (a unit residual stream once multiplied) and the
    final norm."""
    d = int(config["hidden_size"])
    key = jax.random.fold_in(seeded.key_of(seed, 0), 1_000_000)
    return {"tok_emb": _normal(key, shape=(int(config["vocab_size"]), d),
                               scale=1.0 / float(config["embedding_multiplier"])),
            "final_norm": jnp.ones((d,), jnp.bfloat16)}


def layer_params(config: dict, seed: int, layer: int) -> dict:
    """Layer ``layer`` in the program's layout, bfloat16."""
    d, hd = int(config["hidden_size"]), int(config["head_dim"])
    h, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    heads, d_ssm = int(config["mamba_n_heads"]), int(config["mamba_d_ssm"])
    bc = int(config["mamba_n_groups"]) * int(config["mamba_d_state"])
    taps, f = int(config["mamba_d_conv"]), int(config["intermediate_size"])
    attn_in, ssm_in = float(config["attention_in_multiplier"]), float(config["ssm_in_multiplier"])
    keys = iter(jax.random.split(jax.random.fold_in(seeded.key_of(seed, 0), layer), 24))

    def mat(shape, fan_in, gain=1.0, applied=1.0):
        """``gain / applied / sqrt(fan_in)``: ``applied`` is the product of
        the multipliers the model applies to this matrix's product."""
        return _normal(next(keys), shape=shape, scale=gain / applied * fan_in ** -0.5)

    ones = lambda n: jnp.ones((n,), jnp.bfloat16)
    step = jnp.exp(_uniform(next(keys), shape=(heads,), lo=math.log(0.001),
                            hi=math.log(0.1)))
    gate_mult, down_mult = (float(m) for m in config["mlp_multipliers"])
    return {
        "attn_norm": ones(d), "mlp_norm": ones(d),
        "wq": mat((d, h, hd), d, QK_GAIN, attn_in),
        "wk": mat((d, kv, hd), d, QK_GAIN, attn_in * float(config["key_multiplier"])),
        "wv": mat((d, kv, hd), d, VO_GAIN, attn_in),
        "wo": mat((h, hd, d), h * hd, VO_GAIN, float(config["attention_out_multiplier"])),
        "w_in": jnp.concatenate(
            [mat((d, width), d, 1.0, ssm_in * float(m)) for width, m in
             zip((d_ssm, d_ssm, bc, bc, heads), config["ssm_multipliers"])], axis=1),
        "conv_w": mat((taps, d_ssm + 2 * bc), taps),
        "conv_b": mat((d_ssm + 2 * bc,), 100),
        "w_out": mat((d_ssm, d), d_ssm, SSM_OUT_GAIN, float(config["ssm_out_multiplier"])),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(jnp.bfloat16),  # softplus^-1
        "a_log": jnp.log(_uniform(next(keys), shape=(heads,), lo=1.0, hi=16.0)
                         ).astype(jnp.bfloat16),
        "d": ones(heads), "norm": ones(d_ssm),
        "w_gate_up": jnp.concatenate([mat((d, f), d, 1.0, gate_mult), mat((d, f), d)], axis=1),
        "w_down": mat((f, d), f, 1.0, down_mult),
    }


def params(config: dict, seed: int) -> dict:
    """The whole tree, layer by layer."""
    out = embedding_params(config, seed)
    for layer in range(int(config["num_hidden_layers"])):
        out[f"layer_{layer}"] = layer_params(config, seed, layer)
    return out


def sizes(config: dict) -> dict:
    """What ``costs_falcon_h1`` needs, from the configuration file."""
    return {
        "hidden": int(config["hidden_size"]), "layers": int(config["num_hidden_layers"]),
        "heads": int(config["num_attention_heads"]), "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]), "ffn": int(config["intermediate_size"]),
        "ssm_heads": int(config["mamba_n_heads"]), "ssm_head_dim": int(config["mamba_d_head"]),
        "ssm_state": int(config["mamba_d_state"]), "ssm_groups": int(config["mamba_n_groups"]),
        "conv_taps": int(config["mamba_d_conv"]), "chunk": int(config["mamba_chunk_size"]),
        "vocab": int(config["vocab_size"]),
    }
