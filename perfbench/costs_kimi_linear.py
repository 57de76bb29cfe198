"""Operations, parameters and least bytes of the Kimi-Linear embedder's
forward (Kimi Delta Attention and latent attention with no rotary, a held
share of the routed experts), from shapes alone (``sizes`` is
``encoders/kimi_linear.py`` ``sizes(config)``), whatever implements it.

A KDA layer counts its matrices (``W_qkv``, the two low-rank paths, ``W_b``,
``W_o``), a multiply-add a tap and channel of its convolutions, and the
delta rule's scan (:func:`scan_flops`); an MLA layer its four matrices and,
on its causal pairs, ``nope + rope`` multiply-adds for a score and ``v_dim``
for the weighted sum, a head.  A routed layer on this chip multiplies a token
by the router, the shared expert and, on average, ``top_k * experts /
routed_experts`` routed experts (the pairs whose expert this chip holds:
``experts`` is the held count, ``routed_experts`` all the router scores).  Embedding
look-ups, norms, the gates, the decay's exponentials and the router's choice
are left out (thousands of operations a token beside hundreds of millions).
The grouped product's operations and bytes are ``costs_laguna.grouped_matmul_*``,
which read ``hidden`` and ``expert_ffn``.
"""

from __future__ import annotations

SCAN_BYTES = 4  # the scan reads and writes float32


def kda_params(sizes: dict) -> int:
    """One KDA mixer: ``W_qkv``, ``W_o``, the decay's and the gate's
    low-rank paths, ``W_b``, the taps, ``A_log``, ``dt_bias``, the head norm."""
    d, h, dk = sizes["hidden"], sizes["kda_heads"], sizes["kda_head_dim"]
    p = h * dk
    return (3 * d * p + p * d + 2 * (d * dk + dk * p) + d * h
            + sizes["conv_taps"] * 3 * p + h + p + dk)


def kda_matrix_params(sizes: dict) -> int:
    """What one token is multiplied by in a KDA mixer (the taps a
    multiply-add a channel each), the scan aside."""
    d, h, dk = sizes["hidden"], sizes["kda_heads"], sizes["kda_head_dim"]
    p = h * dk
    return 3 * d * p + p * d + 2 * (d * dk + dk * p) + d * h + sizes["conv_taps"] * 3 * p


def mla_params(sizes: dict) -> int:
    """One MLA mixer: ``W_q`` (direct), ``W_kva`` (the latent and the one
    key), ``W_kvb`` (``k_nope`` and ``v`` a head), ``W_o``; the latent norm."""
    d, h, qk = sizes["hidden"], sizes["heads"], sizes["nope"] + sizes["rope"]
    return (d * h * qk + d * (sizes["kv_rank"] + sizes["rope"])
            + sizes["kv_rank"] * h * (sizes["nope"] + sizes["v_dim"]) + h * sizes["v_dim"] * d)


def expert_params(sizes: dict) -> int:
    """One routed expert: gate, up and down projections."""
    return 3 * sizes["hidden"] * sizes["expert_ffn"]


def layer_params(sizes: dict, kind: str, mlp: str) -> int:
    """Everything one layer holds on this chip: its mixer, its two norms, and
    the dense MLP or the router over all experts with its bias, the held
    experts and the shared expert."""
    d = sizes["hidden"]
    held = (kda_params(sizes) if kind == "kda" else mla_params(sizes) + sizes["kv_rank"]) + 2 * d
    if mlp == "dense":
        return held + 3 * d * sizes["dense_ffn"]
    return (held + sizes["routed_experts"] * (d + 1) + sizes["experts"] * expert_params(sizes)
            + 3 * d * sizes["shared_ffn"])


def params(sizes: dict) -> dict:
    """Parameters held on this chip: ``embedding``, ``experts`` (the held
    routed experts), ``total`` (with the final norm)."""
    d = sizes["hidden"]
    layers = list(zip(sizes["layer_types"], sizes["mlp_types"]))
    sparse = sum(1 for _kind, mlp in layers if mlp == "sparse")
    embedding = sizes["vocab"] * d
    return {"embedding": embedding,
            "experts": sparse * sizes["experts"] * expert_params(sizes),
            "total": embedding + d + sum(layer_params(sizes, k, m) for k, m in layers)}


def active_params(sizes: dict) -> float:
    """Parameters one token is multiplied by on this chip, on average: the
    mixers, the dense MLP or the router, the shared expert and the routed
    experts held here of its ``top_k``."""
    d = sizes["hidden"]
    routed = sizes["top_k"] * sizes["experts"] / sizes["routed_experts"]
    active = 0.0
    for kind, mlp in zip(sizes["layer_types"], sizes["mlp_types"]):
        active += kda_matrix_params(sizes) if kind == "kda" else mla_params(sizes)
        if mlp == "dense":
            active += 3 * d * sizes["dense_ffn"]
        else:
            active += (d * sizes["routed_experts"] + routed * expert_params(sizes)
                       + 3 * d * sizes["shared_ffn"])
    return active


def attention_pairs(tokens: int) -> int:
    """(query, key) pairs the causal mask lets through in one document."""
    return tokens * (tokens + 1) // 2


def attention_flops(tokens: int, sizes: dict) -> int:
    """Scores and weighted sums of one document over the MLA layers."""
    latent = sum(1 for kind in sizes["layer_types"] if kind == "latent")
    a_pair = sizes["heads"] * (sizes["nope"] + sizes["rope"] + sizes["v_dim"])
    return latent * 2 * attention_pairs(tokens) * a_pair


def scan_flops(slots: float, sizes: dict) -> float:
    """The chunked delta rule of ONE layer over ``slots`` token slots at the
    configuration's chunk ``C`` (sub-blocks of 16), a head: the pairwise
    decay's two sums inside a sub-block (``2 C dk`` a key offset, 16 of
    them), the products through each later sub-block's first position (``2 x
    16 x dk x C``, both for ``q`` and ``k``), the inverse of ``I + A`` (ten
    [C, C] products), ``T (beta V)`` and ``T (beta K)``, the state's three
    products (``W_k S``, ``Q S``, the state handed on) and ``P U``.  What the
    kernel computes at ``Precision.HIGHEST`` (several passes on the MXU) is
    counted once."""
    c, dk, sub = sizes["chunk"], sizes["kda_head_dim"], 16
    per_chunk = (2 * 2 * c * dk * sub + 2 * 2 * sub * dk * c * (c // sub - 1)
                 + 10 * 2 * c ** 3 + 2 * 2 * c * c * dk + 3 * 2 * c * dk * dk
                 + 2 * c * c * dk)
    return slots / c * sizes["kda_heads"] * per_chunk


def scan_least_bytes(slots: float, sizes: dict) -> float:
    """Least HBM traffic of ONE layer's scan: ``q``, ``k``, ``v``, the decay's
    running sum and ``beta`` read once and ``o`` written once, float32; the
    states stay on the chip."""
    h, dk = sizes["kda_heads"], sizes["kda_head_dim"]
    return SCAN_BYTES * slots * (5 * h * dk + h)


def forward_flops(tokens: int, sizes: dict) -> float:
    """One document of ``tokens`` real tokens through every kept layer on
    this chip: every matrix, MLA's causal pairs, the KDA layers' scans."""
    kda = sum(1 for kind in sizes["layer_types"] if kind == "kda")
    return (2 * tokens * active_params(sizes) + attention_flops(tokens, sizes)
            + kda * scan_flops(tokens, sizes))


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of the two times."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
