"""Operations and parameters of the LFM2 embedder's forward (gated short
convolutions and grouped-query attention, routed experts), from shapes alone
(``sizes`` is ``encoders/lfm2.py`` ``sizes(config)``), whatever implements it.
A conv layer counts ``W_in`` and ``W_out`` and a multiply-add a tap and
channel; an attention layer its four matrices and, on its causal pairs, two
products of ``head_dim`` multiply-adds a pair and query head.  Embedding
look-ups, norms, rotary, the gates' products, the router's sigmoids and its
choice are left out (thousands of operations a token beside hundreds of
millions).  The grouped product's operations and bytes are
``costs_laguna.grouped_matmul_*``, which read ``hidden`` and ``expert_ffn``.
"""

from __future__ import annotations


def conv_params(sizes: dict) -> int:
    """One conv layer's mixer: ``W_in`` (to ``B``, ``C`` and ``h``), the
    taps, ``W_out``."""
    d = sizes["hidden"]
    return d * 3 * d + sizes["conv_taps"] * d + d * d


def attention_params(sizes: dict) -> int:
    """One attention layer: W_q, W_k, W_v, W_o and the q and k norms."""
    d, hd = sizes["hidden"], sizes["head_dim"]
    return 2 * d * sizes["heads"] * hd + 2 * d * sizes["kv_heads"] * hd + 2 * hd


def expert_params(sizes: dict) -> int:
    """One routed expert: gate, up and down projections."""
    return 3 * sizes["hidden"] * sizes["expert_ffn"]


def layer_params(sizes: dict, kind: str, mlp: str) -> int:
    """Everything one layer holds: its mixer, its two norms, and the dense MLP
    or the router with its bias and the experts."""
    d = sizes["hidden"]
    held = (conv_params(sizes) if kind == "conv" else attention_params(sizes)) + 2 * d
    if mlp == "dense":
        return held + 3 * d * sizes["dense_ffn"]
    return held + sizes["experts"] * (d + 1 + expert_params(sizes))


def params(sizes: dict) -> dict:
    """Parameters held: ``embedding``, ``experts`` (all routed experts),
    ``total`` (with the final norm)."""
    d = sizes["hidden"]
    layers = list(zip(sizes["layer_types"], sizes["mlp_types"]))
    sparse = sum(1 for _kind, mlp in layers if mlp == "sparse")
    embedding = sizes["vocab"] * d
    return {"embedding": embedding,
            "experts": sparse * sizes["experts"] * expert_params(sizes),
            "total": embedding + d + sum(layer_params(sizes, k, m) for k, m in layers)}


def active_params(sizes: dict) -> int:
    """Parameters of the matrices one token is multiplied by (the taps
    counted as one multiply-add a channel each): the mixer, the dense MLP or
    the router and ``top_k`` routed experts."""
    d = sizes["hidden"]
    active = 0
    for kind, mlp in zip(sizes["layer_types"], sizes["mlp_types"]):
        if kind == "conv":
            active += conv_params(sizes)
        else:  # the q and k norms multiply nothing
            active += attention_params(sizes) - 2 * sizes["head_dim"]
        if mlp == "dense":
            active += 3 * d * sizes["dense_ffn"]
        else:
            active += d * sizes["experts"] + sizes["top_k"] * expert_params(sizes)
    return active


def attention_pairs(tokens: int) -> int:
    """(query, key) pairs the causal mask lets through in one document."""
    return tokens * (tokens + 1) // 2


def attention_flops(tokens: int, sizes: dict) -> int:
    """Scores and weighted sums of one document over the full layers: two
    products of ``head_dim`` multiply-adds a pair and query head."""
    full = sum(1 for kind in sizes["layer_types"] if kind == "full")
    return full * 2 * 2 * attention_pairs(tokens) * sizes["heads"] * sizes["head_dim"]


def forward_flops(tokens: int, sizes: dict) -> int:
    """One document of ``tokens`` real tokens through every kept layer."""
    return 2 * tokens * active_params(sizes) + attention_flops(tokens, sizes)
