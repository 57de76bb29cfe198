"""Operations and parameters of the latent-attention embedder's forward, from
shapes alone (``sizes`` is ``encoders/joyai.py`` ``sizes(config)``), whatever
implements it.  Attention is counted in its prefill form: a (query, key, head)
triple costs ``nope + rope`` multiply-adds for the score and ``v_dim`` for the
weighted sum (192 + 128; the absorbed form's 576 + 512 is another algorithm's
count, not this one's).  Embedding look-ups, norms, rotary, the router's
sigmoids and its choice are left out (thousands of operations a token beside
hundreds of millions).  The grouped product's operations and bytes are
``costs_laguna.grouped_matmul_*``, which read ``hidden`` and ``expert_ffn``.
"""

from __future__ import annotations


def attention_params(sizes: dict) -> int:
    """One layer's five matrices: q down and up, kv down (the latent and the
    one rotary key) and up (``k_nope`` and ``v`` a head), out."""
    d, h, qk = sizes["hidden"], sizes["heads"], sizes["nope"] + sizes["rope"]
    return (d * sizes["q_rank"] + sizes["q_rank"] * h * qk
            + d * (sizes["kv_rank"] + sizes["rope"])
            + sizes["kv_rank"] * h * (sizes["nope"] + sizes["v_dim"])
            + h * sizes["v_dim"] * d)


def expert_params(sizes: dict) -> int:
    """One routed expert: gate, up and down projections."""
    return 3 * sizes["hidden"] * sizes["expert_ffn"]


def layer_params(sizes: dict, mlp: str) -> int:
    """Everything one layer holds: attention with its two inner norms, the
    layer's two norms, and the dense MLP or the router with its bias, the
    experts and the shared expert."""
    d = sizes["hidden"]
    held = attention_params(sizes) + sizes["q_rank"] + sizes["kv_rank"] + 2 * d
    if mlp == "dense":
        return held + 3 * d * sizes["dense_ffn"]
    return (held + sizes["experts"] * (d + 1 + expert_params(sizes))
            + 3 * d * sizes["shared_ffn"])


def params(sizes: dict) -> dict:
    """Parameters held: ``embedding``, ``experts`` (all routed experts),
    ``total`` (with the final norm)."""
    d = sizes["hidden"]
    sparse = sum(1 for m in sizes["mlp_types"] if m == "sparse")
    embedding = sizes["vocab"] * d
    return {"embedding": embedding,
            "experts": sparse * sizes["experts"] * expert_params(sizes),
            "total": embedding + d + sum(layer_params(sizes, m) for m in sizes["mlp_types"])}


def active_params(sizes: dict) -> int:
    """Parameters of the matrices one token is multiplied by: attention, the
    dense MLP or the router, ``top_k`` routed experts and the shared one."""
    d = sizes["hidden"]
    active = 0
    for mlp in sizes["mlp_types"]:
        active += attention_params(sizes)
        if mlp == "dense":
            active += 3 * d * sizes["dense_ffn"]
        else:
            active += (d * sizes["experts"] + sizes["top_k"] * expert_params(sizes)
                       + 3 * d * sizes["shared_ffn"])
    return active


def attention_pairs(tokens: int) -> int:
    """(query, key) pairs the causal mask lets through in one document."""
    return tokens * (tokens + 1) // 2


def attention_flops(tokens: int, sizes: dict) -> int:
    """Scores and weighted sums of one document, every layer: ``nope + rope``
    and ``v_dim`` multiply-adds a pair and head."""
    a_pair = sizes["heads"] * (sizes["nope"] + sizes["rope"] + sizes["v_dim"])
    return len(sizes["mlp_types"]) * 2 * attention_pairs(tokens) * a_pair


def forward_flops(tokens: int, sizes: dict) -> int:
    """One document of ``tokens`` real tokens through every kept layer."""
    return 2 * tokens * active_params(sizes) + attention_flops(tokens, sizes)
