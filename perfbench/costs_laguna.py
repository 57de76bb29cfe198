"""Operations and least bytes of the language-model embedder's forward, from
shapes alone (``sizes`` is ``encoders/laguna.py`` ``sizes(config)``), whatever
implements it: the whole forward, the grouped matrix product over the routed
experts, attention.  Embedding look-ups, norms, rotary, the router's softmax
and the gates' sigmoids are left out (thousands of operations a token beside
hundreds of millions).
"""

from __future__ import annotations

BYTES = 2  # the configuration states bfloat16 weights and bfloat16 operands


def _layers(sizes: dict):
    return zip(sizes["layer_types"], sizes["heads"], sizes["mlp_types"])


def attention_params(sizes: dict, heads: int) -> int:
    """Wq, Wk, Wv, Wo and the per-head gate of one layer."""
    d, hd, kv = sizes["hidden"], sizes["head_dim"], sizes["kv_heads"]
    return d * heads * hd * 2 + 2 * d * kv * hd + d * heads


def expert_params(sizes: dict) -> int:
    """One routed expert: gate, up and down projections."""
    return 3 * sizes["hidden"] * sizes["expert_ffn"]


def params(sizes: dict) -> dict:
    """Parameters held: ``embedding``, ``dense`` (every matrix each token is
    multiplied by: attention, the dense MLP, routers, shared experts),
    ``experts`` (all routed experts), ``total``."""
    d = sizes["hidden"]
    dense = experts = 0
    for _kind, heads, mlp in _layers(sizes):
        dense += attention_params(sizes, heads)
        if mlp == "dense":
            dense += 3 * d * sizes["dense_ffn"]
        else:
            dense += d * sizes["experts"] + 3 * d * sizes["shared_ffn"]
            experts += sizes["experts"] * expert_params(sizes)
    embedding = sizes["vocab"] * d
    return {"embedding": embedding, "dense": dense, "experts": experts,
            "total": embedding + dense + experts + d * (2 * len(sizes["heads"]) + 1)}


def active_params(sizes: dict) -> int:
    """Parameters one token is multiplied by: ``dense`` and ``top_k`` routed
    experts in each sparse layer."""
    sparse = sum(1 for m in sizes["mlp_types"] if m == "sparse")
    return params(sizes)["dense"] + sparse * sizes["top_k"] * expert_params(sizes)


def attention_pairs(tokens: int, window: int | None) -> int:
    """(query, key) pairs a causal mask lets through in one document."""
    if window is None or tokens <= window:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


def attention_flops(tokens: int, sizes: dict, kinds: tuple = ("full", "window")) -> int:
    """Scores and weighted sums of one document over the layers of ``kinds``:
    two products of ``head_dim`` multiply-adds a pair and head."""
    flops = 0
    for kind, heads, _mlp in _layers(sizes):
        if kind in kinds:
            pairs = attention_pairs(tokens, sizes["window"] if kind == "window" else None)
            flops += 2 * 2 * pairs * heads * sizes["head_dim"]
    return flops


def forward_flops(tokens: int, sizes: dict) -> int:
    """One document of ``tokens`` real tokens through every kept layer."""
    return 2 * tokens * active_params(sizes) + attention_flops(tokens, sizes)


def forward_least_bytes(experts_touched: float, sizes: dict) -> float:
    """Least HBM traffic of one launch: every dense matrix once, the routed
    experts that got a token (``experts_touched``, summed over the sparse
    layers) once; activations and the looked-up embedding rows are a few
    megabytes beside gigabytes and left out."""
    return BYTES * (params(sizes)["dense"] + experts_touched * expert_params(sizes))


def grouped_matmul_flops(routed_pairs: float, sizes: dict) -> float:
    """The grouped product's multiply-adds: each routed (token, expert) pair
    through that expert's three matrices."""
    return 2 * routed_pairs * expert_params(sizes)


def grouped_matmul_least_bytes(experts_touched: float, routed_pairs: float,
                               sizes: dict) -> float:
    """The weights of the experts that got a token, once, and each routed
    pair's row in and out of both products (hidden in, 2 x expert width out;
    expert width in, hidden out) at two bytes a value."""
    rows = routed_pairs * (2 * sizes["hidden"] + 3 * sizes["expert_ffn"])
    return BYTES * (experts_touched * expert_params(sizes) + rows)


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of the two times."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
