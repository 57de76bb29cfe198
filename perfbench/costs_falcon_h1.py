"""Operations and least bytes of the hybrid embedder's forward, from shapes
alone (``sizes`` is ``encoders/falcon_h1.py`` ``sizes(config)``), whatever
implements it: the whole forward, attention, the state-space scan.  Embedding
look-ups, norms, rotary, softplus, the gates and the multipliers are left out
(thousands of operations a token beside billions).
"""

from __future__ import annotations

BYTES = 2  # the configuration states bfloat16 weights and bfloat16 operands
SCAN_BYTES = 4  # and a float32 scan


def ssm_widths(sizes: dict) -> tuple[int, int]:
    """(the mixer's inner width, the width of B and C together's half)."""
    return (sizes["ssm_heads"] * sizes["ssm_head_dim"],
            sizes["ssm_groups"] * sizes["ssm_state"])


def layer_params(sizes: dict) -> dict:
    """One layer: ``attention`` (Wq, Wk, Wv, Wo), ``mixer`` (W_in, W_out and
    the convolution, dt_bias, A_log, D and the gated norm), ``mlp``, ``norms``."""
    d, hd = sizes["hidden"], sizes["head_dim"]
    d_ssm, bc = ssm_widths(sizes)
    w_in = d * (2 * d_ssm + 2 * bc + sizes["ssm_heads"])
    small = (d_ssm + 2 * bc) * (sizes["conv_taps"] + 1) + 3 * sizes["ssm_heads"] + d_ssm
    return {"attention": 2 * d * sizes["heads"] * hd + 2 * d * sizes["kv_heads"] * hd,
            "mixer": w_in + d_ssm * d + small, "mixer_matrices": w_in + d_ssm * d,
            "mlp": 3 * d * sizes["ffn"], "norms": 2 * d}


def params(sizes: dict) -> dict:
    """Parameters held: ``embedding``, ``layers``, ``total``."""
    layer = layer_params(sizes)
    one = layer["attention"] + layer["mixer"] + layer["mlp"] + layer["norms"]
    embedding = sizes["vocab"] * sizes["hidden"]
    return {"embedding": embedding, "layer": one, "layers": sizes["layers"] * one,
            "total": embedding + sizes["layers"] * one + sizes["hidden"]}


def matrix_params(sizes: dict) -> int:
    """Parameters of the matrices every token is multiplied by, all layers."""
    layer = layer_params(sizes)
    return sizes["layers"] * (layer["attention"] + layer["mixer_matrices"] + layer["mlp"])


def attention_flops(tokens: int, sizes: dict) -> int:
    """Scores and weighted sums of one document under the causal mask: two
    products of ``head_dim`` multiply-adds a pair and head, every layer."""
    pairs = tokens * (tokens + 1) // 2
    return sizes["layers"] * 2 * 2 * pairs * sizes["heads"] * sizes["head_dim"]


def scan_flops(tokens: float, sizes: dict) -> float:
    """The chunked scan of ONE layer over ``tokens`` tokens at the
    configuration's chunk ``Q``: a token's ``C . B`` with the (Q + 1) / 2
    tokens of its chunk it may see (a group), those scores times ``dt x``
    (a head), the chunk's state ``x^T B`` and the carried state's ``C S``
    (a head, P x N each); the convolution's taps."""
    h, p, n, g = (sizes[k] for k in ("ssm_heads", "ssm_head_dim", "ssm_state", "ssm_groups"))
    seen = (sizes["chunk"] + 1) / 2
    d_ssm, bc = ssm_widths(sizes)
    a_token = (2 * g * n * seen + 2 * h * p * seen + 2 * 2 * h * p * n
               + 2 * sizes["conv_taps"] * (d_ssm + 2 * bc))
    return tokens * a_token


def scan_least_bytes(tokens: float, sizes: dict) -> float:
    """Least HBM traffic of ONE layer's scan: ``x``, ``dt``, ``B`` and ``C``
    read once and ``y`` written once, float32; the states stay on the chip."""
    d_ssm, bc = ssm_widths(sizes)
    return SCAN_BYTES * tokens * (2 * d_ssm + sizes["ssm_heads"] + 2 * bc)


def forward_flops(tokens: int, sizes: dict) -> float:
    """One document of ``tokens`` real tokens through every kept layer:
    every matrix, attention's causal pairs, the scan."""
    return (2 * tokens * matrix_params(sizes) + attention_flops(tokens, sizes)
            + sizes["layers"] * scan_flops(tokens, sizes))


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of the two times."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
