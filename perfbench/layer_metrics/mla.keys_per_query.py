"""Keys a query of the latent-attention embedder's launches saw: the (query,
key) pairs the causal mask let through (``pathway_mla_attention_pairs_total``:
``L (L + 1) / 2`` a document of ``L`` tokens, once a launch) over the real
tokens (``pathway_mla_tokens_total``), difference over the window: how much of
a token's work is quadratic in its document's length (10,240 multiply-adds a
key and layer beside 69 million a token outside attention).  Nothing when the
program counts no such launches."""


def read(ctx):
    d = ctx["delta"]
    tokens = d.get("mla.tokens_total", 0)
    return d.get("mla.attention_pairs_total", 0) / tokens if tokens else None
