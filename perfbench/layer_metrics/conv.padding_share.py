"""Share of the conv embedder's launched tokens that were padding: 1 - real
tokens (``pathway_conv_tokens_total``) / the tokens of the launches' buckets
(``pathway_conv_bucket_tokens_total``), difference over the window.  Every
layer's projections run over the bucket's padding as over text.  Nothing when
the program counts no such launches."""


def read(ctx):
    d = ctx["delta"]
    bucket = d.get("conv.bucket_tokens_total", 0)
    return 100.0 * (1.0 - d.get("conv.tokens_total", 0) / bucket) if bucket else None
