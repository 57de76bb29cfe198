"""Share of the latent-attention embedder's launched tokens that were padding:
1 - real tokens (``pathway_mla_tokens_total``) / the tokens of the launches'
buckets (``pathway_mla_bucket_tokens_total``), difference over the window.  A
padded token costs what a real one does (PERF.md 7 #14).  Nothing when the
program counts no such launches."""


def read(ctx):
    d = ctx["delta"]
    bucket = d.get("mla.bucket_tokens_total", 0)
    return 100.0 * (1.0 - d.get("mla.tokens_total", 0) / bucket) if bucket else None
