"""Share of the hybrid embedder's launched tokens that were padding: 1 - real
tokens (``pathway_ssm_tokens_total``) / the tokens of the launches' buckets
(``pathway_ssm_bucket_tokens_total``), difference over the window.  A padded
token costs what a real one does.  Nothing when the program counts no such
launches."""


def read(ctx):
    d = ctx["delta"]
    bucket = d.get("ssm.bucket_tokens_total", 0)
    return 100.0 * (1.0 - d.get("ssm.tokens_total", 0) / bucket) if bucket else None
