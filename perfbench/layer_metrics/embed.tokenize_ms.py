"""Mean time of one ``tokenizer.encode_batch`` call of the sentence encoder
(``models/encoder.py`` under ``embed.tokenize``; ``stage="embed.tokenize"``
sum / count over the window)."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("stage.embed.tokenize.count", 0)
    return d["stage.embed.tokenize.sum"] / n if n else None
