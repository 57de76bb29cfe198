"""Mean time the index node takes to apply one engine timestamp's adds and
removes: key bookkeeping, staging the rows for the next scatter, payloads
(``ExternalIndexNode._apply_index_updates`` under ``index.apply``;
``stage="index.apply"`` sum / count over the window)."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("stage.index.apply.count", 0)
    return d["stage.index.apply.sum"] / n if n else None
