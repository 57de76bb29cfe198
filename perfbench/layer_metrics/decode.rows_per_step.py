"""Sequences in one launch of the decode session that advances rows (a
single-token ``decode_step`` or a multi-token ``verify`` launch, which carries
the rows that decode beside the rows whose prompt tail is still ingested):
``pathway_decode_batch_rows`` sum over count of both kinds, difference over
the window.  Nothing when neither launched."""

KINDS = ("decode_step", "verify")


def read(ctx):
    d = ctx["delta"]
    launches = sum(d.get(f'om.pathway_decode_batch_rows_count{{kind="{k}"}}', 0) for k in KINDS)
    rows = sum(d.get(f'om.pathway_decode_batch_rows_sum{{kind="{k}"}}', 0) for k in KINDS)
    return rows / launches if launches else None
