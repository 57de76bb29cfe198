"""The whole answer path's share of the chip's peak FLOP/s over the window:
decoder forward FLOPs of every prefilled and every decoded token (2 x the
124M matrices, attention over the context, the output head;
``costs.decoder_flops``) / (window seconds x peak bf16 FLOP/s).  Prefilled
tokens are the program's own count (adopted prefixes are not recomputed and
not counted)."""


def read(ctx):
    if ctx["peaks"] is None:
        return None
    d, dec = ctx["delta"], ctx["config"]["decoder"]
    prefilled = d.get("om.pathway_decode_prefill_tokens_total", 0)
    decoded = d.get("om.pathway_decode_tokens_total", 0)
    if not prefilled and not decoded:
        return None
    shape = dict(hidden=dec["n_embd"], layers=dec["n_layer"], ffn=dec["n_inner"],
                 vocab=dec["vocab_size"])
    answered = sum(1 for r in ctx["records"] if not r["failed"])
    flops = (ctx["costs"].decoder_flops(prefilled, 256, head_tokens=answered, **shape)
             + ctx["costs"].decoder_flops(decoded, 512, **shape))
    return 100.0 * flops / (ctx["seconds"] * ctx["peaks"]["bf16_flops_per_s"])
