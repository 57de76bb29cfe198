"""The whole answer path's share of the chip's peak FLOP/s over the window:
decoder forward FLOPs of every prompt token and every decoded token of the
answers that came (2 x the 124M matrices, attention over the context, the
output head for the decoded tokens; ``costs.decoder_flops``) / (window seconds
x peak bf16 FLOP/s).  Prompt tokens are counted from the served contexts
(``checks/answers.py`` ``prompt_lengths``, assembled once a run), whichever launch took them in: a
packed prefill or, for a prompt that adopted a resident block, the
multi-token launches.  A prompt token attends to half its prompt, a decoded
one to the prompt and half the answer.  The query encoder and the scan (2.3
GFLOP a question against 100-200 of its prompt) are left out, so the share is
if anything too low."""

from checks import answers


def read(ctx):
    if ctx["peaks"] is None:
        return None
    prompts = answers.prompt_lengths(ctx)
    if not prompts:
        return None
    dec, max_new = ctx["facts"]["decoder"], int(ctx["traffic"]["max_new_tokens"])
    shape = dict(hidden=dec["hidden"], layers=dec["layers"], ffn=dec["ffn"], vocab=dec["vocab"])
    flops = sum(ctx["costs"].decoder_flops(p, p / 2, head_tokens=1, **shape)
                + ctx["costs"].decoder_flops(max_new - 1, p + max_new / 2, **shape)
                for p in prompts)
    return 100.0 * flops / (ctx["seconds"] * ctx["peaks"]["bf16_flops_per_s"])
