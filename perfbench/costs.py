"""Operations and bytes of the work a cell does, from shapes alone.

Whatever implements a step, its least work is reckoned here, so a later PR
that replaces a kernel is still held to the same count.  ``peaks`` reads
``peaks.json``; a device that is not in the table is an error, never a
default.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or not isinstance(table[device_kind], dict):
        raise KeyError(
            f"device kind {device_kind!r} is not in perfbench/peaks.json; "
            "add its published peaks with their source"
        )
    return table[device_kind]


def search_bytes(capacity: int, dim: int, itemsize: int) -> int:
    """Least HBM traffic of one brute-force search tick: every slot of the
    row matrix once plus its one-byte-per-slot validity mask (the kernel
    reads it as f32: not counted, the least is a byte).  The query block
    and the top-k outputs are thousands of bytes and left out."""
    return capacity * dim * itemsize + capacity


def search_flops(queries: int, rows: int, dim: int) -> int:
    """Multiply-adds of scoring ``queries`` against ``rows``."""
    return 2 * queries * rows * dim


def encoder_flops(tokens: int, seq: int, *, hidden: int, layers: int, ffn: int) -> int:
    """Forward FLOPs of a BERT-style encoder over ``tokens`` real tokens in
    sequences of about ``seq`` tokens: per layer 4 h^2 (Q, K, V, output)
    plus 2 h f (MLP) multiply-adds a token, plus 2 seq h for the scores
    and the weighted sum.  Embedding look-ups and norms are left out."""
    per_token = layers * (2 * (4 * hidden * hidden + 2 * hidden * ffn)
                          + 2 * 2 * seq * hidden)
    return tokens * per_token


def decoder_flops(tokens: int, context: int, *, hidden: int, layers: int,
                  ffn: int, vocab: int, head_tokens: int | None = None) -> int:
    """Forward FLOPs of a GPT-2-style decoder over ``tokens`` positions that
    each attend to about ``context`` earlier ones, with the output head
    (2 h V) for ``head_tokens`` of them (all, unless given: a prefill needs
    the logits of a prompt's last position only)."""
    per_token = layers * (2 * (4 * hidden * hidden + 2 * hidden * ffn)
                          + 2 * 2 * context * hidden)
    heads = tokens if head_tokens is None else head_tokens
    return tokens * per_token + heads * 2 * hidden * vocab


def decode_step_bytes(matrix_params: int, kv_values_per_token: int, live_tokens: float,
                      itemsize: int = 2) -> float:
    """Least HBM traffic of one decode step: every matrix the step multiplies
    by once (blocks and output head; ``matrix_params``) and the cached keys
    and values of every live position of the rows in the step
    (``live_tokens`` x ``kv_values_per_token``), each value at ``itemsize``
    bytes: 2, the bfloat16 the configurations state as the decoder's compute
    and KV type.  A program that keeps float32 matrices reads twice that and
    shows as under half of its roofline; activations, the new token's
    embedding row and the logits are thousands of bytes and left out."""
    return itemsize * (matrix_params + kv_values_per_token * live_tokens)
