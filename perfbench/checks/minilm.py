"""Plain reference of the sentence encoder: a BERT-style forward pass in
``jax.numpy`` and float32 with mean pooling and L2 normalisation, as
``sentence-transformers/all-MiniLM-L6-v2`` defines it, and the hash
tokenizer that stands in for its wordpiece vocabulary.  No kernels, no
buckets, no cache; imports nothing of the program.

``lowered=True`` is the control: every matrix product takes its operands
rounded to float8 (e4m3), the nearest precision below the bfloat16 the
configuration states for the encoder.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

PAD, CLS, SEP, N_SPECIAL = 0, 1, 2, 4
# the offset basis is the one the served hash tokenizer uses (the standard
# FNV-1a basis less its last decimal digit); a stand-in vocabulary either way
_FNV_OFFSET, _FNV_PRIME, _U64 = 1469598103934665603, 1099511628211, (1 << 64) - 1
_WS = frozenset(b" \t\n\r\f\v")


def _is_word_byte(c: int) -> bool:
    return (0x61 <= c <= 0x7A or 0x41 <= c <= 0x5A or 0x30 <= c <= 0x39
            or c == 0x5F or c >= 0x80)


def tokenize(text: str, vocab_size: int, max_length: int) -> list[int]:
    """``[CLS] words [SEP]``: a word is a run of letters, digits, ``_`` or
    non-ASCII bytes, anything else is a token of its own; each maps to
    ``4 + FNV-1a64(lower-cased bytes) mod (vocab - 4)``."""
    data = text.encode("utf-8")
    out, i, n = [], 0, len(data)
    while i < n:
        c = data[i]
        if c in _WS:
            i += 1
            continue
        start = i
        if _is_word_byte(c):
            while i < n and _is_word_byte(data[i]):
                i += 1
        else:
            i += 1
        h = _FNV_OFFSET
        for b in data[start:i]:
            if 0x41 <= b <= 0x5A:
                b += 32
            h = ((h ^ b) * _FNV_PRIME) & _U64
        out.append(N_SPECIAL + h % (vocab_size - N_SPECIAL))
    return ([CLS] + out[: max_length - 2] + [SEP])[:max_length]


def tokenize_batch(texts, vocab_size: int, max_length: int):
    rows = [tokenize(t, vocab_size, max_length) for t in texts]
    width = max(len(r) for r in rows)
    ids = np.zeros((len(rows), width), np.int32)
    mask = np.zeros((len(rows), width), np.int32)
    for i, r in enumerate(rows):
        ids[i, : len(r)] = r
        mask[i, : len(r)] = 1
    return ids, mask


def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def forward(params, ids, mask, *, heads: int, eps: float, lowered: bool = False):
    """[B, S] ids and mask -> [B, H] unit embeddings."""
    low = _fp8 if lowered else (lambda x: x)

    def mm(spec, a, b):
        return jnp.einsum(spec, low(a), low(b))

    n_layers = sum(1 for k in params if k.startswith("layer_"))
    x = (params["tok_emb"]["embedding"][ids]
         + params["pos_emb"]["embedding"][jnp.arange(ids.shape[1])][None]
         + params["type_emb"]["embedding"][0][None, None])
    x = _layer_norm(x, params["ln_emb"], eps)
    keep = mask[:, None, None, :] > 0
    for i in range(n_layers):
        p = params[f"layer_{i}"]
        a = p["attention"]
        q = mm("bsh,hnd->bsnd", x, a["query"]["kernel"]) + a["query"]["bias"]
        k = mm("bsh,hnd->bsnd", x, a["key"]["kernel"]) + a["key"]["bias"]
        v = mm("bsh,hnd->bsnd", x, a["value"]["kernel"]) + a["value"]["bias"]
        s = mm("bqnd,bknd->bnqk", q / math.sqrt(q.shape[-1]), k)
        w = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
        ctx = mm("bnqk,bknd->bqnd", w, v)
        out = mm("bqnd,ndh->bqh", ctx, a["out"]["kernel"]) + a["out"]["bias"]
        x = _layer_norm(x + out, p["ln1"], eps)
        h = mm("bsh,hf->bsf", x, p["mlp_in"]["kernel"]) + p["mlp_in"]["bias"]
        h = jax.nn.gelu(h, approximate=False)
        h = mm("bsf,fh->bsh", h, p["mlp_out"]["kernel"]) + p["mlp_out"]["bias"]
        x = _layer_norm(x + h, p["ln2"], eps)
    m = mask[:, :, None].astype(jnp.float32)
    pooled = jnp.sum(x * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)


def encode(params, texts, *, vocab_size: int, max_length: int, heads: int,
           eps: float, lowered: bool = False, block: int = 256) -> np.ndarray:
    """Embeddings of ``texts`` in blocks of ``block`` rows, grouped by
    padded width (multiples of 16) so that few shapes compile."""
    out = np.zeros((len(texts), params["ln_emb"]["scale"].shape[0]), np.float32)
    rows = [tokenize(t, vocab_size, max_length) for t in texts]
    by_width: dict[int, list[int]] = {}
    for i, r in enumerate(rows):
        by_width.setdefault(-(-len(r) // 16) * 16, []).append(i)
    fwd = jax.jit(forward, static_argnames=("heads", "eps", "lowered"))
    with jax.default_matmul_precision("highest"):
        for width, members in sorted(by_width.items()):
            for lo in range(0, len(members), block):
                part = members[lo: lo + block]
                ids = np.zeros((block, width), np.int32)
                mask = np.zeros((block, width), np.int32)
                mask[:, 0] = 1
                for j, i in enumerate(part):
                    ids[j, : len(rows[i])] = rows[i]
                    mask[j, : len(rows[i])] = 1
                emb = fwd(params, jnp.asarray(ids), jnp.asarray(mask),
                          heads=heads, eps=eps, lowered=lowered)
                out[part] = np.asarray(emb)[: len(part)]
    return out
