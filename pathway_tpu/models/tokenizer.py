"""Tokenizers for the JAX encoder stack.

In a connected environment ``load_tokenizer`` uses a local HuggingFace
tokenizer (WordPiece, as the reference's sentence-transformers models do);
offline it falls back to :class:`HashTokenizer` — a deterministic hashing
tokenizer producing the same id for the same word across runs, which is
enough for throughput benchmarking and for tests with fake embedders.

The hash tokenizer is byte-level (whitespace splits; ``[A-Za-z0-9_]`` and
all bytes >= 0x80 are word bytes; any other byte is a single punctuation
token; FNV-1a 64 per token) so the C++ batch encoder
(``_native/native.cpp pw_tokenize_batch``) and this Python fallback
produce identical ids bit-for-bit.
"""

from __future__ import annotations

import itertools
import os
import subprocess
import threading
from typing import Sequence

import numpy as np

from ..internals.lru import BoundedLru

try:  # hot-path C++ batch encoder
    from pathway_tpu import _native
except (OSError, ImportError, subprocess.CalledProcessError) as _exc:
    import warnings

    warnings.warn(
        f"native tokenizer unavailable ({type(_exc).__name__}: {_exc}); "
        "HashTokenizer runs its pure-Python path, ~130x slower",
        stacklevel=2,
    )
    _native = None

__all__ = ["HashTokenizer", "load_tokenizer", "token_cache", "TokenCache"]


class TokenCache(BoundedLru):
    """LRU memoization of per-text token rows.

    Dedup-heavy live streams (connector re-reads, repeated queries,
    unchanged chunks across document re-splits) re-tokenize identical
    text every update; caching the UNPADDED id row makes a repeat hit one
    dict lookup instead of a wordpiece/hash pass.  Rows are stored
    trimmed, so one entry serves every ``max_length`` that doesn't
    truncate differently — the key includes ``max_length`` to stay
    conservative.  Hit/miss totals feed ``/status``
    (``pathway_tokenizer_cache_hits_total`` / ``_misses_total``)."""

    def get_many(self, keys: list, encoder: str = "default") -> list:
        """Cached values (None for misses), LRU order refreshed; counts
        one hit/miss per key into the flight-recorder accumulators under
        ``encoder`` (the cache is process-global and shared — without the
        label two tokenizers in one server alias their hit rates)."""
        out, hits = super().get_many(keys)
        from ..internals.flight_recorder import record_tokenizer_cache

        record_tokenizer_cache(
            hits=hits, misses=len(keys) - hits, encoder=encoder
        )
        return out


_cache_lock = threading.Lock()
_cache: TokenCache | None = None


def token_cache() -> TokenCache | None:
    """Process-global tokenizer cache (``PATHWAY_TOKENIZER_CACHE`` rows,
    default 4096; 0 disables)."""
    global _cache
    if _cache is None:
        with _cache_lock:
            if _cache is None:
                try:
                    capacity = int(
                        os.environ.get("PATHWAY_TOKENIZER_CACHE", "4096")
                    )
                except ValueError:
                    capacity = 4096
                _cache = TokenCache(max(capacity, 0))
    return _cache if _cache.capacity > 0 else None


def reset_token_cache() -> None:
    """Test isolation hook (re-reads the env capacity)."""
    global _cache
    with _cache_lock:
        _cache = None

_FNV_OFFSET = 1469598103934665603
_FNV_PRIME = 1099511628211
_U64 = (1 << 64) - 1

_WS = frozenset(b" \t\n\r\f\v")


def _is_word_byte(c: int) -> bool:
    return (
        0x61 <= c <= 0x7A  # a-z
        or 0x41 <= c <= 0x5A  # A-Z
        or 0x30 <= c <= 0x39  # 0-9
        or c == 0x5F  # _
        or c >= 0x80
    )


def _fnv1a64(data: bytes, lowercase: bool) -> int:
    h = _FNV_OFFSET
    for c in data:
        if lowercase and 0x41 <= c <= 0x5A:
            c += 32
        h = ((h ^ c) * _FNV_PRIME) & _U64
    return h


class HashTokenizer:
    PAD = 0
    CLS = 1
    SEP = 2
    N_SPECIAL = 4

    def __init__(self, vocab_size: int = 30522, lowercase: bool = True):
        self.vocab_size = vocab_size
        self.lowercase = lowercase

    def tokenize(self, text: str) -> list[int]:
        data = text.encode("utf-8")
        mod = self.vocab_size - self.N_SPECIAL
        out: list[int] = []
        i = 0
        n = len(data)
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
            h = _fnv1a64(data[start:i], self.lowercase)
            out.append(self.N_SPECIAL + h % mod)
        return out

    def _encode_batch_raw(
        self,
        texts: Sequence[str],
        max_length: int,
        pair: Sequence[str] | None,
    ) -> tuple[np.ndarray, np.ndarray]:
        if _native is not None:
            batch, mask = _native.tokenize_batch(
                [t.encode("utf-8") for t in texts],
                max_length,
                self.vocab_size,
                self.lowercase,
                [p.encode("utf-8") for p in pair] if pair is not None else None,
            )
        else:
            ids_list = []
            for i, t in enumerate(texts):
                ids = [self.CLS] + self.tokenize(t)[: max_length - 2] + [self.SEP]
                if pair is not None:
                    ids = ids[: max_length // 2]
                    ids += self.tokenize(pair[i])[: max_length - len(ids) - 1] + [self.SEP]
                ids_list.append(ids[:max_length])
            L = max_length
            batch = np.zeros((len(texts), L), dtype=np.int32)
            mask = np.zeros((len(texts), L), dtype=np.int32)
            for i, ids in enumerate(ids_list):
                batch[i, : len(ids)] = ids
                mask[i, : len(ids)] = 1
        return batch, mask

    def encode_batch(
        self,
        texts: Sequence[str],
        max_length: int = 256,
        pair: Sequence[str] | None = None,
        return_type_ids: bool = False,
    ) -> tuple[np.ndarray, ...]:
        """Returns (ids[B,L], mask[B,L]) padded to ``max_length``; with
        ``return_type_ids`` also the BERT segment ids (0 for
        ``[CLS] A [SEP]``, 1 for ``B [SEP]``).  Rows memoize through the
        process-global :func:`token_cache` — only cache misses pay the
        tokenize pass; the padded batch is assembled from trimmed rows
        either way, bit-identical to the uncached path (ids are a
        contiguous non-zero prefix, so the mask is derivable)."""
        cache = token_cache()
        if cache is None:
            batch, mask = self._encode_batch_raw(texts, max_length, pair)
        else:
            keys = [
                (
                    "hash", self.vocab_size, self.lowercase, max_length,
                    t, None if pair is None else pair[i],
                )
                for i, t in enumerate(texts)
            ]
            rows = cache.get_many(keys, encoder="hash")
            miss = [i for i, r in enumerate(rows) if r is None]
            if len(miss) == len(texts):
                # all-miss (cold ingest of unique docs): keep the raw
                # padded arrays as-is — populate the cache, skip the
                # per-row reassembly entirely
                batch, mask = self._encode_batch_raw(texts, max_length, pair)
                cache.put_many(
                    [
                        (keys[i], batch[i, : int(mask[i].sum())].copy())
                        for i in range(len(texts))
                    ]
                )
            else:
                if miss:
                    raw_ids, raw_mask = self._encode_batch_raw(
                        [texts[i] for i in miss],
                        max_length,
                        None if pair is None else [pair[i] for i in miss],
                    )
                    for j, i in enumerate(miss):
                        rows[i] = raw_ids[j, : int(raw_mask[j].sum())].copy()
                    cache.put_many([(keys[i], rows[i]) for i in miss])
                batch = np.zeros((len(texts), max_length), dtype=np.int32)
                mask = np.zeros((len(texts), max_length), dtype=np.int32)
                for i, row in enumerate(rows):
                    batch[i, : len(row)] = row
                    mask[i, : len(row)] = 1
        if not return_type_ids:
            return batch, mask
        if pair is None:
            return batch, mask, np.zeros_like(batch)
        # segment 1 starts strictly after the first SEP (special id, cannot
        # collide with hashed word ids).  If truncation dropped segment A's
        # SEP the row degrades to all-zeros — harmless for hashed vocab.
        is_sep = batch == self.SEP
        type_ids = ((np.cumsum(is_sep, axis=1) - is_sep) > 0).astype(np.int32) * mask
        return batch, mask, type_ids


_hf_wrapper_ids = itertools.count()


class _HFTokenizerWrapper:
    def __init__(self, tok):
        self.tok = tok
        self.vocab_size = tok.vocab_size
        # cache identity: the checkpoint name when there is one, else a
        # process-unique token — NEVER id(tok), whose address can be
        # recycled by a later tokenizer and alias its cached rows
        name = getattr(tok, "name_or_path", None)
        self._cache_name = name if name else f"anon#{next(_hf_wrapper_ids)}"

    def _encode_batch_raw(self, texts, max_length, pair):
        enc = self.tok(
            list(texts),
            list(pair) if pair is not None else None,
            padding="max_length",
            truncation=True,
            max_length=max_length,
            return_tensors="np",
        )
        ids = enc["input_ids"].astype(np.int32)
        mask = enc["attention_mask"].astype(np.int32)
        type_ids = enc.get("token_type_ids")
        type_ids = (
            type_ids.astype(np.int32) if type_ids is not None else np.zeros_like(ids)
        )
        return ids, mask, type_ids

    def encode_batch(self, texts, max_length=256, pair=None, return_type_ids=False):
        cache = token_cache()
        # left-padding tokenizers (some generation models) break the
        # trimmed-prefix row representation — bypass the cache for them
        if cache is None or getattr(self.tok, "padding_side", "right") != "right":
            ids, mask, type_ids = self._encode_batch_raw(texts, max_length, pair)
        else:
            keys = [
                (
                    "hf", self._cache_name, max_length,
                    t, None if pair is None else pair[i],
                )
                for i, t in enumerate(texts)
            ]
            rows = cache.get_many(keys, encoder=self._cache_name)
            miss = [i for i, r in enumerate(rows) if r is None]
            if len(miss) == len(texts):
                # all-miss fast path: return the raw padded arrays as-is
                ids, mask, type_ids = self._encode_batch_raw(
                    texts, max_length, pair
                )
                items = []
                for i in range(len(texts)):
                    n = int(mask[i].sum())
                    items.append(
                        (keys[i], (ids[i, :n].copy(), type_ids[i, :n].copy()))
                    )
                cache.put_many(items)
            else:
                if miss:
                    raw_ids, raw_mask, raw_tids = self._encode_batch_raw(
                        [texts[i] for i in miss],
                        max_length,
                        None if pair is None else [pair[i] for i in miss],
                    )
                    for j, i in enumerate(miss):
                        n = int(raw_mask[j].sum())
                        rows[i] = (raw_ids[j, :n].copy(), raw_tids[j, :n].copy())
                    cache.put_many([(keys[i], rows[i]) for i in miss])
                ids = np.zeros((len(texts), max_length), dtype=np.int32)
                mask = np.zeros((len(texts), max_length), dtype=np.int32)
                type_ids = np.zeros((len(texts), max_length), dtype=np.int32)
                for i, (row, trow) in enumerate(rows):
                    ids[i, : len(row)] = row
                    mask[i, : len(row)] = 1
                    type_ids[i, : len(trow)] = trow
        if not return_type_ids:
            return ids, mask
        return ids, mask, type_ids

    # unpadded id codec (decoder generation path — GPT-2-family
    # tokenizers have no pad token, so padding="max_length" would raise)
    def encode_ids(self, text: str) -> list[int]:
        return list(self.tok.encode(text, add_special_tokens=False))

    def decode_ids(self, ids) -> str:
        return self.tok.decode(list(ids), skip_special_tokens=True)


def load_tokenizer(model_name: str | None = None, vocab_size: int = 30522):
    """Local HF tokenizer when available, hashing fallback otherwise."""
    if model_name is not None:
        try:
            from transformers import AutoTokenizer

            tok = AutoTokenizer.from_pretrained(model_name, local_files_only=True)
            return _HFTokenizerWrapper(tok)
        except Exception:
            pass
    return HashTokenizer(vocab_size=vocab_size)
