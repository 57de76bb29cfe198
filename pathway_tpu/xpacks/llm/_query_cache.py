"""Serving-plane query cache stack + CPU/TPU collaborative embedding.

Production query streams are heavily repeated and near-duplicate, so the
cheapest device tick is the one that never launches (ROADMAP item 5).
Three layers sit between ``RetrievePlane._batch`` and the device, each
independently bounded and disable-able:

* an **embedding cache** keyed on the token-id hash of the query (one
  level up from ``models/tokenizer.py`` ``TokenCache`` — POST
  tokenization, so whitespace/casing variants that tokenize identically
  hit), bounded LRU of ``PATHWAY_EMBED_CACHE`` rows.  Hits skip the
  encoder entirely; only the misses ride the device tick as a PARTIAL
  batch (a tick with 6/8 hits launches a 2-row bucket — PR 5 packed
  dispatch bucketing makes the smaller launch bit-exact, and a fused
  device-array result re-enters ``search_embedded`` combined ON DEVICE
  with the cached host rows, no host round trip for the fresh rows);

* a **result cache** keyed on ``(token-hash, k, metric, filter)`` whose
  entries carry the index freshness watermark
  (``ExternalIndexNode.commit_seq``, bumped by every flush that changes
  the corpus — PR 4's freshness plumbing grown into an exact
  invalidation signal).  A hit is served only while the index has not
  advanced past the entry's watermark; ``PATHWAY_RESULT_CACHE_STALE_S``
  is a stale-while-revalidate window — within it a stale entry is
  served as-is and the query is resubmitted in the background as a
  DEFERRED runtime item (``DeviceTickRuntime.submit(defer=True)``, PR
  12) so the entry refreshes off the latency path.  Tier migrations
  (PR 12) deliberately do NOT bump the watermark: scores are
  tier-independent by construction, and a migration storm must not
  flush the cache;

* a **WindVE-style collaborative path** (arXiv:2504.14941): when the
  INTERACTIVE queue depth exceeds ``PATHWAY_COLLAB_DEPTH``, short cold
  queries (token mass ≤ ``PATHWAY_COLLAB_MAX_TOKENS``) embed on host
  CPU — the SAME flax model applied on the CPU backend over the exact
  param tree, parity-checked against the device encoder once at first
  engagement — concurrently with the in-flight device launch instead of
  queuing behind it.

Correctness across the existing surface: the stack is bypassed entirely
while the index is restoring (PR 6), while the breaker is anything but
closed (PR 3 — BM25 answers must never be cached as authoritative, and
a half-open probe must actually probe the device), and for lexical
(``query_is_text``) indexes; caches live per serving plane, so entries
are per-encoder and per-mesh-identity (PR 8) by construction, and the
values cached are the final f32 embeddings / (key, score) rows — valid
at every ``index_dtype`` (PR 11).

Counters (``pathway_query_cache_*_total{layer=}``,
``pathway_collab_embeds_total``) feed ``/status`` via a weak-registry
metrics provider and a ``"query_cache"`` block on ``/v1/health`` gated
on this module being imported (probes never pull jax).
"""

from __future__ import annotations

import hashlib
import itertools
import threading
import warnings
import weakref
from typing import Any

import numpy as np

from ...internals.lru import BoundedLru

__all__ = [
    "EmbeddingCache",
    "ResultCache",
    "CollabEncoder",
    "QueryCacheStack",
    "build_stack",
    "query_cache_stats",
    "query_cache_status",
    "reset_query_cache_counters",
]


# ---------------------------------------------------------------------------
# knobs (garbage warns and falls back to the default — the PR 11 idiom;
# one shared parser in internals/config so every knob family warns the
# same way)
# ---------------------------------------------------------------------------

from ...internals.config import env_float as _base_env_float
from ...internals.config import env_int as _base_env_int


def _env_int(name: str, default: int, lo: int = 0) -> int:
    return _base_env_int(name, default, lo=lo)


def _env_float(name: str, default: float, lo: float = 0.0) -> float:
    return _base_env_float(name, default, lo=lo)


def embed_cache_rows() -> int:
    """``PATHWAY_EMBED_CACHE`` (default 4096; 0 disables): embedding-cache
    LRU capacity in rows."""
    return _env_int("PATHWAY_EMBED_CACHE", 4096)


def result_cache_rows() -> int:
    """``PATHWAY_RESULT_CACHE`` (default 2048; 0 disables): result-cache
    LRU capacity in entries."""
    return _env_int("PATHWAY_RESULT_CACHE", 2048)


def result_cache_stale_s() -> float:
    """``PATHWAY_RESULT_CACHE_STALE_S`` (default 0 = exact invalidation
    only): stale-while-revalidate window in seconds — a result whose
    watermark the index advanced past within this window is still
    served, with a deferred background refresh."""
    return _env_float("PATHWAY_RESULT_CACHE_STALE_S", 0.0)


def collab_depth() -> int:
    """``PATHWAY_COLLAB_DEPTH`` (default 8; 0 disables the collaborative
    path): INTERACTIVE queue depth beyond which short cold queries embed
    on host CPU instead of queuing for the device."""
    return _env_int("PATHWAY_COLLAB_DEPTH", 8)


def collab_max_tokens() -> int:
    """``PATHWAY_COLLAB_MAX_TOKENS`` (default 32): token-mass ceiling for
    a query to be eligible for the CPU collaborative path (long queries
    stay on the MXU where they are cheap per token)."""
    return _env_int("PATHWAY_COLLAB_MAX_TOKENS", 32, lo=1)


def collab_tolerance() -> float:
    """``PATHWAY_COLLAB_TOL`` (default 0.05): max |CPU − device|
    embedding divergence tolerated by the one-time parity probe before
    the collaborative path disables itself (bf16 device compute vs the
    CPU backend's rounding is the expected source)."""
    return _env_float("PATHWAY_COLLAB_TOL", 5e-2)


# ---------------------------------------------------------------------------
# process-global counters (+ /status provider, /v1/health block)
# ---------------------------------------------------------------------------

_LAYERS = ("embed", "result")
_counters_lock = threading.Lock()
_counters: dict[str, dict[str, int]] = {
    layer: {"hits": 0, "misses": 0, "stale_served": 0, "evictions": 0}
    for layer in _LAYERS
}
_collab_counters = {"embeds_total": 0, "engaged_ticks": 0, "parity_failures": 0}

#: live stacks for the health block (weak: a finished plane's stack
#: drops out with it)
_LIVE_STACKS: "weakref.WeakSet[QueryCacheStack]" = weakref.WeakSet()


def _record(layer: str, **deltas: int) -> None:
    with _counters_lock:
        c = _counters[layer]
        for key, n in deltas.items():
            c[key] += int(n)


def _record_collab(**deltas: int) -> None:
    with _counters_lock:
        for key, n in deltas.items():
            _collab_counters[key] += int(n)


def query_cache_stats() -> dict[str, Any]:
    """Counter snapshot (layer -> totals, plus the collab counters)."""
    with _counters_lock:
        snap: dict[str, Any] = {
            layer: dict(c) for layer, c in _counters.items()
        }
        snap["collab"] = dict(_collab_counters)
    for layer in _LAYERS:
        c = snap[layer]
        total = c["hits"] + c["misses"]
        c["hit_rate"] = round(c["hits"] / total, 4) if total else 0.0
    return snap


def reset_query_cache_counters() -> None:
    """Test isolation hook."""
    with _counters_lock:
        for c in _counters.values():
            for key in c:
                c[key] = 0
        for key in _collab_counters:
            _collab_counters[key] = 0


class _QueryCacheMetricsProvider:
    """``pathway_query_cache_*`` / ``pathway_collab_embeds_total``
    OpenMetrics series for the ``/status`` exposition."""

    def stats(self) -> dict:
        return query_cache_stats()

    def openmetrics_lines(self) -> list[str]:
        snap = query_cache_stats()
        lines: list[str] = []
        for family, key in (
            ("pathway_query_cache_hits_total", "hits"),
            ("pathway_query_cache_misses_total", "misses"),
            ("pathway_query_cache_stale_served_total", "stale_served"),
            ("pathway_query_cache_evictions_total", "evictions"),
        ):
            lines.append(f"# TYPE {family} counter")
            for layer in _LAYERS:
                lines.append(
                    f'{family}{{layer="{layer}"}} {snap[layer][key]}'
                )
        lines.append("# TYPE pathway_collab_embeds_total counter")
        lines.append(
            f"pathway_collab_embeds_total {snap['collab']['embeds_total']}"
        )
        return lines


#: strong module ref — monitoring's provider table is weak-valued
_provider: _QueryCacheMetricsProvider | None = None
_provider_lock = threading.Lock()


def _ensure_provider() -> None:
    global _provider
    with _provider_lock:
        if _provider is None:
            _provider = _QueryCacheMetricsProvider()
            from ...internals.monitoring import register_metrics_provider

            register_metrics_provider("query_cache", _provider)


def query_cache_status() -> dict | None:
    """Per-stack configuration + process counters for ``/v1/health``
    (None when no serving plane built a cache stack)."""
    stacks = [s for s in _LIVE_STACKS]
    if not stacks:
        return None
    out: dict[str, Any] = {"counters": query_cache_stats()}
    per_stack = {}
    for stack in stacks:
        # planes share the default "retrieve" label — disambiguate so one
        # long-lived server's stack can't shadow another's in the block
        label = stack.label
        if label in per_stack:
            label = f"{stack.label}#{stack.stack_id}"
        per_stack[label] = {
            "embed_rows": stack.embed_cache.capacity if stack.embed_cache else 0,
            "embed_used": len(stack.embed_cache) if stack.embed_cache else 0,
            "result_rows": (
                stack.result_cache.capacity if stack.result_cache else 0
            ),
            "result_used": len(stack.result_cache) if stack.result_cache else 0,
            "stale_s": stack.stale_s,
            "collab": stack.collab is not None,
            "collab_depth": stack.collab_depth,
            "collab_max_tokens": stack.collab_max_tokens,
        }
    out["planes"] = per_stack
    return out


# ---------------------------------------------------------------------------
# cache layers
# ---------------------------------------------------------------------------


class EmbeddingCache(BoundedLru):
    """Bounded LRU of token-hash -> final embedding row (np.float32).

    Stores the embeddings EXACTLY as the encoder produced them (the
    fused tick's device rows pulled to host once at fill time), so a
    hit hands the search the same values a fresh encode would — the
    partial-batch parity pin depends on it."""

    def get_many(self, keys: list) -> list:
        out, hits = super().get_many(keys)
        _record("embed", hits=hits, misses=len(keys) - hits)
        return out

    def put_many(self, items: list) -> None:
        evicted = super().put_many(items)
        if evicted:
            _record("embed", evictions=evicted)


class ResultCache(BoundedLru):
    """Bounded LRU of (token-hash, k, metric, filter) -> (node epoch,
    watermark, raw result rows).  Rows are the index's (key, score)
    pairs — the payload join happens at serve time against the LIVE doc
    payloads, so a retracted doc drops out of a cached answer the same
    way it drops out of a fresh one.

    ``get`` is the inherited one — (epoch, watermark, rows) or None; the
    HIT/MISS accounting is the caller's (a watermark mismatch is a miss
    or a stale serve, which this layer can't tell apart)."""

    def put(self, key, epoch: int, watermark: int, rows) -> None:
        evicted = super().put(key, (epoch, watermark, rows))
        if evicted:
            _record("result", evictions=evicted)


# ---------------------------------------------------------------------------
# collaborative CPU twin (WindVE)
# ---------------------------------------------------------------------------


#: the largest encoder that gets a host twin.  A short query costs the host
#: about two FLOPs a parameter a token: 16 tokens through 150M parameters
#: are 5 GFLOP, tens of milliseconds on a few cores, which is as long as a
#: query waits for the device behind a deep queue.  Past that the twin is
#: slower than the wait it was built to avoid, and its copy of the weights
#: (gigabytes, for a language-model embedder) is host memory for nothing.
COLLAB_MAX_PARAMS = 150_000_000


def _param_count(encoder: Any) -> int:
    import jax

    return sum(int(p.size) for p in jax.tree_util.tree_leaves(encoder.params))


class CollabEncoder:
    """CPU twin of a :class:`~pathway_tpu.models.encoder.SentenceEncoder`:
    the SAME flax module applied on the CPU backend over the EXACT param
    tree (copied once, lazily), so short cold queries can embed on host
    concurrently with the in-flight device launch when the INTERACTIVE
    queue is deep.

    ``pallas``/``ragged`` attention impls remap to the fused XLA kernel
    for the dense CPU apply (same numerics contract as the encoder's own
    off-TPU dense fallback); everything else runs as-is.  A one-time
    parity probe against the device encoder guards engagement — past
    ``PATHWAY_COLLAB_TOL`` the path disables itself loudly."""

    def __init__(self, encoder: Any):
        self.encoder = encoder
        self._lock = threading.Lock()
        self._apply = None
        self._params_cpu = None
        self._cpu_device = None
        #: None = not probed yet; True/False once the parity probe ran
        self.parity_ok: bool | None = None

    def _ensure_built(self):
        with self._lock:
            if self._apply is not None:
                return
            import dataclasses

            import jax

            cfg = self.encoder.cfg
            if cfg.attention_impl in ("pallas", "ragged"):
                cfg = dataclasses.replace(cfg, attention_impl="fused")
            model = cfg.build_models()[0]
            record = getattr(model, "record_launch", None)
            self._cpu_device = jax.devices("cpu")[0]
            # one D2H per param, once — afterwards the twin never touches
            # the accelerator
            self._params_cpu = jax.tree_util.tree_map(
                lambda p: jax.device_put(np.asarray(p), self._cpu_device),
                self.encoder.params,
            )

            def forward(params, ids, mask):
                out = model.apply({"params": params}, ids, mask)
                # a forward that also returns launch counters: the twin's
                # launches are not the device's, its counters are dropped
                return out if record is None else out[0]

            self._apply = jax.jit(forward)

    def encode_rows(self, ids_all: np.ndarray, mask_all: np.ndarray) -> np.ndarray:
        """Embed already-tokenized rows on the CPU backend -> [n, dim]
        f32 (normalized, like the device encoder's output).  Shapes pad
        to the shared (batch, seq) bucket grid so the twin's compile set
        stays as bounded as the device one's."""
        self._ensure_built()
        import jax

        from ...models.encoder import (
            BATCH_BUCKETS,
            _bucket,
            dispatch_dtype,
            pad_chunk,
        )

        n = ids_all.shape[0]
        longest = max(int(mask_all.sum(axis=1).max()), 1)
        seq = min(
            _bucket(longest, self.encoder.cfg.seq_buckets), ids_all.shape[1]
        )
        bb = _bucket(n, BATCH_BUCKETS)
        ids, mask, _ = pad_chunk(
            ids_all[:, :seq], mask_all[:, :seq], bb, seq,
            ids_dtype=dispatch_dtype(self.encoder.cfg.vocab_size),
        )
        dev = self._cpu_device
        out = self._apply(
            self._params_cpu,
            jax.device_put(ids, dev),
            jax.device_put(mask, dev),
        )
        return np.asarray(out, dtype=np.float32)[:n]

    def check_parity(self, device_rows: np.ndarray, ids, mask) -> bool:
        """One-time probe: |twin − device| on one query must stay within
        tolerance, else the collaborative path disables itself."""
        if self.parity_ok is not None:
            return self.parity_ok
        try:
            twin = self.encode_rows(ids, mask)
            diff = float(
                np.max(np.abs(twin - np.asarray(device_rows, dtype=np.float32)))
            )
            self.parity_ok = diff <= collab_tolerance()
            if not self.parity_ok:
                _record_collab(parity_failures=1)
                warnings.warn(
                    f"collaborative CPU embed disabled: parity probe diff "
                    f"{diff:.4g} exceeds PATHWAY_COLLAB_TOL="
                    f"{collab_tolerance():g}",
                    stacklevel=2,
                )
        except Exception as exc:  # noqa: BLE001 — never fail the tick
            self.parity_ok = False
            _record_collab(parity_failures=1)
            warnings.warn(
                f"collaborative CPU embed disabled: twin build failed "
                f"({type(exc).__name__}: {exc})",
                stacklevel=2,
            )
        return self.parity_ok


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------


def _token_hash(row: np.ndarray) -> bytes:
    """Key of one trimmed token-id row: whitespace/casing variants that
    tokenize identically share it (the whole point of hashing POST
    tokenization)."""
    return hashlib.blake2b(row.tobytes(), digest_size=16).digest()


_node_epochs = itertools.count(1)
_stack_ids = itertools.count(1)


def _node_epoch(node) -> int:
    """Process-unique epoch stamped per index node: commit_seq restarts
    near 0 for every engine life, so without the epoch a result cached
    at life 1's seq 5 would read as exactly fresh once life 2's counter
    reaches 5 again.  Monotonic counter, never id() (recyclable)."""
    ep = getattr(node, "_pw_query_cache_epoch", None)
    if ep is None:
        ep = next(_node_epochs)
        node._pw_query_cache_epoch = ep
    return ep


class QueryCacheStack:
    """Per-plane cache stack (see module docstring).  One instance per
    :class:`~pathway_tpu.xpacks.llm._scheduler.RetrievePlane`, so keys
    are scoped to one embedder + one index (one mesh identity, one
    metric) by construction."""

    def __init__(
        self,
        embedder: Any,
        label: str = "retrieve",
        *,
        embed_rows: int | None = None,
        result_rows: int | None = None,
        stale_s: float | None = None,
        depth: int | None = None,
        max_tokens: int | None = None,
    ):
        self.embedder = embedder
        self.label = label
        embed_rows = embed_cache_rows() if embed_rows is None else embed_rows
        result_rows = (
            result_cache_rows() if result_rows is None else result_rows
        )
        self.embed_cache = EmbeddingCache(embed_rows) if embed_rows > 0 else None
        self.result_cache = (
            ResultCache(result_rows) if result_rows > 0 else None
        )
        self.stale_s = result_cache_stale_s() if stale_s is None else stale_s
        self.collab_depth = collab_depth() if depth is None else depth
        self.collab_max_tokens = (
            collab_max_tokens() if max_tokens is None else max_tokens
        )
        self.stack_id = next(_stack_ids)
        ensure = getattr(embedder, "_ensure_encoder", None)
        self._has_encoder = ensure is not None
        self.collab: CollabEncoder | None = None
        if self._has_encoder and self.collab_depth > 0:
            enc = ensure()
            # a twin pays only where the host embeds a short query in
            # about a device tick: decided from the parameter count
            if _param_count(enc) <= COLLAB_MAX_PARAMS:
                self.collab = CollabEncoder(enc)
        #: queue-depth signal (overridable in tests); reads the runtime's
        #: INTERACTIVE backlog without spawning its thread
        self._depth_fn = self._runtime_depth
        #: result keys with an in-flight deferred refresh (dedup)
        self._refreshing: set = set()
        self._refresh_lock = threading.Lock()
        _ensure_provider()
        _LIVE_STACKS.add(self)

    # -- keys ------------------------------------------------------------
    def _encoder(self):
        if not self._has_encoder:
            return None
        return self.embedder._ensure_encoder()

    def _tokenize_keys(self, texts: list[str]):
        """(token keys, ids, mask, token lengths).  Model-backed
        embedders key on the trimmed token-id row (the TokenCache makes
        the repeat tokenize a dict lookup); generic deterministic UDF
        embedders fall back to the coerced text."""
        from ._utils import coerce_str

        enc = self._encoder()
        if enc is None:
            keys = [("text", coerce_str(t)) for t in texts]
            return keys, None, None, None
        ids_all, mask_all = enc.tokenizer.encode_batch(
            [coerce_str(t) for t in texts], max_length=enc.max_length
        )
        lens = mask_all.sum(axis=1).astype(int)
        keys = [
            _token_hash(ids_all[i, : lens[i]]) for i in range(len(texts))
        ]
        return keys, ids_all, mask_all, lens

    def _runtime_depth(self) -> int:
        from ...runtime import QoS, get_runtime

        return get_runtime().queue_depth(QoS.INTERACTIVE)

    # -- serve -----------------------------------------------------------
    def serve(self, plane, node, index, texts, specs, items):
        """The healthy vector path of ``RetrievePlane._batch`` with the
        cache stack in front: returns the raw result rows (one list of
        (key, score) per query), having launched the device encoder only
        for queries no layer could answer."""
        n = len(texts)
        tkeys, ids_all, mask_all, lens = self._tokenize_keys(texts)
        metric = getattr(index, "metric", None) or getattr(
            getattr(index, "index", None), "metric", ""
        )
        results: list = [None] * n
        pending: list[int] = list(range(n))
        # 1. result cache (exact watermark, else stale-within-window)
        if self.result_cache is not None:
            epoch_now = _node_epoch(node)
            wm_now = node.commit_seq
            pending = []
            hits = misses = stale = 0
            refresh: list[tuple] = []
            for i in range(n):
                k, flt = specs[i]
                rkey = (tkeys[i], int(k), metric, flt)
                ent = self.result_cache.get(rkey)
                if ent is None:
                    misses += 1
                    pending.append(i)
                    continue
                epoch, watermark, rows = ent
                if epoch == epoch_now and watermark == wm_now:
                    hits += 1
                    results[i] = rows
                    continue
                # guard BEFORE the stale_age scan: with the window
                # disabled (the default) a watermark mismatch must stay
                # a plain miss without paying the per-query history walk
                age = (
                    node.stale_age(watermark)
                    if self.stale_s > 0 and epoch == epoch_now
                    else None
                )
                if age is not None and age <= self.stale_s:
                    stale += 1
                    results[i] = rows
                    refresh.append((rkey, items[i]))
                else:
                    misses += 1
                    pending.append(i)
            _record("result", hits=hits, misses=misses, stale_served=stale)
            if refresh:
                self._schedule_refresh(plane, refresh)
        if not pending:
            return results
        # 2. embedding cache + 3. collab split + device launch for the rest
        wm_entry = node.commit_seq  # BEFORE the index read: a flush that
        # lands mid-search makes the entry conservatively old (a future
        # lookup misses), never wrongly fresh
        qvecs, collab_js = self._embed_pending(
            plane, texts, tkeys, ids_all, mask_all, lens, pending
        )
        from ...internals.flight_recorder import batch_stage

        with batch_stage("search"):
            raw = index.search_embedded(
                qvecs, [specs[i] for i in pending]
            )
        if self.result_cache is not None:
            for j, i in enumerate(pending):
                if j in collab_js:
                    # twin-embedded answers are tolerance-bounded, not
                    # bit-exact: serve them (that's the WindVE deal under
                    # pressure) but never freeze them into the cache —
                    # a later calm-queue repeat must recompute on device
                    continue
                k, flt = specs[i]
                self.result_cache.put(
                    (tkeys[i], int(k), metric, flt),
                    _node_epoch(node), wm_entry, raw[j],
                )
        for j, i in enumerate(pending):
            results[i] = raw[j]
        return results

    def _embed_pending(self, plane, texts, tkeys, ids_all, mask_all, lens,
                       pending):
        """Embeddings for the result-cache misses: cached rows fill from
        the embedding cache, short cold rows may take the CPU twin under
        queue pressure, the rest launch on the device as a partial
        batch.  Returns ``(query batch, collab-served positions)``: the
        [len(pending), dim] batch — a DEVICE array when fresh rows came
        back fused (cached host rows join it on device; the fresh rows
        never round-trip to host except once, to fill the cache) — plus
        the set of pending positions whose row came from the CPU twin
        (tolerance-bounded: the caller must not cache their results)."""
        from ._scheduler import _batch_embed, _batch_embed_device
        from ...internals.flight_recorder import batch_stage

        cached_rows = (
            self.embed_cache.get_many([tkeys[i] for i in pending])
            if self.embed_cache is not None
            else [None] * len(pending)
        )
        miss_pos = [j for j, row in enumerate(cached_rows) if row is None]
        collab_pos: list[int] = []
        if (
            miss_pos
            and self.collab is not None
            and self.collab.parity_ok is not False
            and ids_all is not None
            and self._depth_fn() > self.collab_depth
        ):
            collab_pos = [
                j
                for j in miss_pos
                if int(lens[pending[j]]) <= self.collab_max_tokens
            ]
        collab_set = set(collab_pos)
        device_pos = [j for j in miss_pos if j not in collab_set]
        collab_out: dict = {}
        dev_embs = None
        dev_host = None
        with batch_stage("embed"):
            collab_thread = None
            if collab_pos:
                rows_idx = [pending[j] for j in collab_pos]
                c_ids, c_mask = ids_all[rows_idx], mask_all[rows_idx]
                if self.collab.parity_ok is None:
                    # one-time probe: the FIRST engagement embeds its rows
                    # on the device too and compares — collab serves only
                    # once the twin proved itself
                    probe_rows = _batch_embed(plane.embedder,
                                              [texts[i] for i in rows_idx])
                    if self.collab.check_parity(
                        np.asarray(probe_rows, dtype=np.float32), c_ids, c_mask
                    ):
                        _record_collab(engaged_ticks=1)
                    collab_out["rows"] = np.asarray(probe_rows, np.float32)
                    collab_pos_run = []
                else:
                    collab_pos_run = collab_pos

                    def _twin():
                        try:
                            collab_out["rows"] = self.collab.encode_rows(
                                c_ids, c_mask
                            )
                        except Exception as exc:  # noqa: BLE001 — fall back
                            collab_out["error"] = exc

                    collab_thread = threading.Thread(
                        target=_twin, name="pw-collab-embed", daemon=True
                    )
                    collab_thread.start()
            else:
                collab_pos_run = []
            if device_pos:
                dev_texts = [texts[pending[j]] for j in device_pos]
                dev_embs = _batch_embed_device(plane.embedder, dev_texts)
                if dev_embs is None:
                    dev_host = np.asarray(
                        _batch_embed(plane.embedder, dev_texts),
                        dtype=np.float32,
                    )
            if collab_thread is not None:
                collab_thread.join()
                if "error" in collab_out:
                    # twin failed mid-flight: embed those rows on device
                    # after all (correctness over the concurrency win)
                    self.collab.parity_ok = False
                    _record_collab(parity_failures=1)
                    fb = np.asarray(
                        _batch_embed(
                            plane.embedder,
                            [texts[pending[j]] for j in collab_pos_run],
                        ),
                        dtype=np.float32,
                    )
                    collab_out["rows"] = fb
                elif collab_pos_run:
                    _record_collab(
                        embeds_total=len(collab_pos_run), engaged_ticks=1
                    )
        # every position the collab branch produced rows for is
        # non-cacheable: post-probe twin rows are tolerance-bounded, and
        # the probe tick's / twin-error fallback's rows come from the
        # HOST `_batch_embed` path — on a fused plane those differ from
        # the device encode at ~1e-7, enough to swap a near-tie rank, so
        # freezing their results would break the cached-vs-off bit-exact
        # contract for every later calm-queue repeat
        collab_served = set(collab_pos)
        # assemble the query batch.  Rows pad to the SAME power-of-two
        # batch-bucket grid the fused tick's encode_padded uses: the
        # search (and the device combine below) then compile against the
        # bounded bucket shapes instead of one program per distinct
        # hit/miss occupancy — pad rows are discarded by the search's
        # n_valid contract exactly like fused dispatch pads
        from ...models.encoder import BATCH_BUCKETS, _bucket

        dim = None
        for row in cached_rows:
            if row is not None:
                dim = len(row)
                break
        if dim is None and "rows" in collab_out:
            dim = collab_out["rows"].shape[1]
        if dim is None and dev_host is not None:
            dim = dev_host.shape[1]
        if dim is None and dev_embs is not None:
            dim = int(dev_embs.shape[1])
        n_p = len(pending)
        qb = _bucket(n_p, BATCH_BUCKETS) if n_p <= BATCH_BUCKETS[-1] else n_p
        base = np.zeros((qb, dim), dtype=np.float32)
        for j, row in enumerate(cached_rows):
            if row is not None:
                base[j] = row
        if "rows" in collab_out:
            for jj, j in enumerate(collab_pos):
                base[j] = collab_out["rows"][jj]
        # only DEVICE-encoder rows ever fill the embedding cache: collab
        # twin rows (and the probe tick's host-path rows) are tolerance-
        # bounded, not bit-exact — caching one would freeze its divergence
        # into every later hit, including under zero queue pressure.  The
        # twin absorbs pressure transiently; the cache fills from the
        # device once the queue drains
        fill_items = []
        if dev_host is not None:
            for jj, j in enumerate(device_pos):
                base[j] = dev_host[jj]
                if self.embed_cache is not None:
                    fill_items.append((tkeys[pending[j]], dev_host[jj].copy()))
            if fill_items:
                self.embed_cache.put_many(fill_items)
            # the fresh rows came from the HOST embed path, so the
            # cache-off tick would have searched a host array — match it
            return base[:n_p], collab_served
        if dev_embs is not None:
            # fused path: combine ON DEVICE — cached/collab host rows ride
            # one H2D, the fresh device rows never leave the device for
            # the search (one bounded D2H below only fills the cache).
            # The scatter index pads to the fresh batch's bucket with an
            # out-of-bounds slot (mode="drop"), so the combine compiles
            # once per (bucket, bucket) pair, not per occupancy
            import jax.numpy as jnp

            fresh = jnp.asarray(dev_embs).astype(jnp.float32)
            idx = np.full((int(fresh.shape[0]),), qb, dtype=np.int32)
            idx[: len(device_pos)] = device_pos
            q = jnp.asarray(base).at[jnp.asarray(idx)].set(
                fresh, mode="drop"
            )
            if self.embed_cache is not None:
                host_fresh = np.asarray(fresh, dtype=np.float32)
                for jj, j in enumerate(device_pos):
                    fill_items.append(
                        (tkeys[pending[j]], host_fresh[jj].copy())
                    )
            if fill_items:
                self.embed_cache.put_many(fill_items)
            return q, collab_served
        if fill_items:
            self.embed_cache.put_many(fill_items)
        if self._fused_serving():
            # no fresh device rows this tick, but the cache-off tick
            # would have searched DEVICE queries (encode_padded →
            # _prep_queries normalizes on device) — hand the cached rows
            # over as a device array so hits are bit-exact with misses
            import jax.numpy as jnp

            return jnp.asarray(base), collab_served
        return base[:n_p], collab_served

    def _fused_serving(self) -> bool:
        """Would ``_batch_embed_device`` take the fused path for this
        embedder?  Decides whether cached rows re-enter the search as a
        device array (bit-exact with the fused tick) or a host one."""
        from ._scheduler import _env_flag

        if not _env_flag("PATHWAY_FUSED_SERVING", True):
            return False
        enc = self._encoder()
        return enc is not None and getattr(enc, "encode_padded", None) is not None

    # -- stale-while-revalidate ------------------------------------------
    def _schedule_refresh(self, plane, refresh: list[tuple]) -> None:
        """Resubmit stale-served queries as DEFERRED runtime items
        (fire-and-forget, BULK_INGEST class — a cache refresh must not
        displace interactive work); at most one in flight per key.  The
        payload carries the result key so EVERY exit of the deferred
        batch (including the bypass paths: breaker open, node restoring)
        can release the in-flight marker — a leaked key would disable
        revalidation for that query for the plane's lifetime."""
        from ...runtime import QoS, get_runtime

        rt = get_runtime()
        group = plane._cache_refresh_group()
        for rkey, item in refresh:
            with self._refresh_lock:
                if rkey in self._refreshing:
                    continue
                self._refreshing.add(rkey)
            try:
                rt.submit(
                    group, (*item, rkey), qos=QoS.BULK_INGEST, defer=True,
                    sheddable=False,
                )
            except Exception:  # noqa: BLE001 — refresh is best-effort
                with self._refresh_lock:
                    self._refreshing.discard(rkey)

    def release_refresh(self, rkeys: list) -> None:
        """Drop the in-flight markers for a deferred batch, however it
        ended (computed, bypassed, or failed)."""
        with self._refresh_lock:
            for rkey in rkeys:
                self._refreshing.discard(rkey)

    def refresh(self, plane, node, index, items, rkeys) -> None:
        """Deferred-refresh handler body: recompute WITHOUT reading the
        result cache (a read would hit the same stale entry and loop)
        and write the fresh rows back under the keys the stale serve
        recorded.  The caller releases the in-flight markers."""
        from ...testing import faults as _faults

        if _faults.enabled:
            # chaos site cache.refresh: a raise here is contained by the
            # scheduler's refresh-batch guard (which logs and ALWAYS
            # releases the in-flight markers), so a failed recompute just
            # leaves the stale entry serving out its window
            _faults.perturb("cache.refresh")
        texts = [q for q, _, _ in items]
        specs = [(k, flt) for _, k, flt in items]
        tkeys, ids_all, mask_all, lens = self._tokenize_keys(texts)
        wm_entry = node.commit_seq
        epoch = _node_epoch(node)
        qvecs, collab_js = self._embed_pending(
            plane, texts, tkeys, ids_all, mask_all, lens,
            list(range(len(items))),
        )
        raw = index.search_embedded(qvecs, specs)
        if self.result_cache is not None:
            for i, rkey in enumerate(rkeys):
                if i in collab_js:
                    # a twin-embedded refresh must not freeze its
                    # tolerance-bounded answer; the marker release lets a
                    # later stale serve re-schedule on a calmer queue
                    continue
                self.result_cache.put(rkey, epoch, wm_entry, raw[i])


def build_stack(embedder: Any, label: str = "retrieve") -> QueryCacheStack | None:
    """Stack for one serving plane, or None when every layer is disabled
    or the embedder can't be keyed (non-deterministic UDF with no
    tokenizer — caching its output would freeze nondeterminism into
    answers)."""
    if embedder is None:
        return None
    has_encoder = getattr(embedder, "_ensure_encoder", None) is not None
    if not has_encoder and not getattr(embedder, "deterministic", False):
        return None
    embed_rows = embed_cache_rows()
    result_rows = result_cache_rows()
    depth = collab_depth() if has_encoder else 0
    if embed_rows <= 0 and result_rows <= 0 and depth <= 0:
        return None
    return QueryCacheStack(embedder, label=label)
