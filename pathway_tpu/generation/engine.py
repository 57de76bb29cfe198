"""Continuous-batching paged-KV decode: the generation workload.

The dense path (models/decoder.py) decodes one request batch at a time
over a preallocated contiguous KV cache — no cross-request batching, and
a running batch cannot admit a newcomer or retire a finished row.  This
module is the serving-shaped alternative (ROADMAP item 3):

* :class:`PagedDecoder` — the functional model ops.  Prefill rides
  PR 9's ragged packed attention (``causal=True``) so ONE launch covers
  mixed prompt lengths, writing K/V straight into paged pool blocks;
  each decode step advances ALL live sequences one token in a single
  launch at a pow2 row bucket (compile set flat by construction), with
  the paged-attention gather in ``decode_kernel.py``.
* :class:`DecodeSession` — the continuous-batching table: admit/retire
  per tick, free-list block accounting (token-budget admission →
  :class:`AdmissionRefused`), deadline shedding of queued requests,
  per-token streaming callbacks, and ``extend()`` — a finished-but-
  retained sequence continues from its LIVE KV blocks (the adaptive-RAG
  re-ask path: escalation context rides the decode steps instead of
  re-prefilling the whole prompt).
* Scheduling: each tick is ONE ``GENERATE``-class work item on the
  shared :class:`DeviceTickRuntime` — decode interleaves with
  ``INTERACTIVE`` retrieval at tick granularity on one device, below
  rerank and above bulk ingest.

Numerics contract: prefill/step reuse the dense decoder's ``_ln`` /
``_logits_of`` / masked-softmax formulations verbatim, so greedy decode
is token-for-token identical to the ``lax.scan`` dense-KV oracle
(pinned in tests/test_paged_decode.py, incl. mid-stream admit/retire
and block reuse after free).
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
import weakref
from collections import deque
from typing import Any, Callable, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..internals.config import env_float as _env_float, env_int as _env_int
from ..models.decoder import DecoderConfig, _ln, _logits_of
from ..ops.device_faults import FATAL, TRANSIENT, classify_device_error
from ..testing import faults as _faults
from ..ops.ragged_attention import (
    MAX_PACKED_TOKENS,
    ragged_attention,
    ragged_block,
    ragged_bounds,
)
from .decode_kernel import (
    decode_kernel_mode,
    paged_decode_attention,
    paged_verify_attention,
    resolve_decode_mode,
    validate_decoder_geometry,
)
from .drafting import propose_draft
from .paged_kv import (
    PagedKVPool,
    PrefixIndex,
    decode_prefix_share,
    decode_spec_k,
)

__all__ = [
    "PagedDecoder",
    "DecodeSession",
    "GenerationHandle",
    "generation_status",
]


# ---------------------------------------------------------------------------
# functional model ops (module-level jits: one compile set per process)
# ---------------------------------------------------------------------------

#: packed-prefill token buckets: small sub-blocks so a 1-row admit does
#: not pad to a full 128-token block, then 128-steps (the kernel block)
_PREFILL_TOKEN_BUCKETS: tuple[int, ...] = (32, 64) + tuple(
    range(128, MAX_PACKED_TOKENS + 1, 128)
)
#: dense_s grid for the XLA reference's per-row unpack
_DENSE_BUCKETS: tuple[int, ...] = (32, 64, 128, 256, 512, 1024)

#: max tokens one row consumes per multi-token launch while ingesting a
#: forced tail (extension context / prefix-match remainder): one block's
#: worth keeps the verify launch's K bucket small and the per-tick lock
#: hold bounded
_INGEST_K = 16


def _bucket_of(n: int, grid: Sequence[int]) -> int:
    for b in grid:
        if b >= n:
            return b
    return grid[-1]


def _pow2_bucket(n: int) -> int:
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


def _pick_token(logits, seed, count, temperature):
    """One row's next token — greedy argmax at temperature<=0, else a
    seeded categorical draw keyed on (seq seed, step count) so sampling
    is deterministic regardless of batch composition."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), count)
    sampled = jax.random.categorical(
        key, logits / jnp.maximum(temperature, 1e-6)
    ).astype(jnp.int32)
    return jnp.where(
        temperature <= 0.0, jnp.argmax(logits).astype(jnp.int32), sampled
    )


@jax.jit
def _sample_rows(logits, seeds, counts, temps):
    return jax.vmap(_pick_token)(logits, seeds, counts, temps)


def _paged_prefill_impl(
    params, k_pool, v_pool, ids, pos, seg, starts, bounds, dest_block,
    dest_slot, last_idx, *, cfg: DecoderConfig, num_rows: int, dense_s: int,
    mode: str,
):
    """Packed ragged prefill over admitted prompts: ONE launch for mixed
    lengths, K/V scattered straight into the paged pools (pad tokens
    carry an out-of-range dest block → ``mode="drop"``)."""
    T = ids.shape[0]
    D = cfg.hidden_dim
    H = cfg.num_heads
    Dh = D // H
    x = (
        params["wte"]["embedding"][ids]
        + params["wpe"]["embedding"][jnp.minimum(pos, cfg.max_len - 1)]
    ).astype(cfg.dtype)
    for li in range(cfg.num_layers):
        p = params[f"h_{li}"]
        h = _ln(x, p["ln_1"], cfg.ln_eps).astype(cfg.dtype)
        qkv = h @ p["c_attn"]["kernel"] + p["c_attn"]["bias"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(T, H, Dh)
        k = k.reshape(T, H, Dh)
        v = v.reshape(T, H, Dh)
        k_pool = k_pool.at[li, dest_block, dest_slot].set(
            k.astype(k_pool.dtype), mode="drop"
        )
        v_pool = v_pool.at[li, dest_block, dest_slot].set(
            v.astype(v_pool.dtype), mode="drop"
        )
        ctx = ragged_attention(
            q, k, v, seg,
            pos=pos, starts=starts, bounds=bounds,
            num_rows=num_rows, dense_s=dense_s,
            causal=True, mode=mode,
        )
        x = x + ctx.reshape(T, D) @ p["attn_proj"]["kernel"] + p["attn_proj"]["bias"]
        h2 = _ln(x, p["ln_2"], cfg.ln_eps).astype(cfg.dtype)
        m = jax.nn.gelu(
            h2 @ p["c_fc"]["kernel"] + p["c_fc"]["bias"], approximate=True
        )
        x = x + m @ p["mlp_proj"]["kernel"] + p["mlp_proj"]["bias"]
    x = _ln(x, params["ln_f"], cfg.ln_eps)
    last = x[last_idx]  # [num_rows, D] — each row's final real token
    return k_pool, v_pool, _logits_of(last, params)


def _paged_step_impl(
    params, k_pool, v_pool, bt, lengths, toks, active, seeds, counts, temps,
    *, cfg: DecoderConfig, block_size: int, mode: str,
):
    """One decode tick: every live row consumes its input token (written
    into its current KV block) and emits the next one — a single launch
    at the pow2 row bucket."""
    R = toks.shape[0]
    D = cfg.hidden_dim
    H = cfg.num_heads
    Dh = D // H
    NB = k_pool.shape[1]
    pos = lengths  # the incoming token's write position
    x = (
        params["wte"]["embedding"][toks]
        + params["wpe"]["embedding"][jnp.minimum(pos, cfg.max_len - 1)]
    ).astype(cfg.dtype)
    blk = pos // block_size
    slot = pos % block_size
    bidx = jnp.take_along_axis(bt, blk[:, None], axis=1)[:, 0]
    bidx = jnp.where(active, bidx, NB)  # dead rows: dropped write
    att_len = jnp.where(active, lengths + 1, 0)
    for li in range(cfg.num_layers):
        p = params[f"h_{li}"]
        h = _ln(x, p["ln_1"], cfg.ln_eps).astype(cfg.dtype)
        qkv = h @ p["c_attn"]["kernel"] + p["c_attn"]["bias"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(R, H, Dh)
        k_pool = k_pool.at[li, bidx, slot].set(
            k.reshape(R, H, Dh).astype(k_pool.dtype), mode="drop"
        )
        v_pool = v_pool.at[li, bidx, slot].set(
            v.reshape(R, H, Dh).astype(v_pool.dtype), mode="drop"
        )
        ctx = paged_decode_attention(
            q, k_pool, v_pool, bt, att_len, li,
            block_size=block_size, mode=mode,
        )
        x = x + ctx.reshape(R, D) @ p["attn_proj"]["kernel"] + p["attn_proj"]["bias"]
        h2 = _ln(x, p["ln_2"], cfg.ln_eps).astype(cfg.dtype)
        m = jax.nn.gelu(
            h2 @ p["c_fc"]["kernel"] + p["c_fc"]["bias"], approximate=True
        )
        x = x + m @ p["mlp_proj"]["kernel"] + p["mlp_proj"]["bias"]
    x = _ln(x, params["ln_f"], cfg.ln_eps)
    logits = _logits_of(x, params)  # [R, V]
    toks_next = jax.vmap(_pick_token)(logits, seeds, counts, temps)
    return k_pool, v_pool, toks_next


def _paged_multi_step_impl(
    params, k_pool, v_pool, bt, base, n_new, toks, active, seeds, counts,
    temps, *, cfg: DecoderConfig, block_size: int, mode: str,
):
    """One speculative/ingest tick: each live row consumes up to K new
    tokens (``toks[r, :n_new[r]]``) in a SINGLE launch — drafted tokens
    plus their verification logits, or an extension's forced tail being
    ingested against resident pool KV (which the packed ragged prefill
    cannot attend).  K/V for all K positions land in the row's reserved
    blocks; lanes at or past ``n_new[r]`` (and dead rows) write nowhere.
    Sampling uses per-lane counts ``counts[r] + k`` so the emitted
    stream is exactly the sequential single-step stream — rejected lanes
    are simply never committed by the host (their KV entries sit beyond
    the accepted length, structurally unreachable until overwritten)."""
    R, K = toks.shape
    D = cfg.hidden_dim
    H = cfg.num_heads
    Dh = D // H
    NB = k_pool.shape[1]
    W = bt.shape[1]
    k_iota = jnp.arange(K, dtype=jnp.int32)[None, :]
    pos = base[:, None] + k_iota                      # [R, K] write positions
    x = (
        params["wte"]["embedding"][toks]
        + params["wpe"]["embedding"][jnp.minimum(pos, cfg.max_len - 1)]
    ).astype(cfg.dtype)                               # [R, K, D]
    writing = active[:, None] & (k_iota < n_new[:, None])
    blk = jnp.minimum(pos // block_size, W - 1)
    slot = pos % block_size
    bidx = jnp.take_along_axis(bt, blk, axis=1)       # [R, K]
    bidx = jnp.where(writing, bidx, NB)               # pad lanes: dropped write
    for li in range(cfg.num_layers):
        p = params[f"h_{li}"]
        h = _ln(x, p["ln_1"], cfg.ln_eps).astype(cfg.dtype)
        qkv = h @ p["c_attn"]["kernel"] + p["c_attn"]["bias"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(R, K, H, Dh)
        k_pool = k_pool.at[li, bidx, slot].set(
            k.reshape(R, K, H, Dh).astype(k_pool.dtype), mode="drop"
        )
        v_pool = v_pool.at[li, bidx, slot].set(
            v.reshape(R, K, H, Dh).astype(v_pool.dtype), mode="drop"
        )
        ctx = paged_verify_attention(
            q, k_pool, v_pool, bt,
            jnp.where(active, base, 0), jnp.where(active, n_new, 0), li,
            block_size=block_size, mode=mode,
        )
        x = x + ctx.reshape(R, K, D) @ p["attn_proj"]["kernel"] + p["attn_proj"]["bias"]
        h2 = _ln(x, p["ln_2"], cfg.ln_eps).astype(cfg.dtype)
        m = jax.nn.gelu(
            h2 @ p["c_fc"]["kernel"] + p["c_fc"]["bias"], approximate=True
        )
        x = x + m @ p["mlp_proj"]["kernel"] + p["mlp_proj"]["bias"]
    x = _ln(x, params["ln_f"], cfg.ln_eps)
    logits = _logits_of(x, params)                    # [R, K, V]
    counts_grid = counts[:, None] + k_iota
    seeds_grid = jnp.broadcast_to(seeds[:, None], (R, K))
    temps_grid = jnp.broadcast_to(temps[:, None], (R, K))
    toks_out = jax.vmap(jax.vmap(_pick_token))(
        logits, seeds_grid, counts_grid, temps_grid
    )
    return k_pool, v_pool, toks_out


_JIT_LOCK = threading.Lock()
_PREFILL_JIT: Any = None
_STEP_JIT: Any = None
_MULTI_JIT: Any = None


def _donate() -> tuple[int, ...]:
    # donation is a no-op (with a warning per call) on CPU — only donate
    # where the backend honors it, so a CPU tick does not warn-spam
    return (1, 2) if jax.default_backend() == "tpu" else ()


def _prefill_jit():
    global _PREFILL_JIT
    with _JIT_LOCK:
        if _PREFILL_JIT is None:
            from ..internals.flight_recorder import instrument_jit

            fn = jax.jit(
                _paged_prefill_impl,
                static_argnames=("cfg", "num_rows", "dense_s", "mode"),
                donate_argnums=_donate(),
            )
            _PREFILL_JIT = instrument_jit(fn, "decoder.paged_prefill")
        return _PREFILL_JIT


def _step_jit():
    global _STEP_JIT
    with _JIT_LOCK:
        if _STEP_JIT is None:
            from ..internals.flight_recorder import instrument_jit

            fn = jax.jit(
                _paged_step_impl,
                static_argnames=("cfg", "block_size", "mode"),
                donate_argnums=_donate(),
            )
            _STEP_JIT = instrument_jit(fn, "decoder.paged_step")
        return _STEP_JIT


def _multi_jit():
    global _MULTI_JIT
    with _JIT_LOCK:
        if _MULTI_JIT is None:
            from ..internals.flight_recorder import instrument_jit

            fn = jax.jit(
                _paged_multi_step_impl,
                static_argnames=("cfg", "block_size", "mode"),
                donate_argnums=_donate(),
            )
            _MULTI_JIT = instrument_jit(fn, "decoder.paged_verify_step")
        return _MULTI_JIT


# ---------------------------------------------------------------------------
# process-wide observability (metrics provider + health block)
# ---------------------------------------------------------------------------

_MX = threading.Lock()
_COUNTERS = {
    "tokens_generated_total": 0,
    "prefill_tokens_total": 0,
    "shed_total": 0,
    "retired_total": 0,
    # prefix sharing + speculative decode (ISSUE 16)
    "prefix_hit_blocks_total": 0,
    "prefix_hit_tokens_total": 0,
    "prefix_candidate_blocks_total": 0,
    "cow_copies_total": 0,
    "draft_proposed_total": 0,
    "draft_accepted_total": 0,
    # generation-plane fault containment (ISSUE 18)
    "fault_retries_total": 0,
    "fault_contained_total": 0,
    "fault_replays_total": 0,
    "kv_pool_rebuilds_total": 0,
}
_SESSIONS: "weakref.WeakSet[DecodeSession]" = weakref.WeakSet()


def _kv_pool_hbm_bytes(session: "DecodeSession") -> int:
    """HBM ledger ``bytes_fn`` (module-level: the ledger's weak owner
    ref must stay the only reference to the session)."""
    return int(session.pool.hbm_bytes())


def _bump(name: str, n: int = 1) -> None:
    with _MX:
        _COUNTERS[name] += n


# -- per-launch decode telemetry (ISSUE 19) ---------------------------------
# Timed launch guards feed these: one histogram pair per launch kind
# (prefill / decode_step / verify) — the direct input for the MFU hunt
# (ROADMAP item 3: launch wall time × rows ≈ where the chip time goes).
_LAUNCH_MS_BUCKETS = (
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 1000.0,
)
_LAUNCH_ROW_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0)
_launch_ms: dict[str, Any] = {}
_launch_rows: dict[str, Any] = {}


def _observe_launch(kind: str, duration_ms: float, rows: int) -> None:
    from ..internals.metrics_names import Histogram

    with _MX:
        ms = _launch_ms.get(kind)
        if ms is None:
            ms = _launch_ms[kind] = Histogram(_LAUNCH_MS_BUCKETS)
            _launch_rows[kind] = Histogram(_LAUNCH_ROW_BUCKETS)
        ms.observe(duration_ms)
        _launch_rows[kind].observe(float(rows))


class _RateWindow:
    """Per-second event buckets → rolling tokens/s and draft-acceptance
    series for one DecodeSession (the ``/v1/health`` generation block's
    time series).  NOT internally locked — every caller already holds
    the session lock."""

    __slots__ = ("window_s", "_cells")

    def __init__(self, window_s: int = 60):
        self.window_s = int(window_s)
        #: sec -> [tokens, draft_proposed, draft_accepted]
        self._cells: deque[tuple[int, list[int]]] = deque()

    def _cell(self, now: float) -> list[int]:
        sec = int(now)
        if self._cells and self._cells[-1][0] == sec:
            cell = self._cells[-1][1]
        else:
            cell = [0, 0, 0]
            self._cells.append((sec, cell))
        while self._cells and self._cells[0][0] <= sec - self.window_s:
            self._cells.popleft()
        return cell

    def note_tokens(self, n: int, now: float | None = None) -> None:
        self._cell(time.time() if now is None else now)[0] += n

    def note_draft(
        self, proposed: int, accepted: int, now: float | None = None
    ) -> None:
        cell = self._cell(time.time() if now is None else now)
        cell[1] += proposed
        cell[2] += accepted

    def snapshot(self, now: float | None = None) -> dict[str, Any]:
        now = time.time() if now is None else now
        sec = int(now)
        # the health thread snapshots while the decode pump appends —
        # deque iteration during a mutation raises, so retry the copy
        for _ in range(3):
            try:
                cells = [(s, list(c)) for s, c in self._cells]
                break
            except RuntimeError:
                continue
        else:
            cells = []
        live = [(s, c) for s, c in cells if s > sec - self.window_s]
        tokens = sum(c[0] for _s, c in live)
        proposed = sum(c[1] for _s, c in live)
        accepted = sum(c[2] for _s, c in live)
        span = (
            min(self.window_s, max(1, sec - live[0][0] + 1)) if live else 1
        )
        return {
            "window_s": self.window_s,
            "tokens_per_s": tokens / span,
            "draft_acceptance_rate": accepted / proposed if proposed else 0.0,
            "series": [
                {
                    "t": s,
                    "tokens": c[0],
                    "draft_proposed": c[1],
                    "draft_accepted": c[2],
                }
                for s, c in live
            ],
        }


class _GenerationMetricsProvider:
    """``pathway_decode_*`` series for /status; also the ``generation``
    block on ``/v1/health`` (internals/health.py gates on this module
    being imported, so a bare probe never pulls jax)."""

    def stats(self) -> dict[str, Any]:
        return generation_status()

    def openmetrics_lines(self) -> list[str]:
        s = generation_status()
        with _MX:
            counters = dict(_COUNTERS)
        lines = [
            "# TYPE pathway_decode_live_sequences gauge",
            f"pathway_decode_live_sequences {s.get('live_sequences', 0)}",
            "# TYPE pathway_decode_kv_blocks gauge",
            f'pathway_decode_kv_blocks{{state="used"}} '
            f"{s.get('kv_blocks_used', 0)}",
            f'pathway_decode_kv_blocks{{state="free"}} '
            f"{s.get('kv_blocks_free', 0)}",
            "# TYPE pathway_decode_tokens_total counter",
            f"pathway_decode_tokens_total {counters['tokens_generated_total']}",
            "# TYPE pathway_decode_prefill_tokens_total counter",
            f"pathway_decode_prefill_tokens_total "
            f"{counters['prefill_tokens_total']}",
            "# TYPE pathway_decode_shed_total counter",
            f"pathway_decode_shed_total {counters['shed_total']}",
            "# TYPE pathway_decode_retired_total counter",
            f"pathway_decode_retired_total {counters['retired_total']}",
            "# TYPE pathway_decode_prefix_hit_blocks_total counter",
            f"pathway_decode_prefix_hit_blocks_total "
            f"{counters['prefix_hit_blocks_total']}",
            "# TYPE pathway_decode_shared_blocks gauge",
            f"pathway_decode_shared_blocks {s.get('shared_blocks', 0)}",
            "# TYPE pathway_decode_cow_copies_total counter",
            f"pathway_decode_cow_copies_total {counters['cow_copies_total']}",
            "# TYPE pathway_decode_draft_proposed_total counter",
            f"pathway_decode_draft_proposed_total "
            f"{counters['draft_proposed_total']}",
            "# TYPE pathway_decode_draft_accepted_total counter",
            f"pathway_decode_draft_accepted_total "
            f"{counters['draft_accepted_total']}",
            "# TYPE pathway_decode_fault_retries_total counter",
            f"pathway_decode_fault_retries_total "
            f"{counters['fault_retries_total']}",
            "# TYPE pathway_decode_fault_contained_total counter",
            f"pathway_decode_fault_contained_total "
            f"{counters['fault_contained_total']}",
            "# TYPE pathway_decode_fault_replays_total counter",
            f"pathway_decode_fault_replays_total "
            f"{counters['fault_replays_total']}",
            "# TYPE pathway_kv_pool_rebuilds_total counter",
            f"pathway_kv_pool_rebuilds_total "
            f"{counters['kv_pool_rebuilds_total']}",
        ]
        from ..internals.metrics_names import escape_label_value

        with _MX:
            if _launch_ms:
                lines.append("# TYPE pathway_decode_launch_ms histogram")
                for kind, hist in sorted(_launch_ms.items()):
                    lines.extend(
                        hist.openmetrics_lines(
                            "pathway_decode_launch_ms",
                            f'kind="{escape_label_value(kind)}"',
                        )
                    )
            if _launch_rows:
                lines.append("# TYPE pathway_decode_batch_rows histogram")
                for kind, hist in sorted(_launch_rows.items()):
                    lines.extend(
                        hist.openmetrics_lines(
                            "pathway_decode_batch_rows",
                            f'kind="{escape_label_value(kind)}"',
                        )
                    )
        return lines


#: strong module-level ref — monitoring's provider table is weak-valued
_PROVIDER = _GenerationMetricsProvider()


def generation_status() -> dict[str, Any]:
    """Aggregate snapshot over every live session (health/status)."""
    sessions = list(_SESSIONS)
    with _MX:
        counters = dict(_COUNTERS)
    status: dict[str, Any] = {
        "sessions": len(sessions),
        "kernel_mode": decode_kernel_mode(),
        **counters,
    }
    live = pending = used = free = shared = 0
    block_size = None
    recovering = False
    breakers: dict[str, str] = {}
    throughput: dict[str, Any] = {}
    for s in sessions:
        st = s.stats()
        live += st["live_sequences"]
        pending += st["pending"]
        used += st["kv_blocks_used"]
        free += st["kv_blocks_free"]
        shared += st["shared_blocks"]
        block_size = st["block_size"]
        recovering = recovering or bool(st.get("recovering"))
        if st.get("breaker") is not None:
            breakers[s.name] = st["breaker"]
        if st.get("rates") is not None:
            throughput[s.name] = st["rates"]
    if throughput:
        # rolling per-session tokens/s + draft-acceptance time series —
        # the /v1/health generation block's MFU-hunt input (ROADMAP 3)
        status["throughput"] = throughput
    # the faults sub-block rides the health "generation" block so the
    # fleet router's health poller sees a replica mid-recovery (and an
    # open generation breaker) without a dedicated probe
    status["faults"] = {
        "retries_total": counters["fault_retries_total"],
        "contained_total": counters["fault_contained_total"],
        "replays_total": counters["fault_replays_total"],
        "kv_pool_rebuilds_total": counters["kv_pool_rebuilds_total"],
        "recovering": recovering,
        "breakers": breakers,
    }
    status.update(
        live_sequences=live,
        pending=pending,
        kv_blocks_used=used,
        kv_blocks_free=free,
        shared_blocks=shared,
    )
    cand = counters["prefix_candidate_blocks_total"]
    status["prefix_hit_rate"] = (
        counters["prefix_hit_blocks_total"] / cand if cand else 0.0
    )
    prop = counters["draft_proposed_total"]
    status["draft_acceptance_rate"] = (
        counters["draft_accepted_total"] / prop if prop else 0.0
    )
    if block_size is not None:
        status["block_size"] = block_size
    return status


# ---------------------------------------------------------------------------
# continuous-batching session
# ---------------------------------------------------------------------------


class _Seq:
    __slots__ = (
        "ids", "max_new", "eos_id", "temperature", "seed", "blocks",
        "length", "next_input", "generated", "count", "handle",
        "deadline_at", "retain", "forced", "submitted_at",
        "all_tokens", "chain", "registered_upto", "cow_spare",
        "replayed", "trace_link",
    )

    def __init__(self, ids, max_new, eos_id, temperature, seed,
                 deadline_at, retain, trace_link=None):
        self.ids = list(ids)
        self.max_new = int(max_new)
        self.eos_id = eos_id
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.blocks: list[int] = []
        self.length = 0          # tokens resident in KV
        self.next_input = None   # last sampled (or forced) token, not yet consumed
        self.generated: list[int] = []
        self.count = 0           # sampling counter (rng fold key)
        self.handle: GenerationHandle | None = None
        self.deadline_at = deadline_at
        self.retain = bool(retain)
        self.forced: deque[int] = deque()
        self.submitted_at = time.monotonic()
        #: full known token stream; ``all_tokens[:length]`` is exactly
        #: the KV-resident tokens (drafting context + prefix registration)
        self.all_tokens: list[int] = list(ids)
        self.chain = 0           # prefix-index chain key after registered blocks
        self.registered_upto = 0  # full blocks content-registered so far
        #: pre-reserved COW destination for a partially-shared tail block
        self.cow_spare: int | None = None
        #: times this sequence was resurrected by replay re-prefill
        #: after a fatal pool quarantine
        self.replayed = 0
        #: (trace_id, parent_span_id) of the request that submitted this
        #: sequence — the launch spans it rides link back to it
        self.trace_link = trace_link


class GenerationHandle:
    """Client-facing handle: blocking result, or per-token streaming."""

    _DONE = object()

    def __init__(self, session: "DecodeSession"):
        self._session = session
        self._q: "queue.Queue[Any]" = queue.Queue()
        self._done = threading.Event()
        self._tokens: list[int] = []
        self.error: BaseException | None = None

    def _on_token(self, tok: int) -> None:
        self._tokens.append(tok)
        self._q.put(tok)

    def _finish(self, error: BaseException | None = None) -> None:
        self.error = error
        self._done.set()
        self._q.put(self._DONE)

    @property
    def done(self) -> bool:
        return self._done.is_set()

    @property
    def tokens(self) -> list[int]:
        return list(self._tokens)

    def stream(self) -> Iterator[int]:
        """Yield generated token ids as they land (ends when the
        sequence retires; raises the sequence's error, if any)."""
        while True:
            item = self._q.get()
            if item is self._DONE:
                break
            yield item
        if self.error is not None:
            raise self.error

    def result(self, timeout: float | None = 30.0) -> list[int]:
        if not self._done.wait(timeout):
            raise TimeoutError("generation did not finish in time")
        if self.error is not None:
            raise self.error
        return list(self._tokens)


def iter_text_pieces(
    handle: GenerationHandle,
    decode_tokens: Callable[[list[int]], str],
    eos_id: int | None,
) -> Iterator[str]:
    """Incrementally detokenize a handle's token stream: yields the text
    DELTA each token adds (re-decoding the whole prefix every step, so
    multi-token graphemes resolve correctly); ``eos_id`` terminates the
    stream and never contributes text.  The full decoded text is exactly
    the concatenation of the yielded pieces — one implementation shared
    by every streaming surface (``CausalLM.generate_stream`` and both QA
    ``_stream_rounds``)."""
    toks: list[int] = []
    emitted = ""
    for tok in handle.stream():
        if eos_id is not None and tok == eos_id:
            break
        toks.append(tok)
        full = decode_tokens(toks)
        piece, emitted = full[len(emitted):], full
        if piece:
            yield piece


class DecodeSession:
    """Continuous-batching table over one :class:`PagedKVPool`.

    ``auto=True`` (default) runs a pump thread that drives one tick per
    loop through the shared :class:`DeviceTickRuntime` as a
    ``GENERATE``-class item.
    ``auto=False`` is the test/bench mode: the caller steps with
    :meth:`tick` / :meth:`drain`.
    """

    def __init__(
        self,
        cfg: DecoderConfig,
        params: Any,
        *,
        tokenizer: Any = None,
        block_size: int | None = None,
        pool_tokens: int | None = None,
        mode: str | None = None,
        max_live: int | None = None,
        max_pending: int | None = None,
        auto: bool = True,
        name: str = "decode",
        spec_k: int | None = None,
        prefix_share: bool | None = None,
    ):
        self.cfg = cfg
        self.params = params
        self.tokenizer = tokenizer
        self.mode = resolve_decode_mode(mode)
        self.spec_k = decode_spec_k() if spec_k is None else max(0, int(spec_k))
        self.prefix_share = (
            decode_prefix_share() if prefix_share is None else bool(prefix_share)
        )
        head_dim = cfg.hidden_dim // cfg.num_heads
        if self.mode == "pallas":
            validate_decoder_geometry(
                head_dim, knob="PATHWAY_DECODE_KERNEL=pallas (paged decode)"
            )
        self.pool = PagedKVPool(
            cfg, block_size=block_size, pool_tokens=pool_tokens
        )
        self.max_live = (
            _env_int("PATHWAY_DECODE_MAX_LIVE", 64)
            if max_live is None else int(max_live)
        )
        self.max_pending = (
            _env_int("PATHWAY_DECODE_PENDING", 256)
            if max_pending is None else int(max_pending)
        )
        self.name = name
        self._auto = bool(auto)
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._pending: deque[_Seq] = deque()
        self._live: list[_Seq] = []
        self._retained: dict[int, _Seq] = {}
        self._closed = False
        self._pump: threading.Thread | None = None
        self._group = None
        self.ticks_total = 0
        #: rolling tokens/s + draft-acceptance window (mutated under
        #: self._lock; snapshotted by stats())
        self._rates = _RateWindow()
        #: per-launch transient retry budget (PR 6 containment contract
        #: extended to the generation plane)
        self.fault_retries = _env_int("PATHWAY_DECODE_FAULT_RETRIES", 1, lo=0)
        self._recovering = False
        # generation breaker: contained launch failures trip it; while
        # OPEN, submit() sheds NEW admissions (503 + Retry-After through
        # the HTTP planes) but live rows keep decoding
        from ..xpacks.llm._breaker import CircuitBreaker

        self.breaker = CircuitBreaker(
            f"generation:{name}",
            failure_threshold=_env_int(
                "PATHWAY_GENERATION_BREAKER_FAILURES", 3, lo=1
            ),
            cooldown_s=_env_float(
                "PATHWAY_GENERATION_BREAKER_COOLDOWN_S", 5.0, lo=0.0
            ),
        )
        from ..internals.monitoring import register_metrics_provider
        from ..observability.hbm_ledger import get_ledger

        _SESSIONS.add(self)
        register_metrics_provider("generation", _PROVIDER, replace=False)
        # unified HBM ledger: the paged K/V block pools are the largest
        # single generation allocation and must show up next to the
        # index tiers (register_unique: same-named "decode" sessions
        # must not collide)
        get_ledger().register_unique(
            f"kv_pool:{self.name}", self, _kv_pool_hbm_bytes
        )

    # -- submission ------------------------------------------------------
    def submit(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int = 32,
        *,
        temperature: float = 0.0,
        seed: int = 0,
        eos_id: int | None = None,
        deadline_s: float | None = None,
        stream_cb: Callable[[int], None] | None = None,
        retain: bool = False,
        trace_link: tuple[str, str] | None = None,
    ) -> GenerationHandle:
        """Queue one sequence; admission happens at the next tick once
        the free list covers its worst case.  Raises
        :class:`AdmissionRefused` immediately when the request can NEVER
        fit the pool, or when the pending queue is at its depth target
        (backpressure, not collapse — HTTP planes map it to
        503 + Retry-After)."""
        from ..runtime import AdmissionRefused

        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.breaker is not None and self.breaker.state == "open":
            # decode launches are failing: shed NEW admissions while the
            # breaker cools down — live rows keep decoding, and the next
            # successful launch closes it.  (state == "open" on purpose,
            # not allow(): admissions must not consume the half-open
            # probe slot — the launches themselves are the probe.)
            _bump("shed_total")
            raise AdmissionRefused(
                f"generation breaker open for session {self.name!r}: "
                "decode launches are failing; new admissions shed",
                retry_after_s=max(0.1, self.breaker.cooldown_s),
            )
        if int(max_new_tokens) > self.cfg.max_len:
            # past max_len the per-sequence block table (blocks_per_seq =
            # ceil(max_len/block_size) entries) can NEVER hold the
            # sequence — admitted, it would overflow the decode tick's
            # block-table row and _fail_all every in-flight sequence
            raise AdmissionRefused(
                f"max_new_tokens={max_new_tokens} exceeds the model's "
                f"max_len={self.cfg.max_len}; lower max_new_tokens",
                retry_after_s=0.0,
            )
        if eos_id is None and self.tokenizer is not None:
            eos_id = getattr(self.tokenizer, "eos_token_id", None)
            if eos_id is None:
                # HF wrapper nests the real tokenizer at .tok (the same
                # two-level lookup CausalLM.eos_id performs)
                eos_id = getattr(
                    getattr(self.tokenizer, "tok", None),
                    "eos_token_id", None,
                )
        # over-long prompts keep their TAIL, like the dense path
        cap = max(1, self.cfg.max_len - int(max_new_tokens))
        prompt_ids = list(prompt_ids)[-cap:]
        if not prompt_ids:
            raise ValueError("empty prompt")
        if len(prompt_ids) > MAX_PACKED_TOKENS:
            # a prompt the packed prefill cannot hold must be refused
            # HERE — admitted, it would blow up inside tick() and
            # _fail_all every in-flight sequence with it
            raise AdmissionRefused(
                f"prompt of {len(prompt_ids)} tokens exceeds the packed "
                f"prefill launch cap ({MAX_PACKED_TOKENS}); use the dense "
                "decoder (CausalLM.generate_ids) for this geometry",
                retry_after_s=0.0,
            )
        need = self.pool.blocks_for(len(prompt_ids) + max_new_tokens - 1)
        if need > self.pool.num_blocks:
            raise AdmissionRefused(
                f"request needs {need} KV blocks but the pool holds "
                f"{self.pool.num_blocks} (PATHWAY_DECODE_POOL_TOKENS)",
                retry_after_s=0.0,
            )
        seq = _Seq(
            prompt_ids, max_new_tokens, eos_id, temperature, seed,
            None if deadline_s is None
            else time.monotonic() + float(deadline_s),
            retain,
            trace_link,
        )
        seq.chain = PrefixIndex.root_key(self.params)
        handle = GenerationHandle(self)
        if stream_cb is not None:
            orig = handle._on_token

            def _tee(tok: int, _orig=orig, _cb=stream_cb) -> None:
                _orig(tok)
                _cb(tok)

            handle._on_token = _tee  # type: ignore[method-assign]
        seq.handle = handle
        with self._lock:
            if self._closed:
                raise RuntimeError("DecodeSession is closed")
            if len(self._pending) >= self.max_pending:
                _bump("shed_total")
                raise AdmissionRefused(
                    f"decode pending queue full ({self.max_pending})",
                    retry_after_s=1.0,
                )
            self._pending.append(seq)
            if self._auto:
                self._ensure_pump_locked()
            self._work.notify_all()
        return handle

    def extend(
        self,
        handle: GenerationHandle,
        extra_ids: Sequence[int],
        max_new_tokens: int = 32,
    ) -> GenerationHandle:
        """Continue a RETAINED finished sequence from its live KV blocks:
        the extra tokens (an adaptive-RAG escalation, a follow-up turn)
        ride the decode steps — the original prompt is never
        re-prefilled.  Returns a fresh handle for the continuation."""
        from ..runtime import AdmissionRefused

        extra_ids = list(extra_ids)
        with self._lock:
            seq = self._retained.pop(id(handle), None)
            if seq is None:
                raise ValueError(
                    "extend() needs a finished handle submitted with "
                    "retain=True (and not yet released)"
                )
            total = seq.length + 1 + len(extra_ids) + max_new_tokens - 1
            if total > self.cfg.max_len:
                self._retained[id(handle)] = seq
                raise ValueError(
                    f"extension would exceed max_len={self.cfg.max_len}"
                )
            need = self.pool.blocks_for(total) - len(seq.blocks)
            if need > 0:
                more = None
                with self._record_span(
                    "kv:alloc", {"blocks": need}, seqs=(seq,)
                ) as timed:
                    try:
                        if _faults.enabled:
                            _faults.perturb("kv.alloc")
                        more = self.pool.allocator.alloc(need)
                    except _faults.FaultInjected:
                        # injected alloc fault (any severity): refuse the
                        # extension — the retained sequence stays parked
                        # and extendable, nothing was allocated
                        more = None
                    timed.set(ok=more is not None)
                if more is None:
                    self._retained[id(handle)] = seq
                    raise AdmissionRefused(
                        f"KV pool cannot grow the sequence by {need} blocks",
                        retry_after_s=1.0,
                    )
                seq.blocks.extend(more)
            new_handle = GenerationHandle(self)
            seq.handle = new_handle
            seq.max_new = int(max_new_tokens)
            seq.generated = []
            seq.forced = deque(extra_ids)
            seq.all_tokens.extend(extra_ids)
            seq.count += 1  # fresh sampling stream for the continuation
            self._live.append(seq)
            self._work.notify_all()
        return new_handle

    def _free_seq_blocks_locked(self, seq: _Seq) -> None:
        """Drop every block reference a sequence holds — its table AND
        its reserved COW spare (refcount decrement; shared blocks stay
        resident for their remaining readers)."""
        if seq.blocks:
            self.pool.allocator.free(seq.blocks)
            seq.blocks = []
        if seq.cow_spare is not None:
            self.pool.allocator.free([seq.cow_spare])
            seq.cow_spare = None

    def release(self, handle: GenerationHandle) -> None:
        """Free a retained sequence's blocks."""
        with self._lock:
            seq = self._retained.pop(id(handle), None)
            if seq is not None:
                self._free_seq_blocks_locked(seq)
            self._work.notify_all()  # freed blocks may unblock admission

    def cancel(self, handle: GenerationHandle) -> None:
        """Stop and forget a sequence in ANY state (queued, live,
        retained or finished) and free its blocks — the abandoned-stream
        path: a client that disconnects mid-round must not park a
        retain=True sequence in the retained table forever."""
        with self._lock:
            seq = self._retained.pop(id(handle), None)
            if seq is None:
                for s in self._live:
                    if s.handle is handle:
                        seq = s
                        self._live.remove(s)
                        break
            if seq is None:
                for s in self._pending:
                    if s.handle is handle:
                        seq = s
                        self._pending.remove(s)
                        break
            if seq is None:
                return
            seq.retain = False
            self._free_seq_blocks_locked(seq)
            if seq.handle is not None and not seq.handle.done:
                seq.handle._finish()
            self._work.notify_all()

    # -- tick engine -----------------------------------------------------
    @contextlib.contextmanager
    def _record_span(
        self,
        name: str,
        attrs: dict,
        seqs: "Sequence[_Seq]" = (),
        launch_kind: str | None = None,
    ):
        """Time the block as a ``generate`` span (ring, and the profiler's
        host plane while a session is open); a launch that ends well also
        feeds ``pathway_decode_launch_ms{kind=}``.  A contained failure
        marks itself with ``timed.set(ok=False)``."""
        from ..internals.flight_recorder import span

        # sequences carry the (trace_id, span_id) of the request that
        # submitted them: a launch serving traced sequences is recorded
        # once per distinct triggering trace so the stitched fleet tree
        # reaches all the way down to the device launches
        links: list[tuple[str, str]] = []
        for seq in seqs:
            if seq.trace_link is not None and seq.trace_link not in links:
                links.append(seq.trace_link)
        with span(name, "generate", links=links or None, **attrs) as timed:
            yield timed
        if launch_kind is not None and timed.attrs.get("ok", True):
            _observe_launch(
                launch_kind, timed.duration_ms, int(attrs.get("rows", 1))
            )

    def _has_work_locked(self) -> bool:
        return bool(self._pending) or bool(self._live)

    def tick(self) -> bool:
        """One tick: shed expired, admit+prefill what fits, advance every
        live row one token.  Returns whether anything progressed."""
        with self._lock:
            return self._tick_locked()

    def _tick_locked(self) -> bool:
        self.ticks_total += 1
        try:
            progressed = self._admit_and_prefill_locked()
            if self._live:
                progressed = self._decode_step_locked() or progressed
        except BaseException as exc:
            if classify_device_error(exc) == FATAL and not self._recovering:
                # the device arrays are suspect: quarantine the pool and
                # resurrect every live/retained sequence by replay
                # re-prefill from its recorded tokens — the session
                # survives, streams resume token-for-token
                self._recover_locked(exc)
                return True
            raise  # host-side bug: the pump's _fail_all keeps its role
        return progressed

    def _admit_and_prefill_locked(self) -> bool:
        from ..runtime import DeadlineExceeded

        now = time.monotonic()
        # deadline shedding: queued work whose budget passed never runs
        kept: deque[_Seq] = deque()
        for seq in self._pending:
            if seq.deadline_at is not None and now > seq.deadline_at:
                _bump("shed_total")
                seq.handle._finish(
                    DeadlineExceeded(
                        "decode request shed: deadline passed while queued",
                        retry_after_s=1.0,
                    )
                )
            else:
                kept.append(seq)
        self._pending = kept
        admitted: list[_Seq] = []
        matched_any = False
        while self._pending and len(self._live) + len(admitted) < self.max_live:
            seq = self._pending[0]
            need = self.pool.blocks_for(len(seq.ids) + seq.max_new - 1)
            alloc = self.pool.allocator
            full: list[int] = []
            chain = seq.chain
            partial: tuple[int, int] | None = None
            if self.prefix_share:
                full, chain, partial = self.pool.prefix.match(
                    self.params, seq.ids
                )
                _bump(
                    "prefix_candidate_blocks_total",
                    self.pool.blocks_for(len(seq.ids) - 1)
                    if len(seq.ids) > 1 else 0,
                )
            # pin the matched blocks FIRST: acquire pulls lingering
            # (refcount-0, still content-addressed) blocks out of the
            # free list before alloc could hand them to this very
            # sequence as fresh blocks and evict their registrations
            for b in full:
                alloc.acquire(b)
            if partial is not None:
                alloc.acquire(partial[0])
            # worst-case reservation discounts fully-matched blocks; a
            # partial match still reserves its block slot PLUS one COW
            # spare (net: no discount) so the first divergent write can
            # always copy without allocating under pressure
            fresh_need = need - len(full)
            fresh = None
            fatal_exc: BaseException | None = None
            with self._record_span(
                "kv:alloc", {"blocks": fresh_need, "matched": len(full)},
                seqs=(seq,),
            ) as timed:
                try:
                    if _faults.enabled:
                        _faults.perturb("kv.alloc")
                    fresh = alloc.alloc(fresh_need)
                except _faults.FaultInjected as exc:
                    # transient alloc fault: the request simply stays
                    # queued for the next tick; a fatal one escalates to
                    # recovery
                    if classify_device_error(exc) == FATAL:
                        fatal_exc = exc
                timed.set(ok=fresh is not None)
            if fresh is None:
                # roll the shares back; pool full — stays queued until
                # retirements free blocks
                rollback = list(full) + (
                    [partial[0]] if partial is not None else []
                )
                if rollback:
                    alloc.free(rollback)
                if fatal_exc is not None:
                    raise fatal_exc
                break
            self._pending.popleft()
            if not full and partial is None:
                seq.blocks = fresh
                admitted.append(seq)
                continue
            # prefix hit: adopt the resident blocks and skip their
            # prefill entirely — the unmatched tail rides the decode
            # ticks as forced input (the multi-token verify launch can
            # attend resident pool KV; the packed ragged prefill cannot)
            bs = self.pool.block_size
            matched_len = len(full) * bs + (partial[1] if partial else 0)
            hit_blocks = len(full) + (1 if partial is not None else 0)
            with self._record_span(
                "kv:prefix_match",
                {"blocks": hit_blocks, "tokens": matched_len,
                 "partial": partial is not None},
                seqs=(seq,),
            ):
                if partial is not None:
                    seq.blocks = full + [partial[0]] + fresh[1:]
                    seq.cow_spare = fresh[0]
                else:
                    seq.blocks = full + fresh
                seq.length = matched_len
                seq.chain = chain
                seq.registered_upto = len(full)
                tail = seq.ids[matched_len:]
                seq.next_input = tail[0]
                seq.forced = deque(tail[1:])
                seq.count = 0
                _bump("prefix_hit_blocks_total", hit_blocks)
                _bump("prefix_hit_tokens_total", matched_len)
            self._live.append(seq)
            matched_any = True
        if not admitted:
            return matched_any
        # pack admitted prompts into bounded ragged launches; a failed
        # launch is contained to ITS batch — remaining batches (and the
        # live set) carry on
        start = 0
        while start < len(admitted):
            batch: list[_Seq] = []
            total = 0
            while start < len(admitted):
                ln = len(admitted[start].ids)
                if batch and total + ln > MAX_PACKED_TOKENS:
                    break
                batch.append(admitted[start])
                total += ln
                start += 1
            try:
                self._prefill_batch_locked(batch)
            except BaseException as exc:
                if classify_device_error(exc) == FATAL:
                    # the pool is suspect: nothing this batch wrote can
                    # be trusted.  Requeue the whole un-prefilled
                    # remainder at the queue head (their old-pool block
                    # refs are void wholesale once the pool is
                    # quarantined) and let the tick-level handler
                    # rebuild + replay.
                    for seq in reversed(batch + admitted[start:]):
                        if seq.handle is not None and seq.handle.done:
                            continue
                        if any(s is seq for s in self._live):
                            continue
                        seq.blocks = []
                        seq.cow_spare = None
                        seq.length = 0
                        self._pending.appendleft(seq)
                    raise
                # per-launch blast radius: only this packed launch's
                # sequences fail — free + finish them (they are in
                # neither _live nor _pending, so nothing else covers
                # them) and move on to the next batch
                self._contain_launch_failure_locked(batch, exc, "prefill")
        return True

    # -- prefix-index registration ---------------------------------------
    def _register_progress_locked(self, seq: _Seq) -> None:
        """Content-register every block newly covered by the ACCEPTED
        length (never blocks holding rejected draft KV) so later prompts
        can adopt it."""
        if not self.prefix_share:
            return
        bs = self.pool.block_size
        while (seq.registered_upto + 1) * bs <= seq.length:
            u = seq.registered_upto
            seq.chain = self.pool.prefix.register_full(
                seq.chain, seq.all_tokens[u * bs:(u + 1) * bs], seq.blocks[u]
            )
            seq.registered_upto += 1

    def _register_partial_locked(self, seq: _Seq) -> None:
        """Register the partial tail block (prompt tail at prefill,
        accepted tail at retirement) — entries below the write cursor
        stay valid even as the owner keeps appending."""
        if not self.prefix_share:
            return
        bs = self.pool.block_size
        u = seq.registered_upto
        tail = seq.all_tokens[u * bs:seq.length]
        if tail and u < len(seq.blocks):
            self.pool.prefix.register_partial(seq.chain, tail, seq.blocks[u])

    # -- fault containment (ISSUE 18) ------------------------------------
    def _launch_guarded_locked(self, site: str, fn: Callable[[], Any]) -> Any:
        """Run one device launch under the containment contract: the
        chaos site perturbs first, and a TRANSIENT classification retries
        the launch up to ``PATHWAY_DECODE_FAULT_RETRIES`` times (safe: a
        failed dispatch leaves the pools untouched — donation is
        TPU-only, and a donated-buffer loss classifies FATAL).  On
        exhaustion the error propagates for the caller to contain to
        this launch's sequences; a clean launch records breaker
        success."""
        attempt = 0
        while True:
            try:
                if _faults.enabled:
                    _faults.perturb(site)
                out = fn()
            except BaseException as exc:
                if (
                    classify_device_error(exc) == TRANSIENT
                    and attempt < self.fault_retries
                ):
                    attempt += 1
                    _bump("fault_retries_total")
                    continue
                raise
            if self.breaker is not None:
                self.breaker.record_success()
            return out

    def _contain_launch_failure_locked(
        self, seqs: list[_Seq], exc: BaseException, what: str
    ) -> None:
        """Blast-radius isolation: fail ONLY the given launch's
        sequences (free blocks, finish handles with the error), charge
        the generation breaker, and keep the session serving."""
        _bump("fault_contained_total")
        failed = 0
        for seq in seqs:
            if seq in self._live:
                self._live.remove(seq)
            if seq.handle is not None and seq.handle.done:
                # a parked retained sequence can no longer be resumed —
                # unpark it (its blocks go back) rather than keep a
                # stale table; an already-retired row is left alone
                if self._retained.pop(id(seq.handle), None) is not None:
                    self._free_seq_blocks_locked(seq)
                continue
            self._free_seq_blocks_locked(seq)
            if seq.handle is not None:
                seq.handle._finish(exc)
            failed += 1
        if self.breaker is not None:
            self.breaker.record_failure(exc)
        from ..internals.errors import register_error

        register_error(
            f"decode {what} launch contained: {type(exc).__name__}: {exc} "
            f"({failed} sequence(s) failed; session keeps serving)",
            kind="serving",
            operator=self.name,
        )

    def recover(self, exc: BaseException | None = None) -> int:
        """Quarantine the paged-KV pool and resurrect every live and
        retained sequence by replay re-prefill from its recorded token
        ids (prompt + accepted tokens).  The tick loop calls this
        automatically on a FATAL classification; it is public for
        operators and tests.  Returns the number of sequences
        replayed."""
        with self._lock:
            return self._recover_locked(
                exc if exc is not None
                else RuntimeError("manual DecodeSession.recover()")
            )

    def _recover_locked(self, exc: BaseException) -> int:
        self._recovering = True
        try:
            with self._record_span("kv:rebuild", {}) as timed:
                return self._rebuild_pool_locked(exc, timed)
        finally:
            self._recovering = False

    def _rebuild_pool_locked(self, exc: BaseException, timed: Any) -> int:
        from ..internals.errors import register_error

        old = self.pool
        # quarantine: never touch the suspect arrays again — a fresh
        # pool (arrays + allocator + prefix index) replaces them
        # atomically, and the HBM ledger's bytes_fn reads self.pool
        # through the session so the ledger follows the swap
        self.pool = PagedKVPool(
            self.cfg,
            block_size=old.block_size,
            pool_tokens=old.num_blocks * old.block_size,
        )
        old.quarantine()
        _bump("kv_pool_rebuilds_total")
        victims = list(self._live) + list(self._retained.values())
        self._live = []
        replayed = 0
        # one victim at a time, ON PURPOSE: each replay prefill
        # content-registers its blocks before the next victim's
        # prefix match runs, so identical prefixes (the shared RAG
        # template case) re-prefill once and are adopted by every
        # later victim — the PrefixIndex makes replay cheap
        for seq in victims:
            # old-pool block refs are void wholesale (the allocator
            # was quarantined with the arrays)
            seq.blocks = []
            seq.cow_spare = None
            plan = self._resurrect_locked(seq, exc)
            if plan is None:
                continue
            replayed += 1
            tag, head = plan
            if tag == "prefill":
                try:
                    self._prefill_batch_locked(
                        [seq], tokens=[head], replay=True
                    )
                except BaseException as exc2:  # noqa: BLE001
                    # a replay prefill failing (even fatally) is
                    # contained to its sequence — recovery NEVER
                    # recurses into another recovery
                    self._contain_launch_failure_locked(
                        [seq], exc2, "replay_prefill"
                    )
            elif seq.handle is not None and not seq.handle.done:
                self._live.append(seq)
        register_error(
            f"decode pool quarantined after fatal device error "
            f"({type(exc).__name__}: {exc}); rebuilt fresh and "
            f"replayed {replayed} sequence(s)",
            kind="serving",
            operator=self.name,
        )
        timed.set(replayed=replayed, pending=len(self._pending))
        # queued admissions were never lost — wake the pump so they
        # drain against the fresh pool
        self._work.notify_all()
        return replayed

    def _resurrect_locked(
        self, seq: _Seq, exc: BaseException
    ) -> tuple[str, list[int]] | None:
        """Re-seat one sequence in the fresh pool and restore its stream
        state so decode resumes token-for-token.  Returns
        ``("prefill", head)`` when a replay prefill launch is still
        needed, ``("live", [])`` when a prefix match covered the replay
        (the remainder rides forced ingestion), or ``None`` when the
        sequence could not be resurrected (requeued or failed)."""
        resident = seq.length
        if resident <= 0:
            # nothing device-resident yet: back to the queue head for a
            # fresh admission
            self._pending.appendleft(seq)
            return None
        replay = seq.all_tokens[:resident]
        # worst-case reservation mirrors admission: cover the resident
        # replay plus every token the stream may still consume (equal to
        # the sequence's original reservation, so it always fits)
        rest = 1 + len(seq.forced) + max(0, seq.max_new - len(seq.generated))
        need = self.pool.blocks_for(
            min(resident + rest - 1, self.cfg.max_len)
        )
        alloc = self.pool.allocator
        full: list[int] = []
        chain = PrefixIndex.root_key(self.params)
        partial: tuple[int, int] | None = None
        if self.prefix_share:
            full, chain, partial = self.pool.prefix.match(self.params, replay)
        for b in full:
            alloc.acquire(b)
        if partial is not None:
            alloc.acquire(partial[0])
        fresh = alloc.alloc(need - len(full))
        if fresh is None:
            rollback = list(full) + (
                [partial[0]] if partial is not None else []
            )
            if rollback:
                alloc.free(rollback)
            self._retained.pop(id(seq.handle), None)
            if seq.handle is not None and not seq.handle.done:
                seq.handle._finish(exc)
            return None
        bs = self.pool.block_size
        matched_len = len(full) * bs + (partial[1] if partial else 0)
        if partial is not None:
            seq.blocks = full + [partial[0]] + fresh[1:]
            seq.cow_spare = fresh[0]
        else:
            seq.blocks = full + fresh
        if matched_len:
            _bump(
                "prefix_hit_blocks_total",
                len(full) + (1 if partial is not None else 0),
            )
            _bump("prefix_hit_tokens_total", matched_len)
        seq.chain = chain
        seq.registered_upto = len(full)
        seq.replayed += 1
        _bump("fault_replays_total")
        # restore the stream state so decode resumes EXACTLY where it
        # left off: the not-yet-consumed input chain (next_input +
        # forced) is prepended with whatever part of the replay is not
        # covered by prefill/prefix blocks, and the sampling counter is
        # rewound so it returns to its fault-time value exactly when the
        # length does (every replay lane's sampled output is discarded
        # by _consume_token_locked while forced input remains, so the
        # interim counter values never reach a committed token)
        pend = [seq.next_input] + list(seq.forced)
        if matched_len == 0:
            head = replay[:MAX_PACKED_TOKENS]
            seq.length = 0
            seq.forced = deque(replay[len(head):] + pend)
            seq.count -= resident - len(head)
            return ("prefill", head)
        seq.length = matched_len
        tail = replay[matched_len:] + pend
        seq.next_input = tail[0]
        seq.forced = deque(tail[1:])
        seq.count -= resident - matched_len
        return ("live", [])

    def _prefill_batch_locked(
        self,
        batch: list[_Seq],
        tokens: list[list[int]] | None = None,
        replay: bool = False,
    ) -> None:
        """Packed prefill of one batch.  ``tokens`` overrides the rows'
        token lists (replay re-prefill feeds the recorded stream head,
        not ``seq.ids``); ``replay=True`` keeps each row's restored
        sampling counter instead of resetting it — the launch's sampled
        tokens are discarded either way (the true continuation sits in
        ``seq.forced``)."""
        bs = self.pool.block_size
        NB = self.pool.num_blocks
        row_tokens = tokens if tokens is not None else [s.ids for s in batch]
        lens = [len(t) for t in row_tokens]
        t_real = sum(lens)
        T = _bucket_of(t_real, _PREFILL_TOKEN_BUCKETS)
        R = _pow2_bucket(len(batch))
        dense_s = _bucket_of(max(lens), _DENSE_BUCKETS)
        if dense_s < max(lens):
            # reference-mode unpack must hold the longest row: past the
            # grid, fall back to the next pow2 (never clip silently)
            dense_s = 1 << (max(lens) - 1).bit_length()
        ids = np.zeros(T, np.int32)
        pos = np.zeros(T, np.int32)
        seg = np.full(T, R, np.int32)
        dest_block = np.full(T, NB, np.int32)  # pads: dropped write
        dest_slot = np.zeros(T, np.int32)
        starts = np.zeros(R, np.int32)
        last_idx = np.zeros(R, np.int32)
        cu = np.zeros(len(batch) + 1, np.int64)
        off = 0
        for j, seq in enumerate(batch):
            ln = lens[j]
            ids[off : off + ln] = row_tokens[j]
            p = np.arange(ln, dtype=np.int32)
            pos[off : off + ln] = p
            seg[off : off + ln] = j
            blocks = np.asarray(seq.blocks, np.int32)
            dest_block[off : off + ln] = blocks[p // bs]
            dest_slot[off : off + ln] = p % bs
            starts[j] = off
            last_idx[j] = off + ln - 1
            off += ln
            cu[j + 1] = off
        bounds = ragged_bounds(cu, T, ragged_block(T))
        with self._record_span(
            "prefill",
            {"rows": len(batch), "tokens": t_real, "bucket": T},
            seqs=batch, launch_kind="prefill",
        ):
            k_pool, v_pool, logits = self._launch_guarded_locked(
                "device.prefill",
                lambda: _prefill_jit()(
                    self.params, self.pool.k_pool, self.pool.v_pool,
                    jnp.asarray(ids), jnp.asarray(pos), jnp.asarray(seg),
                    jnp.asarray(starts), jnp.asarray(bounds),
                    jnp.asarray(dest_block), jnp.asarray(dest_slot),
                    jnp.asarray(last_idx),
                    cfg=self.cfg, num_rows=R, dense_s=dense_s,
                    mode=self.mode,
                ),
            )
            self.pool.k_pool, self.pool.v_pool = k_pool, v_pool
            seeds = np.zeros(R, np.int32)
            counts = np.zeros(R, np.int32)
            temps = np.zeros(R, np.float32)
            for j, seq in enumerate(batch):
                seeds[j] = seq.seed
                temps[j] = seq.temperature
            first = np.asarray(
                _sample_rows(
                    logits, jnp.asarray(seeds), jnp.asarray(counts),
                    jnp.asarray(temps),
                )
            )
        _bump("prefill_tokens_total", t_real)
        for j, seq in enumerate(batch):
            seq.length = lens[j]
            if not replay:
                seq.count = 1
            self._register_progress_locked(seq)
            self._register_partial_locked(seq)
            tok = int(first[j])
            self._consume_token_locked(seq, tok)
            if seq.handle is not None and not seq.handle.done:
                self._live.append(seq)

    def _consume_token_locked(self, seq: _Seq, tok: int) -> None:
        """Route one sampled token: discarded while forced (extension)
        input remains, else appended/streamed; retires on EOS/max_new."""
        if seq.forced:
            seq.next_input = seq.forced.popleft()
            return
        seq.generated.append(tok)
        seq.all_tokens.append(tok)
        seq.next_input = tok
        _bump("tokens_generated_total")
        self._rates.note_tokens(1)
        seq.handle._on_token(tok)
        if len(seq.generated) >= seq.max_new or (
            seq.eos_id is not None and tok == seq.eos_id
        ):
            self._retire_locked(seq)

    def _retire_locked(self, seq: _Seq) -> None:
        _bump("retired_total")
        if seq in self._live:
            self._live.remove(seq)
        # content-register what this sequence produced BEFORE the blocks
        # go anywhere: retained blocks serve matches while parked, and
        # non-retained blocks linger in the free list still addressed —
        # a sequential re-ask of the same prompt revives them for free
        self._register_progress_locked(seq)
        self._register_partial_locked(seq)
        if seq.retain:
            self._retained[id(seq.handle)] = seq
        else:
            self._free_seq_blocks_locked(seq)
        seq.handle._finish()

    def _prepare_write_locked(self, seq: _Seq, n: int) -> bool:
        """COW / registration maintenance for the blocks positions
        ``[seq.length, seq.length + n)`` are about to write.  A shared
        block (refcount > 1) is copied into the sequence's reserved
        spare (or a fresh block) first; a sole-owned block's partial
        registration is truncated at the write cursor.  Returns False to
        STALL the row this tick when a copy destination cannot be
        allocated right now — sound, because every other live sequence
        holds its worst-case reservation and will retire."""
        bs = self.pool.block_size
        alloc = self.pool.allocator
        first = seq.length
        for bi in range(first // bs, (first + n - 1) // bs + 1):
            b = seq.blocks[bi]
            if alloc.refcount(b) > 1:
                dst = seq.cow_spare
                if dst is not None:
                    seq.cow_spare = None
                else:
                    got = alloc.alloc(1)
                    if got is None:
                        return False
                    dst = got[0]
                self.pool.copy_block(b, dst)
                alloc.free([b])  # drop our read ref; others keep it
                seq.blocks[bi] = dst
                _bump("cow_copies_total")
            else:
                # sole owner appending into its own registered tail:
                # entries from the write slot on are clobbered
                slot = first % bs if bi == first // bs else 0
                self.pool.prefix.truncate_partial(b, slot)
        return True

    def _decode_step_locked(self) -> bool:
        """Advance the live set: plan each row's input bundle (next
        token + forced-extension tail + prompt-lookup drafts), COW any
        shared block in the write span, launch, then commit outputs
        with EXACT sequential semantics — a draft lane is accepted only
        while it matches what the sequential step stream would have
        consumed.  Returns whether any row advanced."""
        rows = list(self._live)
        bs = self.pool.block_size
        plans: list[tuple[_Seq, list[int], int, int]] = []
        k_max = 1
        for seq in rows:
            cap = len(seq.blocks) * bs - seq.length
            inputs = [seq.next_input]
            n_forced = 0
            n_draft = 0
            if seq.forced:
                take = min(len(seq.forced), _INGEST_K - 1, max(0, cap - 1))
                for i, t in enumerate(seq.forced):
                    if i >= take:
                        break
                    inputs.append(t)
                n_forced = take
            elif self.spec_k > 0:
                remaining = seq.max_new - len(seq.generated)
                m = min(self.spec_k, remaining - 1, cap - 1)
                if m > 0:
                    draft = propose_draft(seq.all_tokens, m)
                    if draft:
                        inputs.extend(draft)
                        n_draft = len(draft)
                        _bump("draft_proposed_total", n_draft)
                        self._rates.note_draft(n_draft, 0)
            plans.append((seq, inputs, n_forced, n_draft))
            k_max = max(k_max, len(inputs))
        if k_max <= 1:
            return self._single_step_locked(plans)
        return self._multi_step_locked(plans, k_max)

    def _single_step_locked(
        self, plans: list[tuple[_Seq, list[int], int, int]]
    ) -> bool:
        R = _pow2_bucket(len(plans))
        W = self.pool.blocks_per_seq
        bt = np.zeros((R, W), np.int32)
        lengths = np.zeros(R, np.int32)
        toks = np.zeros(R, np.int32)
        active = np.zeros(R, bool)
        seeds = np.zeros(R, np.int32)
        counts = np.zeros(R, np.int32)
        temps = np.zeros(R, np.float32)
        for r, (seq, _inputs, _nf, _nd) in enumerate(plans):
            if not self._prepare_write_locked(seq, 1):
                continue  # stalled: dead row this tick
            blocks = seq.blocks
            bt[r, : len(blocks)] = blocks
            lengths[r] = seq.length
            toks[r] = seq.next_input
            active[r] = True
            seeds[r] = seq.seed
            counts[r] = seq.count
            temps[r] = seq.temperature
        if not active.any():
            return False
        with self._record_span(
            "decode:step", {"rows": len(plans), "bucket": R},
            seqs=[p[0] for p in plans], launch_kind="decode_step",
        ) as timed:
            try:
                k_pool, v_pool, toks_next = self._launch_guarded_locked(
                    "device.decode_step",
                    lambda: _step_jit()(
                        self.params, self.pool.k_pool, self.pool.v_pool,
                        jnp.asarray(bt), jnp.asarray(lengths),
                        jnp.asarray(toks), jnp.asarray(active),
                        jnp.asarray(seeds), jnp.asarray(counts),
                        jnp.asarray(temps),
                        cfg=self.cfg, block_size=self.pool.block_size,
                        mode=self.mode,
                    ),
                )
            except BaseException as exc:
                if classify_device_error(exc) == FATAL:
                    raise  # tick-level handler quarantines + replays
                timed.set(ok=False)
                self._contain_launch_failure_locked(
                    [p[0] for r, p in enumerate(plans) if active[r]],
                    exc, "decode_step",
                )
                return True
            self.pool.k_pool, self.pool.v_pool = k_pool, v_pool
            # host read = device sync (handler contract)
            out = np.asarray(toks_next)
        for r, (seq, _inputs, _nf, _nd) in enumerate(plans):
            if not active[r]:
                continue
            seq.length += 1
            seq.count += 1
            self._consume_token_locked(seq, int(out[r]))
            if seq.blocks:
                self._register_progress_locked(seq)
        return True

    def _multi_step_locked(
        self, plans: list[tuple[_Seq, list[int], int, int]], k_max: int
    ) -> bool:
        K = max(2, _pow2_bucket(k_max))
        R = _pow2_bucket(len(plans))
        W = self.pool.blocks_per_seq
        bt = np.zeros((R, W), np.int32)
        base = np.zeros(R, np.int32)
        n_new = np.zeros(R, np.int32)
        toks = np.zeros((R, K), np.int32)
        active = np.zeros(R, bool)
        seeds = np.zeros(R, np.int32)
        counts = np.zeros(R, np.int32)
        temps = np.zeros(R, np.float32)
        for r, (seq, inputs, _nf, _nd) in enumerate(plans):
            n = len(inputs)
            if not self._prepare_write_locked(seq, n):
                continue  # stalled: dead row this tick
            blocks = seq.blocks
            bt[r, : len(blocks)] = blocks
            base[r] = seq.length
            n_new[r] = n
            toks[r, :n] = inputs
            active[r] = True
            seeds[r] = seq.seed
            counts[r] = seq.count
            temps[r] = seq.temperature
        if not active.any():
            return False
        with self._record_span(
            "decode:verify",
            {"rows": len(plans), "bucket": R, "k": K},
            seqs=[p[0] for p in plans], launch_kind="verify",
        ) as timed:
            try:
                k_pool, v_pool, toks_out = self._launch_guarded_locked(
                    "device.verify",
                    lambda: _multi_jit()(
                        self.params, self.pool.k_pool, self.pool.v_pool,
                        jnp.asarray(bt), jnp.asarray(base),
                        jnp.asarray(n_new), jnp.asarray(toks),
                        jnp.asarray(active), jnp.asarray(seeds),
                        jnp.asarray(counts), jnp.asarray(temps),
                        cfg=self.cfg, block_size=self.pool.block_size,
                        mode=self.mode,
                    ),
                )
            except BaseException as exc:
                if classify_device_error(exc) == FATAL:
                    raise  # tick-level handler quarantines + replays
                timed.set(ok=False)
                self._contain_launch_failure_locked(
                    [p[0] for r, p in enumerate(plans) if active[r]],
                    exc, "verify",
                )
                return True
            self.pool.k_pool, self.pool.v_pool = k_pool, v_pool
            out = np.asarray(toks_out)  # host read = device sync
        for r, (seq, inputs, nf, nd) in enumerate(plans):
            if not active[r]:
                continue
            n = int(n_new[r])
            accepted = 0
            for j in range(n):
                if nd and j >= 1 + nf:
                    accepted += 1  # the draft at inputs[j] got consumed
                seq.length += 1
                seq.count += 1
                self._consume_token_locked(seq, int(out[r, j]))
                if seq.handle is not None and seq.handle.done:
                    break  # retired mid-bundle (EOS / max_new)
                if j + 1 < n and seq.next_input != inputs[j + 1]:
                    break  # draft diverged: later lanes are rolled back
            if accepted:
                _bump("draft_accepted_total", accepted)
                self._rates.note_draft(0, accepted)
            if seq.blocks:
                self._register_progress_locked(seq)
        return True

    # -- pump / runtime integration -------------------------------------
    def _ensure_pump_locked(self) -> None:
        if self._pump is None or not self._pump.is_alive():
            self._pump = threading.Thread(
                target=self._pump_loop, daemon=True, name="pw-decode",
            )
            self._pump.start()

    def _pump_loop(self) -> None:
        from ..internals.flight_recorder import name_thread
        from ..runtime import QoS, WorkGroup, get_runtime

        name_thread("pw-decode")
        if self._group is None:
            self._group = WorkGroup(
                f"{self.name}:tick",
                lambda payloads: [self.tick() for _ in payloads],
                max_batch=1,
            )
        while True:
            with self._lock:
                while not self._closed and not self._has_work_locked():
                    self._work.wait()
                if self._closed:
                    return
                live = len(self._live)
            try:
                # ONE decode step per GENERATE item: INTERACTIVE
                # retrieval preempts between steps, never mid-step
                progressed = get_runtime().submit(
                    self._group, None, qos=QoS.GENERATE,
                    tokens=max(1, live), coalesce_s=0.0,
                ).result()
            except BaseException as exc:  # noqa: BLE001 — fail waiters, keep pumping
                self._fail_all(exc)
                continue
            if not progressed:
                # pending work that cannot be admitted yet (pool held by
                # retained sequences): poll at a bounded rate — deadline
                # shedding still needs periodic ticks — instead of
                # busy-spinning no-op ticks at 100% CPU; release/cancel/
                # submit notify the condition to wake us early
                with self._lock:
                    if not self._closed:
                        self._work.wait(timeout=0.05)

    def _fail_all(self, exc: BaseException) -> None:
        with self._lock:
            seqs = list(self._live) + list(self._pending)
            self._live.clear()
            self._pending.clear()
            for seq in seqs:
                self._free_seq_blocks_locked(seq)
                if seq.handle is not None and not seq.handle.done:
                    seq.handle._finish(exc)
        from ..internals.errors import register_error

        register_error(
            f"decode tick failed: {type(exc).__name__}: {exc}",
            kind="serving",
            operator=self.name,
        )

    def drain(self, timeout: float | None = 60.0) -> None:
        """Manual mode: run ticks inline until idle."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._lock:
                if not self._has_work_locked():
                    return
            self.tick()
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError("decode session did not drain in time")

    def close(self) -> None:
        with self._lock:
            self._closed = True
            self._work.notify_all()

    # -- introspection ---------------------------------------------------
    @property
    def live_count(self) -> int:
        return len(self._live)

    def stats(self) -> dict[str, Any]:
        alloc = self.pool.allocator
        return {
            "live_sequences": len(self._live),
            "pending": len(self._pending),
            "retained": len(self._retained),
            "kv_blocks_used": alloc.used_count,
            "kv_blocks_free": alloc.free_count,
            "shared_blocks": alloc.shared_count,
            "prefix_index_entries": len(self.pool.prefix),
            "spec_k": self.spec_k,
            "prefix_share": self.prefix_share,
            "block_size": self.pool.block_size,
            "pool_blocks": self.pool.num_blocks,
            "ticks_total": self.ticks_total,
            "mode": self.mode,
            "hbm_bytes": self.pool.hbm_bytes(),
            "recovering": self._recovering,
            "breaker": None if self.breaker is None else self.breaker.state,
            "fault_retries": self.fault_retries,
            "replayed_sequences": sum(
                1 for s in list(self._live) + list(self._retained.values())
                if s.replayed
            ),
            "rates": self._rates.snapshot(),
        }


class PagedDecoder:
    """Thin convenience wrapper: a :class:`DecodeSession` plus one-shot
    batch generation (the bench entry point)."""

    def __init__(self, cfg: DecoderConfig, params: Any, **session_kwargs):
        session_kwargs.setdefault("auto", False)
        self.session = DecodeSession(cfg, params, **session_kwargs)

    def generate_ids(
        self,
        prompts_ids: Sequence[Sequence[int]],
        max_new_tokens: int = 32,
        **submit_kwargs,
    ) -> list[list[int]]:
        handles = [
            self.session.submit(
                p, max_new_tokens=max_new_tokens, **submit_kwargs
            )
            for p in prompts_ids
        ]
        self.session.drain()
        return [h.result(timeout=5.0) for h in handles]
