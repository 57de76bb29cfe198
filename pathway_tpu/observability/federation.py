"""Fleet-wide telemetry federation and cross-process trace stitching.

Two planes, both router-side (the replicas stay dumb — they already
expose ``/v1/debug/traces`` and ``/status``; this module only teaches the
router to *join* what N processes each know a fragment of):

* **Trace stitching** — one logical request crosses the router
  (dispatch + per-attempt spans), one or more replicas (request span,
  retrieval stages), and the generation plane (launch-guard spans under
  the same trace id).  :func:`stitch_trace` merges the fragments into a
  single parent-linked tree; a replica that cannot be reached marks the
  result ``incomplete`` instead of silently dropping its spans.

* **Metrics federation** — :class:`FederationState` parses each
  replica's OpenMetrics ``/status`` exposition, re-exposes every
  ``pathway_*`` family with a ``replica=`` label, and maintains
  restart-safe fleet aggregates for counters (a replica restart folds
  the last-seen value into a monotonic base instead of producing a
  negative rate).  The federated per-endpoint latency histograms feed
  fleet-level SLO burn verdicts through the SAME multi-window math the
  replicas use (:mod:`.slo` public helpers) — the router and a replica
  must agree about the same incident.

Kill switch: ``PATHWAY_FLEET_FEDERATION=0`` disables the scrape plane
entirely (the ``benchmarks/obs_overhead.py --fleet`` off-phase).
"""

from __future__ import annotations

import os
import re
import threading
import time
from collections import deque
from typing import Any, Callable

__all__ = [
    "KNOWN_SPAN_KINDS",
    "KNOWN_SPAN_PREFIXES",
    "federation_enabled",
    "stitch_trace",
    "render_tree",
    "stitched_perfetto",
    "FederationState",
]


def federation_enabled() -> bool:
    """The ``PATHWAY_FLEET_FEDERATION`` kill switch (default on)."""
    return os.environ.get(
        "PATHWAY_FLEET_FEDERATION", "1"
    ).strip().lower() not in ("0", "false", "off", "no")


# ---------------------------------------------------------------------------
# trace schema: the renderer's known-kinds table
# ---------------------------------------------------------------------------

#: span name -> (plane, description).  The ``generate`` plane entries are
#: lint-pinned against the engine's ``_record_span`` blocks (tests
#: assert set equality in BOTH directions, the fault-site registry
#: idiom): a new launch guard must document itself here, and a stale
#: entry must not outlive its guard.
KNOWN_SPAN_KINDS: dict[str, tuple[str, str]] = {
    # generation launch guards (generation/engine.py)
    "kv:alloc": ("generate", "paged KV block allocation for one sequence"),
    "kv:prefix_match": (
        "generate", "adoption of copy-on-write prefix blocks matched in the paged pool"
    ),
    "kv:rebuild": (
        "generate", "KV-pool resurrection by replay re-prefill"
    ),
    "prefill": ("generate", "batched prompt prefill device launch"),
    "decode:step": ("generate", "one batched decode device launch"),
    "decode:verify": (
        "generate", "speculative draft verification device launch"
    ),
    # fleet routing (fleet/router.py)
    "fleet:dispatch": (
        "fleet", "router-side lifetime of one proxied request"
    ),
    "fleet:attempt": (
        "fleet", "one proxy attempt against one replica (siblings on failover)"
    ),
}

#: dynamic span-name prefixes (the suffix is a label, not a kind)
KNOWN_SPAN_PREFIXES: dict[str, tuple[str, str]] = {
    "tick:": ("scheduler", "deferred runtime batch execution"),
    "tier:migrate:": ("runtime", "background tier migration"),
}


def span_kind_info(name: str) -> tuple[str, str] | None:
    """Lookup a span name in the known-kinds schema (exact match first,
    then dynamic prefixes)."""
    info = KNOWN_SPAN_KINDS.get(name)
    if info is not None:
        return info
    for prefix, pinfo in KNOWN_SPAN_PREFIXES.items():
        if name.startswith(prefix):
            return pinfo
    return None


# ---------------------------------------------------------------------------
# trace stitching
# ---------------------------------------------------------------------------

def stitch_trace(
    trace_id: str,
    router_spans: list[dict[str, Any]],
    replica_payloads: dict[str, dict[str, Any] | None],
) -> dict[str, Any]:
    """Merge the router's own spans with per-replica fragments into one
    parent-linked tree.

    ``replica_payloads`` maps replica name to its ``/v1/debug/traces``
    JSON body (``{"spans": [...]}``) or ``None`` when the fetch failed.
    An unreachable replica marks the stitched result ``incomplete``
    (partial evidence beats a 500); a span whose ``parent_id`` is not in
    the merged set becomes a root marked ``orphan`` (its parent span was
    dropped from some ring, or lives on an unreachable replica)."""
    spans: list[dict[str, Any]] = []
    seen: set[str] = set()
    incomplete = False
    replicas: dict[str, str] = {}

    def _add(raw: dict[str, Any], source: str) -> None:
        sid = raw.get("span_id")
        if sid is not None:
            if sid in seen:
                return  # router + replica can both hold the same span
            seen.add(sid)
        d = dict(raw)
        d["replica"] = source
        info = span_kind_info(str(d.get("name", "")))
        if info is not None:
            d["kind_info"] = {"plane": info[0], "description": info[1]}
        spans.append(d)

    for raw in router_spans:
        _add(raw, "router")
    for name in sorted(replica_payloads):
        payload = replica_payloads[name]
        if not isinstance(payload, dict) or "spans" not in payload:
            replicas[name] = "unreachable"
            incomplete = True
            continue
        replicas[name] = "ok"
        for raw in payload.get("spans") or []:
            if not isinstance(raw, dict):
                continue
            if raw.get("trace_id") not in (None, trace_id):
                continue  # defensive: a replica must only send this trace
            _add(raw, name)

    spans.sort(key=lambda d: (float(d.get("start_s", 0.0) or 0.0),
                              str(d.get("name", ""))))
    by_id = {d["span_id"]: d for d in spans if d.get("span_id")}
    children: dict[str, list[dict[str, Any]]] = {}
    roots: list[dict[str, Any]] = []
    for d in spans:
        pid = d.get("parent_id")
        if pid and pid in by_id and by_id[pid] is not d:
            children.setdefault(pid, []).append(d)
        else:
            if pid:
                d["orphan"] = True
            roots.append(d)

    # nest iteratively with a visited set: corrupt parent links (a
    # cycle) degrade to extra roots instead of infinite recursion
    visited: set[int] = set()

    def _node(d: dict[str, Any]) -> dict[str, Any]:
        visited.add(id(d))
        out = dict(d)
        kids = children.get(d.get("span_id") or "", [])
        out["children"] = [
            _node(k) for k in kids if id(k) not in visited
        ]
        return out

    tree = [_node(d) for d in roots if id(d) not in visited]
    return {
        "trace_id": trace_id,
        "incomplete": incomplete,
        "replicas": replicas,
        "span_count": len(spans),
        "spans": spans,
        "tree": tree,
    }


def render_tree(stitched: dict[str, Any]) -> str:
    """ASCII rendering of a stitched tree — one line per span, indented
    by depth, annotated from the known-kinds schema."""
    lines = [
        f"trace {stitched['trace_id']}"
        + (" (incomplete)" if stitched.get("incomplete") else "")
    ]

    def _walk(node: dict[str, Any], depth: int) -> None:
        info = node.get("kind_info") or {}
        desc = f" — {info['description']}" if info.get("description") else ""
        orphan = " [orphan]" if node.get("orphan") else ""
        lines.append(
            "  " * depth
            + f"{node.get('name', '?')} "
            f"({float(node.get('duration_ms', 0.0) or 0.0):.3f} ms) "
            f"@{node.get('replica', '?')}{orphan}{desc}"
        )
        for kid in node.get("children", []):
            _walk(kid, depth + 1)

    for root in stitched.get("tree", []):
        _walk(root, 1)
    return "\n".join(lines)


def stitched_perfetto(stitched: dict[str, Any]) -> dict[str, Any]:
    """Chrome-tracing export of a stitched tree, reusing the profiler's
    span-export path (one converter, not two)."""
    from ..internals.flight_recorder import FlightRecorder, Span

    spans = [
        Span(
            str(d.get("name", "?")),
            str(d.get("category", "?")),
            float(d.get("start_s", 0.0) or 0.0),
            float(d.get("duration_ms", 0.0) or 0.0),
            d.get("trace_id"),
            d.get("span_id"),
            d.get("parent_id"),
            {**(d.get("attrs") or {}), "replica": d.get("replica", "")},
        )
        for d in stitched.get("spans", [])
    ]
    return FlightRecorder.perfetto(spans)


# ---------------------------------------------------------------------------
# OpenMetrics exposition parsing (the scrape side)
# ---------------------------------------------------------------------------

_TYPE_RE = re.compile(r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) ([a-z]+)\s*$")
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"  # sample name
    r"(?:\{(.*)\})?"                # label set (raw, unsplit)
    r"\s+(\S+)\s*$"                 # value
)
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')

#: sample-name suffixes that resolve to a complex family's base name
_FAMILY_SUFFIXES = ("_bucket", "_sum", "_count", "_created")


def _unescape(value: str) -> str:
    out: list[str] = []
    it = iter(range(len(value)))
    i = 0
    while i < len(value):
        c = value[i]
        if c == "\\" and i + 1 < len(value):
            nxt = value[i + 1]
            out.append({"n": "\n", "\\": "\\", '"': '"'}.get(nxt, nxt))
            i += 2
        else:
            out.append(c)
            i += 1
    return "".join(out)


def parse_labels(labels_str: str | None) -> dict[str, str]:
    if not labels_str:
        return {}
    return {
        m.group(1): _unescape(m.group(2))
        for m in _LABEL_RE.finditer(labels_str)
    }


def parse_exposition(text: str) -> dict[str, dict[str, Any]]:
    """Parse one OpenMetrics exposition into
    ``{family: {"type": str, "samples": [(sample_name, labels_str, value)]}}``.

    Only ``pathway_*`` families are kept.  Exemplar suffixes
    (``... # {trace_id="..."} v ts``) are stripped before the sample
    regex runs — the ``# TYPE``-driven family table resolves
    ``_bucket``/``_sum``/``_count`` sample names onto their histogram
    family."""
    families: dict[str, dict[str, Any]] = {}
    types: dict[str, str] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        if line.startswith("#"):
            m = _TYPE_RE.match(line)
            if m:
                types[m.group(1)] = m.group(2)
            continue
        # exemplars ride after ` # ` on bucket lines; the label regex
        # must never see the exemplar's own brace group
        body = line.split(" # ", 1)[0].rstrip()
        m = _SAMPLE_RE.match(body)
        if m is None:
            continue
        sname, labels_str, raw_value = m.group(1), m.group(2), m.group(3)
        try:
            value = float(raw_value)
        except ValueError:
            continue
        family = sname if sname in types else None
        if family is None:
            for suffix in _FAMILY_SUFFIXES:
                if sname.endswith(suffix) and sname[: -len(suffix)] in types:
                    family = sname[: -len(suffix)]
                    break
        if family is None:
            family = sname
        if not family.startswith("pathway_"):
            continue
        fam = families.get(family)
        if fam is None:
            fam = families[family] = {
                "type": types.get(family, "gauge"),
                "samples": [],
            }
        fam["samples"].append((sname, labels_str or "", value))
    return families


def _inject_replica_label(
    sname: str, labels_str: str, replica: str
) -> str:
    from ..internals.metrics_names import escape_label_value

    lab = f'replica="{escape_label_value(replica)}"'
    if labels_str:
        lab = f"{lab},{labels_str}"
    return f"{sname}{{{lab}}}"


def _fmt(value: float) -> str:
    if float(value).is_integer() and abs(value) < 1e15:
        return str(int(value))
    return repr(float(value))


# ---------------------------------------------------------------------------
# federation state (scrapes, aggregates, fleet SLO)
# ---------------------------------------------------------------------------

#: families the federation plane itself owns — never re-exposed from a
#: replica (a collision would emit two TYPE lines for one family)
_OWN_FAMILIES = frozenset({
    "pathway_fleet_aggregate_total",
    "pathway_fleet_scrapes_total",
    "pathway_fleet_scrape_errors_total",
    "pathway_fleet_slo_burn_rate",
    "pathway_fleet_slo_verdict",
})

#: the per-endpoint latency histogram the fleet SLO verdicts read
_LATENCY_FAMILY = "pathway_endpoint_latency_ms"

#: a sample that already carries a ``replica=`` label was federated by
#: some OTHER router (a replica whose process embeds one, or a tiered
#: router topology): folding it again would double-count aggregates and
#: nest ``replica=`` labels one level deeper per scrape cycle
_FEDERATED_RE = re.compile(r'(?:^|,)replica="')


def _already_federated(labels_str: str) -> bool:
    return bool(_FEDERATED_RE.search(labels_str))


class FederationState:
    """Router-side scrape state: per-replica re-exposition, restart-safe
    counter aggregates, and fleet SLO burn rings.

    Thread-safe; the router calls :meth:`note_scrape` from its poller
    thread and :meth:`openmetrics_lines` / :meth:`status` from the
    aiohttp loop."""

    def __init__(
        self,
        *,
        clock: Callable[[], float] = time.monotonic,
        stale_after_s: float | None = None,
    ):
        self._lock = threading.Lock()
        self._clock = clock
        self.stale_after_s = (
            stale_after_s
            if stale_after_s is not None
            else float(os.environ.get("PATHWAY_FLEET_SCRAPE_STALE_S", "15.0"))
        )
        #: latest parse per replica (re-exposition source)
        self._families: dict[str, dict[str, dict[str, Any]]] = {}
        self._scraped_at: dict[str, float] = {}
        #: counter folding: aggregate(key) = retired + Σ(base + last)
        #: over replicas — monotonic across restarts AND drops
        self._last: dict[str, dict[tuple[str, str], float]] = {}
        self._base: dict[str, dict[tuple[str, str], float]] = {}
        self._retired: dict[tuple[str, str], float] = {}
        #: fleet SLO: per-replica (count, bad) baselines and the shared
        #: per-endpoint per-second rings the burn windows read
        self._slo_last: dict[str, dict[str, tuple[float, float]]] = {}
        self._slo_series: dict[str, deque] = {}
        self.scrapes_total = 0
        self.scrape_errors_total = 0

    # -- scrape ingestion -------------------------------------------------
    def note_scrape(self, replica: str, text: str) -> None:
        """Fold one replica ``/status`` body in."""
        families = parse_exposition(text)
        now = self._clock()
        with self._lock:
            self.scrapes_total += 1
            self._families[replica] = families
            self._scraped_at[replica] = now
            last = self._last.setdefault(replica, {})
            base = self._base.setdefault(replica, {})
            for family, fam in families.items():
                if fam["type"] != "counter" or family in _OWN_FAMILIES:
                    continue
                for sname, labels_str, value in fam["samples"]:
                    if sname != family or _already_federated(labels_str):
                        continue  # _created etc. are not the counter
                    key = (family, labels_str)
                    prev = last.get(key)
                    if prev is not None and value < prev:
                        # counter went backwards without an epoch signal:
                        # an in-place restart — fold, stay monotonic
                        base[key] = base.get(key, 0.0) + prev
                    last[key] = value
            self._ingest_slo_locked(replica, families, now)

    def note_scrape_error(self, replica: str) -> None:
        with self._lock:
            self.scrape_errors_total += 1

    def _ingest_slo_locked(
        self,
        replica: str,
        families: dict[str, dict[str, Any]],
        now: float,
    ) -> None:
        from . import slo

        fam = families.get(_LATENCY_FAMILY)
        if fam is None:
            return
        # per endpoint: cumulative request count and the cumulative
        # count inside the latency target (largest bucket <= target)
        counts: dict[str, float] = {}
        good: dict[str, tuple[float, float]] = {}  # endpoint -> (le, cum)
        for sname, labels_str, value in fam["samples"]:
            if _already_federated(labels_str):
                continue
            labels = parse_labels(labels_str)
            endpoint = labels.get("endpoint")
            if not endpoint:
                continue
            if sname == f"{_LATENCY_FAMILY}_count":
                counts[endpoint] = value
            elif sname == f"{_LATENCY_FAMILY}_bucket":
                target = slo.latency_target_ms(endpoint)
                if target <= 0.0:
                    continue
                try:
                    le = float(labels.get("le", "nan"))
                except ValueError:
                    continue
                best = good.get(endpoint)
                if le <= target and (best is None or le > best[0]):
                    good[endpoint] = (le, value)
        baselines = self._slo_last.setdefault(replica, {})
        for endpoint, count in counts.items():
            if endpoint not in good:
                continue  # no configured target -> no fleet objective
            bad = max(0.0, count - good[endpoint][1])
            prev = baselines.get(endpoint)
            baselines[endpoint] = (count, bad)
            if prev is None:
                continue  # first scrape after (re)start: baseline only
            dn, dbad = count - prev[0], bad - prev[1]
            if dn <= 0 or dbad < 0:
                continue  # restart raced the epoch signal: re-baseline
            ring = self._slo_series.get(endpoint)
            if ring is None:
                ring = self._slo_series[endpoint] = deque()
            sec = int(now)
            if ring and ring[-1][0] == sec:
                ring[-1][1] += dn
                ring[-1][2] += dbad
            else:
                ring.append([sec, dn, dbad])
            # prune beyond the slow window (the longest reader)
            horizon = slo.burn_settings()["slow_s"]
            while ring and now - ring[0][0] > horizon:
                ring.popleft()

    # -- membership hooks -------------------------------------------------
    def reset_replica(self, replica: str) -> None:
        """Epoch restart: the NEXT scrape's counters start near zero.
        Fold every last-seen value into the monotonic base now so the
        aggregate never decreases, and drop the SLO delta baselines so
        the first post-restart scrape only re-baselines."""
        with self._lock:
            last = self._last.get(replica, {})
            base = self._base.setdefault(replica, {})
            for key, value in last.items():
                base[key] = base.get(key, 0.0) + value
                last[key] = 0.0
            self._slo_last.pop(replica, None)

    def drop_replica(self, replica: str) -> None:
        """Replica left the fleet: retire its contribution (aggregates
        stay monotonic) and DROP its re-exposed series (stale series
        vanish instead of freezing at their last value)."""
        with self._lock:
            last = self._last.pop(replica, {})
            base = self._base.pop(replica, {})
            for key in set(last) | set(base):
                self._retired[key] = (
                    self._retired.get(key, 0.0)
                    + base.get(key, 0.0)
                    + last.get(key, 0.0)
                )
            self._families.pop(replica, None)
            self._scraped_at.pop(replica, None)
            self._slo_last.pop(replica, None)

    # -- read side --------------------------------------------------------
    def _live_replicas_locked(self, now: float) -> list[str]:
        return sorted(
            n
            for n, at in self._scraped_at.items()
            if now - at <= self.stale_after_s
        )

    def verdicts(self) -> dict[str, Any]:
        """Fleet-level burn verdicts from the federated latency
        histograms — same windows, budget, and thresholds as a replica's
        own verdict."""
        from . import slo

        cfg = slo.burn_settings()
        now = self._clock()
        endpoints: dict[str, Any] = {}
        worst = "ok"
        with self._lock:
            series = {ep: list(ring) for ep, ring in self._slo_series.items()}
        for endpoint in sorted(series):
            fast, n_fast = _ring_burn(
                series[endpoint], cfg["fast_s"], slo.LATENCY_BUDGET, now
            )
            slow, n_slow = _ring_burn(
                series[endpoint], cfg["slow_s"], slo.LATENCY_BUDGET, now
            )
            verdict = slo.burn_verdict(fast, slow, cfg)
            endpoints[endpoint] = {
                "verdict": verdict,
                "burn_fast": round(fast, 3),
                "burn_slow": round(slow, 3),
                "samples_fast": n_fast,
                "samples_slow": n_slow,
                "p99_ms": slo.latency_target_ms(endpoint),
            }
            worst = slo.worse_verdict(worst, verdict)
        return {"verdict": worst, "endpoints": endpoints}

    def status(self) -> dict[str, Any]:
        now = self._clock()
        with self._lock:
            replicas = {
                n: {
                    "age_s": round(now - at, 3),
                    "stale": (now - at) > self.stale_after_s,
                }
                for n, at in sorted(self._scraped_at.items())
            }
            scrapes = self.scrapes_total
            errors = self.scrape_errors_total
        out = self.verdicts()
        out["replicas"] = replicas
        out["scrapes"] = scrapes
        out["scrape_errors"] = errors
        return out

    def openmetrics_lines(
        self, skip_families: frozenset | set | None = None
    ) -> list[str]:
        """Federated exposition: per-replica re-exposed families (live
        replicas only — stale series are dropped, not frozen), monotonic
        counter aggregates, scrape counters, and the fleet SLO gauges."""
        from ..internals.metrics_names import escape_label_value

        skip = set(skip_families or ()) | set(_OWN_FAMILIES)
        now = self._clock()
        lines: list[str] = []
        with self._lock:
            live = self._live_replicas_locked(now)
            # family -> (type, [(replica, sname, labels_str, value)])
            merged: dict[str, tuple[str, list]] = {}
            for replica in live:
                for family, fam in self._families[replica].items():
                    if family in skip:
                        continue
                    entry = merged.get(family)
                    if entry is None:
                        entry = merged[family] = (fam["type"], [])
                    for sname, labels_str, value in fam["samples"]:
                        if _already_federated(labels_str):
                            continue
                        entry[1].append((replica, sname, labels_str, value))
            aggregates: dict[tuple[str, str], float] = dict(self._retired)
            for replica in self._last:
                base = self._base.get(replica, {})
                last = self._last[replica]
                for key in set(last) | set(base):
                    aggregates[key] = (
                        aggregates.get(key, 0.0)
                        + base.get(key, 0.0)
                        + last.get(key, 0.0)
                    )
            scrapes = self.scrapes_total
            errors = self.scrape_errors_total
        for family in sorted(merged):
            ftype, samples = merged[family]
            if not samples:
                continue  # everything filtered as already-federated
            lines.append(f"# TYPE {family} {ftype}")
            for replica, sname, labels_str, value in samples:
                lines.append(
                    f"{_inject_replica_label(sname, labels_str, replica)}"
                    f" {_fmt(value)}"
                )
        lines.append("# TYPE pathway_fleet_aggregate_total counter")
        for (family, labels_str), value in sorted(aggregates.items()):
            lab = f'family="{escape_label_value(family)}"'
            if labels_str:
                lab = f"{lab},{labels_str}"
            lines.append(
                f"pathway_fleet_aggregate_total{{{lab}}} {_fmt(value)}"
            )
        lines.append("# TYPE pathway_fleet_scrapes_total counter")
        lines.append(f"pathway_fleet_scrapes_total {scrapes}")
        lines.append("# TYPE pathway_fleet_scrape_errors_total counter")
        lines.append(f"pathway_fleet_scrape_errors_total {errors}")
        fleet = self.verdicts()
        if fleet["endpoints"]:
            lines.append("# TYPE pathway_fleet_slo_burn_rate gauge")
            for endpoint, obj in fleet["endpoints"].items():
                safe = escape_label_value(endpoint)
                for window in ("fast", "slow"):
                    lines.append(
                        "pathway_fleet_slo_burn_rate"
                        f'{{endpoint="{safe}",window="{window}"}} '
                        f'{obj[f"burn_{window}"]}'
                    )
            lines.append("# TYPE pathway_fleet_slo_verdict gauge")
            rank = {"ok": 0, "warn": 1, "burning": 2}
            for endpoint, obj in fleet["endpoints"].items():
                safe = escape_label_value(endpoint)
                lines.append(
                    "pathway_fleet_slo_verdict"
                    f'{{endpoint="{safe}"}} '
                    f'{rank.get(obj["verdict"], 0)}'
                )
        return lines


def _ring_burn(
    cells: list, window_s: float, budget: float, now: float
) -> tuple[float, int]:
    """Burn rate over the trailing window — the :class:`slo._Series`
    rule applied to the fleet ring's ``[sec, n, bad]`` cells."""
    n = 0
    bad = 0.0
    for sec, cnt, b in reversed(cells):
        if now - sec > window_s:
            break  # append-ordered: everything older too
        n += int(cnt)
        bad += b
    if n == 0:
        return 0.0, 0
    return (bad / n) / max(budget, 1e-9), n
