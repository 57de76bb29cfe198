"""Percentiles as every end-to-end metric and the sweep take them."""

from __future__ import annotations


def rank(values: list[float], pct: int) -> float | None:
    """Nearest-rank percentile; nothing for no values."""
    values = sorted(values)
    return values[max(0, -(-pct * len(values) // 100) - 1)] if values else None


def tail(records: list[dict], key: str, pct: int, worst: float) -> float | None:
    """Percentile over ALL records: one that failed, or has no ``key``,
    counts at ``worst`` so that failures lengthen the tail."""
    return rank([worst if r["failed"] or r.get(key) is None else r[key] for r in records], pct)
