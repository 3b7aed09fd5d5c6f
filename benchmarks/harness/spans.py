"""Arithmetic over one request's span tree, as the program's trace store
holds it (`tracing.get_trace`: a flat list of spans with `start_ms` and
`dur_ms` from the trace's start, the root first)."""

from __future__ import annotations

from typing import List, Optional

from harness.stats import union_seconds

DISPATCH_SPANS = ("dispatch_queue_wait", "dispatch_launch", "dispatch_collect")


def root(doc: dict) -> Optional[dict]:
    for s in doc["spans"]:
        if s["parent"] is None:
            return s
    return None


def covered_ms(doc: dict, names) -> float:
    """Milliseconds of the request covered by spans with these names
    (overlaps counted once)."""
    return union_seconds(
        [(s["start_ms"], s["start_ms"] + s["dur_ms"]) for s in doc["spans"] if s["name"] in names]
    )


def durations_ms(doc: dict, name: str) -> List[float]:
    return [s["dur_ms"] for s in doc["spans"] if s["name"] == name]
