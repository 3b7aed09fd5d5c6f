"""A hybrid statement's span tree (one filtered search, then set riders of
the graph): where its two stretches start and end."""

from __future__ import annotations

from typing import Optional


def stages(doc: dict) -> Optional[tuple]:
    """(search start, search end, set riders' first submit, last collect's
    end) in the statement's milliseconds: `knn_prepare`'s start, the end of
    the statement's first `dispatch_collect` (the hits come before anything
    is asked of the graph), the first `dispatch_queue_wait` after it and
    the end of the last `dispatch_collect`. None where the statement has no
    search, or no launch of the set kernel (a `dispatch_launch` whose labels
    carry `slots`) after it."""
    spans = doc["spans"]
    prepare = [s["start_ms"] for s in spans if s["name"] == "knn_prepare"]
    collects = sorted((s["start_ms"], s["start_ms"] + s["dur_ms"]) for s in spans if s["name"] == "dispatch_collect")
    if not prepare or len(collects) < 2:
        return None
    found = collects[0][1]
    swept = [s["start_ms"] for s in spans if s["name"] == "dispatch_launch" and "slots" in s["labels"]]
    waits = [s["start_ms"] for s in spans if s["name"] == "dispatch_queue_wait" and s["start_ms"] >= found]
    if not swept or min(swept) < found or not waits:
        return None
    return min(prepare), found, min(waits), max(e for _, e in collects)
