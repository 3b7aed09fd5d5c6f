"""Interpreter collections inside tagged statements: the `gc_pause` spans of a
request summed, and the mean (not the median: most statements meet none) over
the requests. A program that records collections also records `ws_write`
(both came with one change), so a doc without `ws_write` counts for nothing,
and with no such doc the metric is left out rather than read as 0."""

from harness import spans

NAME, UNIT, LAYER, MOVES, SOURCE = "host.gc_ms", "ms", "host runtime", "p95_ms", "program_span"


def read(ctx):
    xs = [
        sum(spans.durations_ms(t["doc"], "gc_pause"))
        for t in ctx["tagged"]
        if spans.durations_ms(t["doc"], "ws_write")
    ]
    return sum(xs) / len(xs) if xs else None
