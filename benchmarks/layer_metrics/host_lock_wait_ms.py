"""Milliseconds a tagged request's thread was runnable and not running: its
root span's `dur_ms`, less the root's `cpu_ms` (the thread ran), less the
union, inside the root, of the spans in which the thread sleeps by design:
`dispatch_queue_wait`, `dispatch_ready_wait`, and a `dispatch_launch` or
`dispatch_collect` WITHOUT `cpu_ms` (a rider's copy: the leader's own carries
its CPU), less the `cpu_ms` such a sleep span carries (the leader's own
`dispatch_ready_wait`: what of the device's wait its thread ran after all; a
backend that runs the program on the waiting thread, all of it).
`dispatch_pipeline_wait` is left out: the leader's wait for the
depth semaphore lies inside its own `dispatch_queue_wait` (submit to launch),
and the copy a rider gets starts before that rider had submitted, over its own
parse and plan. What is left the thread spent waiting for the interpreter
lock, for a wake-up to reach it, or for a lock of the program's. The MEAN
over the tagged requests, not the median: the thread clock of the machine
with the chip ticks every 10 ms, so one request's `cpu_ms` reads 0 or 10 and
its own difference says nothing; the mean over some hundred does (and is the
same on a clock that ticks finer). A request whose root has no `cpu_ms`
counts for nothing. What a leader runs inside its own queue wait (submit to
launch: some 10 us of the queue's bookkeeping a dispatch) is counted as sleep,
so the reading is low by that: that span carries no `cpu_ms`, because one
more clock read a submit costs more than it tells (PERF.md section 6, PR 49).
A mean negative by more means CPU burned inside a span counted as sleep, or a
sleep with no span: it is not clamped."""

from harness import spans
from harness.stats import union_seconds

NAME, UNIT, LAYER, MOVES, SOURCE = "host.lock_wait_ms", "ms", "host runtime", "p50_ms", "program_span"
SLEEPS = ("dispatch_queue_wait", "dispatch_ready_wait")
LEADERS = ("dispatch_launch", "dispatch_collect")  # sleep in a rider's trace, work in the leader's


def asleep_ms(doc: dict, root: dict) -> float:
    lo, hi = root["start_ms"], root["start_ms"] + root["dur_ms"]
    slept = [
        s for s in doc["spans"]
        if (s["name"] in SLEEPS or (s["name"] in LEADERS and "cpu_ms" not in s))
        and s["start_ms"] < hi and s["start_ms"] + s["dur_ms"] > lo
    ]
    covered = union_seconds([(max(s["start_ms"], lo), min(s["start_ms"] + s["dur_ms"], hi)) for s in slept])
    return covered - sum(s.get("cpu_ms", 0.0) for s in slept)


def read(ctx):
    xs = []
    for t in ctx["tagged"]:
        r = spans.root(t["doc"])
        if r is not None and "cpu_ms" in r:
            xs.append(r["dur_ms"] - r["cpu_ms"] - asleep_ms(t["doc"], r))
    return sum(xs) / len(xs) if xs else None
