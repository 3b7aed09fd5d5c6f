"""CPU milliseconds of a tagged request's own thread: its root span's `cpu_ms`
(`time.thread_time` between the root's ends) and its `ws_encode`'s, which runs
on the same worker after the root has closed. The MEAN over the tagged
requests, not the median: the thread clock of the machine with the chip ticks
every 10 ms, so one request reads 0 or 10 and only the sum over some hundred
says anything (a clock that ticks finer gives the same mean). What
`exec.host_ms` holds besides is waiting. A request whose root has no `cpu_ms`
(a program that does not read the clock) counts for nothing."""

from harness import spans

NAME, UNIT, LAYER, MOVES, SOURCE = "exec.cpu_ms", "ms", "parse/plan + executor", "p50_ms", "program_span"


def read(ctx):
    xs = []
    for t in ctx["tagged"]:
        r = spans.root(t["doc"])
        if r is not None and "cpu_ms" in r:
            xs.append(r["cpu_ms"] + sum(s.get("cpu_ms", 0.0) for s in t["doc"]["spans"] if s["name"] == "ws_encode"))
    return sum(xs) / len(xs) if xs else None
