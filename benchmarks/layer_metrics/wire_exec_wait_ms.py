"""How long a decoded request of a tagged statement waited for a thread:
`ws_admit_wait` (admission and the connection's window) plus `ws_exec_wait`
(queued for one of the executor pool's threads)."""

from harness import spans
from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "wire.exec_wait_ms", "ms", "wire", "p95_ms", "program_span"


def read(ctx):
    xs = []
    for t in ctx["tagged"]:
        adm, ex = spans.durations_ms(t["doc"], "ws_admit_wait"), spans.durations_ms(t["doc"], "ws_exec_wait")
        if adm and ex:
            xs.append(sum(adm) + sum(ex))
    return median(xs) if xs else None
