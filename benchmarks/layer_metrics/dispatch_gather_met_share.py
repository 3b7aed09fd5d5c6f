"""Of the times a gathering leader waited for the riders of its group, the
share in which they came before the deadline. A bucket that is one batch deep
and gathers (`dbs/dispatch.py::_gather`: the swept graph counts and the set
chain) has its leader wait until the queue is as wide as the batch before
last, at most the time one launch phase takes; the queue counts in `stats()`
the waits (`gather_waits`), those the riders met (`gather_met`) and the
seconds waited (`gather_wait_s`). `gather_met / gather_waits` over the window:
low, the wait's bound runs out and batches ride narrow. A window without a
wait reports nothing, and so does a program without the counters."""

NAME, UNIT, LAYER, MOVES, SOURCE = "dispatch.gather_met_share", "ratio", "dispatch", "p95_ms", "program_counter"


def read(ctx):
    d = ctx["window"]["dispatch"]
    if "gather_met" not in d or d.get("gather_waits", 0) <= 0:
        return None
    return d["gather_met"] / d["gather_waits"]
