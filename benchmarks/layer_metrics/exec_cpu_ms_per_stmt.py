"""CPU milliseconds the executor workers burned a completed statement: the
window's delta of `cpu_exec_s` (`DispatchQueue.stats()`: the `time.thread_time`
of the bg:net_exec threads) over the requests the window completed. Every
request counts, tagged or not, and so does what a worker did for others (a
leader's launch for its riders): the counter's view of what `exec.cpu_ms`
reads from ~300 span trees. A program without the key reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "exec.cpu_ms_per_stmt", "ms", "parse/plan + executor", "stmt_per_s", "program_counter"


def read(ctx):
    d, n = ctx["window"]["dispatch"], len(ctx["window"]["records"])
    if "cpu_exec_s" not in d or not n:
        return None
    return d["cpu_exec_s"] * 1e3 / n
