"""CPU milliseconds the event-loop threads burned a completed statement: the
window's delta of `cpu_loop_s` (`DispatchQueue.stats()`) over the requests the
window completed. The wire's own work on the server (read, unmask, decode,
admit, hand off, write), without the waiting that `wire.ms` and
`wire.write_ms` hold. A program without the key reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "wire.cpu_ms_per_stmt", "ms", "wire", "stmt_per_s", "program_counter"


def read(ctx):
    d, n = ctx["window"]["dispatch"], len(ctx["window"]["records"])
    if "cpu_loop_s" not in d or not n:
        return None
    return d["cpu_loop_s"] * 1e3 / n
