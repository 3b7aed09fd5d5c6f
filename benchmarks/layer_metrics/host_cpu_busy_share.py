"""Share of the window in which the interpreter was running the server's
threads: the CPU seconds (`time.thread_time`) of the executor workers
(`cpu_exec_s`) and of the event-loop threads (`cpu_loop_s`), as
`DispatchQueue.stats()` sums them, over the window's wall time as the program
counted it (the four sums of the dispatcher's state clock, as
`dispatch.fed_share` takes it). The clock counts a thread's CPU whether it
holds the interpreter lock or not (socket and futex system calls, `numpy`,
the runtime's own calls release it), so the share passes 1.0 where the
serving threads keep more than one core busy: 1.26-1.62 in the eight-session
cells (my chip runs, PR 49). At 1.0 or over the host is saturated and a
statement waits for work to end; well under 1.0 with `host.lock_wait_ms`
large means threads wait for the hand-off, not for each other's work. A
thread writes its clock at most once in 0.25 s (`telemetry.CPU_SLOT_EVERY_S`:
the read is a system call on the chip's host), so each edge of the window is
off by up to a quarter second of each thread's CPU: under 2% of a 30 s
window. A program without the keys reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "host.cpu_busy_share", "ratio", "host runtime", "stmt_per_s", "program_counter"
STATES = ("fed_s", "launching_s", "handoff_s", "empty_s")
CPU = ("cpu_exec_s", "cpu_loop_s")


def read(ctx):
    d = ctx["window"]["dispatch"]
    if any(k not in d for k in STATES + CPU):
        return None
    wall = sum(d[k] for k in STATES)
    return sum(d[k] for k in CPU) / wall if wall > 0 else None
