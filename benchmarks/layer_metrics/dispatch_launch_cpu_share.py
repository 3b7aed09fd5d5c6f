"""Of a launch phase's wall time, the share its leader ran itself: the window's
delta of `launch_cpu_s` (`time.thread_time` around a launch phase) over that
of `launch_cpu_of_s`, the wall time of the SAME phases: the program reads the
clock round the launches of tagged requests and round one launch in a tenth
of a second (on the chip's host the read is a system call, and two round
every launch cost a one-session cell 5% of its p50). Near 1.0 the launch is
work (look-ups, uploads, the jitted call's host side); well under it a leader
in its launch phase is mostly runnable without the interpreter, and a shorter
launch path would not shorten the phase. A program without the keys, or a
window without a sampled launch, reports nothing. Reported in
every cell, and a READING in the eight-session cells alone: with ONE session
it read 1.15-1.82 in `vec1m768.knn_c1` and `vec500k768f.knn99p_c1` (my chip
runs, PR 49), and no thread runs more than its wall. The chip's host counts
CPU in ticks of 10 ms and the reading catches up where the thread enters the
kernel, which a launch does (its ioctls) and the span before it does not: CPU
of the neighbouring spans lands in the launch. A value over 1 is the clock's
grain, so in a one-session cell read `dispatch.launch_ms` and
`exec.cpu_ms_per_stmt` instead. (Left on those cells' lines all the same: a
`workloads` list here would break the accepted tests that take a cell's
metrics to be the unlisted ones and its own.)"""

NAME, UNIT, LAYER, MOVES, SOURCE = "dispatch.launch_cpu_share", "ratio", "dispatch", "stmt_per_s", "program_counter"


def read(ctx):
    d = ctx["window"]["dispatch"]
    if "launch_cpu_s" not in d or d.get("launch_cpu_of_s", 0) <= 0:
        return None
    return d["launch_cpu_s"] / d["launch_cpu_of_s"]
