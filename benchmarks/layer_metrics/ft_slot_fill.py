"""Share of the candidate slots the conjunctive top-k launches swept that
held a real candidate: the program's `ft_postings` counter (the riders'
rarest lists, as long as they are) over its `ft_slots` counter (lanes times
the ladder step, as launched). The harness hands a reader no counter
snapshot of the window's opening, so the two are read as they stand at the
end of the run, over the process's whole life: the first statement, the
warm-up and the window, all of the cell's one traffic (the background
warm's empty launches count in neither). A program without the counters
(another kind of cell, an older program) reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "ft.slot_fill", "ratio", "kernels", "p50_ms", "program_counter"


def read(ctx):
    from surrealdb_tpu import telemetry

    slots = telemetry.get_counter("ft_slots")
    return telemetry.get_counter("ft_postings") / slots if slots > 0 else None
