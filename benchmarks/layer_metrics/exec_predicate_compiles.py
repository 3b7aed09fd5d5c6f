"""WHERE trees a tagged statement lowered onto the column mirror
(`ops/predicates.py::compile_where`, one `predicate_compile` span each): the
riding WHERE of a chain compiles once a statement where the statement's
expressions share one preparation, and once an expression (and once more a
comparison of two bindings) where each prepares for itself. The median over
the tagged statements that compiled any."""

import statistics

NAME, UNIT, LAYER, MOVES, SOURCE = "exec.predicate_compiles", "count/stmt", "parse/plan + executor", "p50_ms", "program_span"


def read(ctx):
    xs = [n for n in (sum(s["name"] == "predicate_compile" for s in t["doc"]["spans"]) for t in ctx["tagged"]) if n]
    return statistics.median(xs) if xs else None
