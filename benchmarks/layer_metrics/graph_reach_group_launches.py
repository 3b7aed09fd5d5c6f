"""Launches of the set kernel a tagged statement rode: its `dispatch_launch`
spans whose labels carry `slots` (idx/graph_csr.py::_collect_rings puts the
operand slots a launch swept on every rider's span), a launch once however
many of the statement's riders it carried (the spans of one launch start at
one instant of the statement's clock), mean over the tagged statements with
such a span. Ten where every row's rider makes its own round trip, one or two
where a statement's rows ride together."""

NAME, UNIT, LAYER, MOVES, SOURCE = "graph.reach_group_launches", "launches/stmt", "dispatch", "p50_ms", "program_span"


def launches(doc) -> int:
    return len({
        (round(s["start_ms"], 3), round(s["dur_ms"], 3))
        for s in doc["spans"] if s["name"] == "dispatch_launch" and "slots" in s["labels"]
    })


def read(ctx):
    per = [n for t in ctx["tagged"] if (n := launches(t["doc"]))]
    return sum(per) / len(per) if per else None
