"""Of the tagged statements that ask for the set a graph chain reaches
(`array::distinct(<chain>)`), the share the device served as the cell means it
to: ONE dispatch a statement, launched by the expression that fills the
statement's ring memo with `form=csc`, `operand=composed`, `filter=fused`. Read
from the labels the program puts on such an expression's `graph_prepare` span
(`memo`: `fill` on the one that ran the chain, `hit` on those that read its
rings; a count's span has no `memo`) and from the statement's
`dispatch_launch` spans. A statement the host walked, one whose predicate fell
back to the KV walk, and one that took two dispatches or none all count
against it. A run with no such span (a count cell, a program older than the
label) reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "graph.reach_device_share", "ratio", "kernels", "p50_ms", "program_span"
WANTED = {"form": "csc", "operand": "composed", "filter": "fused"}


def read(ctx):
    served = []
    for t in ctx["tagged"]:
        spans = t["doc"]["spans"]
        sets = [s["labels"] for s in spans if s["name"] == "graph_prepare" and "memo" in s["labels"]]
        if not sets:
            continue
        fills = [l for l in sets if l["memo"] == "fill"]
        launches = sum(1 for s in spans if s["name"] == "dispatch_launch")
        served.append(
            launches == 1 and len(fills) == 1
            and all(l.get(k) == v for l in sets for k, v in WANTED.items())
        )
    return sum(served) / len(served) if served else None
