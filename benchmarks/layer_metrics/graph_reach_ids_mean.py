"""Record ids the set expansion handed the executor a tagged statement, all its
`array::distinct(<chain>)` expressions together (the `ids` label of each
expression's `graph_prepare` span, which also carries `memo`): what the
executor turns into record ids and the dialect's `array::concat` /
`array::distinct` then walk, whatever the LIMIT keeps. The mean over the tagged
statements: it follows the names the window's requests asked for."""

NAME, UNIT, LAYER, MOVES, SOURCE = "graph.reach_ids_mean", "ids/stmt", "kernels", "p50_ms", "program_span"


def read(ctx):
    xs = []
    for t in ctx["tagged"]:
        ids = [int(s["labels"]["ids"]) for s in t["doc"]["spans"]
               if s["name"] == "graph_prepare" and "memo" in s["labels"] and "ids" in s["labels"]]
        if ids:
            xs.append(sum(ids))
    return sum(xs) / len(xs) if xs else None
