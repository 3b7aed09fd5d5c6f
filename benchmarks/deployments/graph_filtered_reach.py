"""Deployment kind `graph_filtered_reach`: `graph_filtered_count`'s graph,
persons, names and pool, asked for the PERSONS and not for a count: LDBC SNB
Interactive's IC1 as the source states it. From one start person and one bound
first name, the persons of that name a walk of 1, 2 or 3 `knows` records
reaches, each once, nearest first, at most 20 of them, as rows.

The graph, the names, the pool and the bulk of the load are
`graph_filtered_count`'s own calls, so a seed gives this kind what it gives that
one; the NumPy reference, the loader's probe and the check are here and read
nothing the program made. The reference works on walks: ring h is the set of
persons of the asked name that a walk of exactly h records ends at, the ball
of radius 3 is the union of the three rings, and a person's distance is the
first ring it stands in. The start person is not taken out (a walk of two
records returns to it), as the configuration's `assumed` says.

The loader ends with a probe, through `ds.execute()` before any client
starts: the three rings as three fields (`statements.probe`), asked of
PROBES pool entries, the one whose start person knows the fewest first, then
those that ask for the commonest names. Each ring is compared with NumPy's as
a whole set, and `dispatch.submitted` has to rise by the statement's
`dispatches`. A program that serves the chains
by expanding them (a multiset of up to 14 million records a statement here,
then a record fetch and a WHERE a record) is refused at that first, cheapest
entry, and never starts the others.
"""

from __future__ import annotations

import threading

import numpy as np

from deployments import graph_filtered_count as base

KIND = "graph_filtered_reach"
# eight caught the `u8` control on 19 seeds of 21 and missed it on two; of these 32 it has 3-13 wrong on each of the 21 (PERF.md section 2, PR 44)
PROBES = 32
SESSIONS = 8  # the cell's traffic: ws_closed_c8

generate = base.generate
pool = base.pool
person = base.person
count_sql = base.count_sql
release = base.release
kernel_shapes = base.kernel_shapes
wait_background = base.wait_background


# ------------------------------------------------------------------ reference
def walks_by_steps(pairs: np.ndarray, nodes: int, starts: np.ndarray, hops: int) -> list:
    """[hops] arrays [len(starts), nodes]: the walks of exactly h edge records
    from each start node to each node. Dense adjacency in float64
    (`x @ adj`, a step a product): every product and sum is an integer far
    below 2**53, so the arithmetic is exact."""
    adj = np.zeros((nodes, nodes), dtype=np.float64)
    np.add.at(adj, (pairs[:, 0], pairs[:, 1]), 1.0)
    out = [adj[starts]]
    for _ in range(hops - 1):
        out.append(out[-1] @ adj)
    return out


def nearest(rings: list) -> tuple:
    """(ids, dist) of one pool entry from its rings (ascending ids each):
    every person of the ball once, at the first ring it stands in, ring
    after ring and ascending inside a ring."""
    ids, dist, seen = [], [], np.empty(0, dtype=np.int64)
    for h, ring in enumerate(rings, start=1):
        new = np.setdiff1d(ring, seen, assume_unique=True)
        ids.append(new)
        dist.append(np.full(new.size, h, dtype=np.int64))
        seen = np.union1d(seen, new)
    return np.concatenate(ids), np.concatenate(dist)


def reference(cfg: dict, data: dict) -> dict:
    """Per pool entry the three rings, and the ball in IC1's order of
    distance. Beside them the two controls' rings (`correct.why`): `u8`,
    the rings of a frontier carried unclamped in an 8-bit integer (a person
    that a multiple of 256 walks reach is lost), and `unmasked`, the rings
    without the name."""
    hops, limit = int(cfg["hops"]), int(cfg["limit"])
    walks = walks_by_steps(data["pairs"], data["nodes"], data["starts"], hops)
    named = data["first"][None, :] == data["asked"][:, None]
    kinds = {
        "exact": [(w > 0) & named for w in walks],
        "u8": [(np.mod(w, 256.0) > 0) & named for w in walks],
        "unmasked": [w > 0 for w in walks],
    }
    out = {}
    for kind, by_step in kinds.items():
        rings = [[np.flatnonzero(step[q]) for step in by_step] for q in range(data["starts"].size)]
        out[kind] = {"rings": rings, "ball": [nearest(r) for r in rings]}
    ref = {"rings": out["exact"]["rings"], "ball": out["exact"]["ball"], "limit": limit}
    # what each control would have answered: the first `limit` of its own ball
    ref["control_answers"] = {
        kind: [ball[0][:limit].tolist() for ball in out[kind]["ball"]] for kind in ("u8", "unmasked")
    }
    ref["start_in_ball"] = int(sum(int(s) in set(b[0].tolist()) for s, b in zip(data["starts"], ref["ball"])))
    # the pool entries with a ring that the u8 control gets wrong as a WHOLE set: what the loader's probe compares
    differ = [any(a.size != b.size for a, b in zip(x, y)) for x, y in zip(out["exact"]["rings"], out["u8"]["rings"])]
    ref["u8_rings_differ"] = {"pool": int(sum(differ)), "probed": int(sum(differ[q] for q in probe_entries(data)))}
    return ref


# ------------------------------------------------------------------ load
def load(ds, cfg: dict, data: dict, execute_ok) -> dict:
    """`graph_filtered_count`'s load: persons, their count read back, the
    configuration's `ask_before_edges`, the edges, and that kind's probe,
    which asks `load.probe` (here the three rings as three fields) of the
    pool entry whose start person knows the fewest and holds
    `dispatch.submitted` to the statement's `dispatches`: a program that
    serves the rings by expanding the chains is refused there, in that
    kind's words. Then this kind's own: the whole rings of PROBES entries,
    a statement at a time (probe_rings) and from SESSIONS WebSocket
    sessions at once (probe_sessions)."""
    loaded = base.load(ds, cfg, data, execute_ok)
    probe_rings(ds, cfg, data, execute_ok)
    return {**loaded, "probe_sessions": probe_sessions(ds, cfg, data)}


def probe_entries(data: dict) -> list:
    """The pool entries whose rings the probe compares, in order: the one
    whose start person knows the fewest (the cheapest for a program that
    expands the chains), then those that ask for the commonest names (the
    largest rings: thousands of persons each, where a person lost to a
    narrow type or a wrong bit is likeliest), PROBES in all."""
    degree = np.bincount(data["pairs"][:, 0], minlength=data["nodes"])
    chosen = [int(np.argmin(degree[data["starts"]]))] + np.argsort(data["asked"], kind="stable").tolist()
    return list(dict.fromkeys(chosen))[:PROBES]


def rings_by_steps(data: dict, q: int, hops: int) -> list:
    """Pool entry q's rings (ascending ids each) by boolean steps over the
    edge list: a second plain way, beside the reference's products."""
    pairs, named = data["pairs"], data["first"] == data["asked"][q]
    frontier = np.zeros(data["nodes"], dtype=bool)
    frontier[data["starts"][q]] = True
    rings = []
    for _ in range(hops):
        # one step: the far ends of the records that leave the frontier
        reached = np.zeros(data["nodes"], dtype=bool)
        reached[pairs[frontier[pairs[:, 0]], 1]] = True
        frontier = reached
        rings.append(np.flatnonzero(frontier & named).tolist())
    return rings


def ring_faults(cfg: dict, entry: dict, row, want: list) -> list:
    """What is wrong with the probe statement's reply `row` for `entry`:
    each of its fields against the ring it names, as whole sets."""
    row = row[0] if isinstance(row, list) else row
    faults = []
    for field, ring in zip(cfg["load"]["probe_fields"], want):
        got = sorted(int(t.id) for t in row[field])
        if got != ring:
            faults.append(f"ring {field} of the loader's probe {entry} is not the reference's: {len(got)} "
                          f"persons for {len(ring)}, {len(set(got) ^ set(ring))} in one and not the other")
    return faults


def probe_rings(ds, cfg: dict, data: dict, execute_ok) -> None:
    """Each ring of each probed entry as a whole set against NumPy's
    (rings_by_steps), and one dispatch a statement."""
    statement, entries = cfg["statements"][cfg["load"]["probe"]], pool(cfg, data)
    for q in probe_entries(data):
        before = ds.dispatch.stats()["submitted"]
        out = execute_ok(ds, statement["sql"], {statement["bind"]: entries[q]})
        made = ds.dispatch.stats()["submitted"] - before
        if made != statement["dispatches"]:
            raise RuntimeError(f"{made} device dispatches for the loader's probe {entries[q]} "
                               f"where the statement makes {statement['dispatches']}")
        faults = ring_faults(cfg, entries[q], out[-1]["result"], rings_by_steps(data, q, int(cfg["hops"])))
        if faults:
            raise RuntimeError(faults[0])


def probe_sessions(ds, cfg: dict, data: dict) -> dict:
    """The probe's statement as the window's traffic meets the program:
    SESSIONS WebSocket sessions in a closed loop, each asking every probed
    entry from a place of its own in the list, so that statements of
    different starts and names ride one dispatch, several lanes wide.
    probe_rings asks a statement at a time: a batch of one, seven lanes of
    padding. A fault between lanes (a rider's mask on another's frontier, a
    ring handed to the wrong rider) shows in a whole ring only here; the
    window's check sees the 20 nearest. Every reply's rings are compared as
    whole sets, `dispatch.submitted` has to rise by one a statement, and
    some dispatch has to have carried more than one. Served by a listener
    of its own over the run's datastore (the harness hands the loader none),
    closed when the probe ends. Returns the statements asked and how wide
    their dispatches were, for the run's `ingest` line."""
    from surrealdb_tpu import Surreal
    from surrealdb_tpu.net.server import Server

    statement, entries, chosen = cfg["statements"][cfg["load"]["probe"]], pool(cfg, data), probe_entries(data)
    want = {q: rings_by_steps(data, q, int(cfg["hops"])) for q in chosen}
    faults: list = []

    def session(i: int) -> None:
        try:
            client = Surreal(f"ws://{srv.host}:{srv.port}/rpc")
            try:
                client.use(cfg["ns"], cfg["db"])
                at = i * len(chosen) // SESSIONS
                for q in chosen[at:] + chosen[:at]:
                    out = client.query(statement["sql"], {statement["bind"]: entries[q]})
                    if any(r.get("status") != "OK" for r in out):
                        faults.append(f"the loader's probe {entries[q]} failed over the WebSocket: {str(out)[:300]}")
                    else:
                        faults.extend(ring_faults(cfg, entries[q], out[-1]["result"], want[q]))
            finally:
                client.close()
        except Exception as e:  # noqa: BLE001: a session's failure is the probe's, raised below
            faults.append(f"a session of the loader's probe failed: {e!r}")

    srv = Server(ds, "127.0.0.1", 0, auth_enabled=False).start_background()
    try:
        before, w0 = ds.dispatch.stats()["submitted"], ds.dispatch.width_distribution()
        threads = [threading.Thread(target=session, args=(i,), name=f"probe-session-{i}") for i in range(SESSIONS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        made, w1 = ds.dispatch.stats()["submitted"] - before, ds.dispatch.width_distribution()
    finally:
        srv.shutdown()
    asked = SESSIONS * len(chosen)
    widths = {w: n - w0.get(w, 0) for w, n in sorted(w1.items()) if n != w0.get(w, 0)}
    if faults:
        raise RuntimeError(f"{len(faults)} faults in {asked} statements from {SESSIONS} sessions at once; the first: {faults[0]}")
    if made != asked * statement["dispatches"]:
        raise RuntimeError(f"{made} device dispatches for {asked} statements of the loader's probe from "
                           f"{SESSIONS} sessions where each makes {statement['dispatches']}")
    if max(widths, default=0) < 2:
        raise RuntimeError(f"no dispatch of the loader's {asked} probe statements from {SESSIONS} sessions "
                           f"carried more than one of them: widths {widths}")
    return {"statements": asked, "widths": {str(w): n for w, n in widths.items()}}


# ------------------------------------------------------------------ check
NUMBERS = ("wrong_ids", "duplicates", "ring_violations", "short_answers")


def judge(ids: list, ball: tuple, limit: int) -> dict:
    """One answer against its pool entry's ball, as the four numbers of
    `correct`: returned ids outside the three rings, ids returned twice,
    whether the answer breaks IC1's order of distance (a person after a
    farther one, or a ring before the last returned one not wholly there),
    and whether it is not min(limit, persons in the ball) long."""
    every, dist = ball
    dist_of = dict(zip(every.tolist(), dist.tolist()))
    ds = [dist_of[i] for i in ids if i in dist_of]
    broken = any(a > b for a, b in zip(ds, ds[1:]))
    if ds and not broken:
        nearer = sum(1 for i in set(ids) if dist_of.get(i, ds[-1]) < ds[-1])
        broken = nearer != int((dist < ds[-1]).sum())
    return {
        "wrong_ids": len(ids) - len(ds),
        "duplicates": len(ids) - len(set(ids)),
        "ring_violations": int(broken),
        "short_answers": int(len(ids) != min(limit, every.size)),
    }


def check(cfg: dict, ref: dict, records: list) -> dict:
    """Every answer of the window against the reference; beside it, what the
    same judge says of the answers each control would have given to the
    window's requests (a control's answer to a pool entry is one answer,
    judged once and counted a request)."""
    total = dict.fromkeys(NUMBERS, 0)
    control = {kind: dict.fromkeys(NUMBERS, 0) for kind in ref["control_answers"]}
    judged: dict = {}
    compared = rows = 0
    for r in records:
        if r["status"] != "OK":
            continue
        q = r["q"]
        compared += 1
        rows += len(r["ids"])
        for name, v in judge(r["ids"], ref["ball"][q], ref["limit"]).items():
            total[name] += v
        if q not in judged:
            judged[q] = {kind: judge(answers[q], ref["ball"][q], ref["limit"])
                         for kind, answers in ref["control_answers"].items()}
        for kind, verdict in judged[q].items():
            for name, v in verdict.items():
                control[kind][name] += v
    return {
        "numbers": [[name, total[name] if compared else 1, "<=", cfg["correct"][name + "_max"]] for name in NUMBERS],
        "control": {**{f"{name}_{kind}": v for kind, by in control.items() for name, v in by.items()},
                    "pool_entries_with_a_whole_ring_wrong_u8": ref["u8_rings_differ"]["pool"],
                    "probed_entries_with_a_whole_ring_wrong_u8": ref["u8_rings_differ"]["probed"]},
        "metrics": {},
        "compared": {"answers": compared, "rows": rows,
                     "pool_entries_whose_start_person_is_in_its_ball": ref["start_in_ball"]},
    }
