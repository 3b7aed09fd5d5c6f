"""The sparse count's path array has the slots its paths need (ISSUE 34):
`csrc` pads to a sixteenth of the power of two above the paths, not to the
power of two. The rule, the same int32 on operands whose slot count is no
power of two or holds no pad slot at all (composed and record-level, bare
and with end weights, at 8 and 16 lanes), the compiled shape a RELATE keeps
inside a quantum and changes across it, the warm-up and the audit shape, and
the `paths` / `slots` labels of `graph_csc_build`. References are int64
NumPy walks over the edge list and the dense count form."""

import re

import numpy as np
import pytest

from surrealdb_tpu import cnf, compile_log, key as keys, telemetry, tracing
from surrealdb_tpu.dbs.session import Session
from surrealdb_tpu.idx import graph_csr
from surrealdb_tpu.sql.value import Thing
from surrealdb_tpu.utils.num import next_pow2, path_slots
from test_graph_count_lanes import serve_batch as serve_mirrors
from test_graph_dense_exact import DB, NS, forms, mirrors_of, walk_count
from test_graph_filtered_count import BARE, BY_NAME, N, ask, person, serve_batch, walk_ending


@pytest.fixture(autouse=True)
def fresh_counters(monkeypatch):
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    telemetry.reset()
    compile_log.reset()
    tracing.store_reset()
    yield
    tracing.store_reset()


# ------------------------------------------------------------------ the rule
@pytest.mark.parametrize("paths, slots", [(0, 1), (1, 1), (6, 8), (1024, 1024), (1025, 1152), (2**20, 2**20),
                                          (2**20 + 1, 1_179_648), (1_130_494, 1_179_648)])
def test_the_slot_count_follows_the_paths(paths, slots):
    assert path_slots(paths) == slots
    cptr, csrc = graph_csr._csc_arrays(np.zeros(paths, dtype=np.int32), np.zeros(paths, dtype=np.int32), 4)
    assert csrc.shape == (slots,) and csrc.dtype == np.int32 and (csrc[paths:] == 4).all()  # pad slots: the sentinel
    assert cptr.tolist() == [0, paths, paths, paths, paths]  # and past the last bin


@pytest.mark.parametrize("octave", range(0, 24))
def test_an_octave_has_eight_shapes_a_pad_of_an_eighth_at_most_and_no_step_down(octave):
    lo, hi = 2**octave, 2 ** (octave + 1)
    paths = np.unique(np.concatenate([np.linspace(lo + 1, hi, 4099).astype(np.int64), [lo + 1, max(hi - 1, lo + 1), hi]]))
    slots = np.asarray([path_slots(int(p)) for p in paths])
    assert (slots >= paths).all() and (np.diff(slots) >= 0).all() and path_slots(lo) == lo <= slots[0]
    if lo < 1024:  # under a 128-lane row a sixteenth: the power of two, as before
        assert (slots == hi).all() and next_pow2(lo + 1) == hi
        return
    assert (slots % 128 == 0).all() and ((slots - paths) * 8 <= paths).all()
    assert sorted(set(slots.tolist())) == [lo + k * lo // 8 for k in range(1, 9)]


# ------------------------------------------------------------------ the same int32
def random_edges(count: int, seed: int) -> np.ndarray:
    """`count` knows records among N persons, drawn with repeats: every
    person relates from and to someone (the first 2N cover both sides)."""
    rng = np.random.default_rng(seed)
    src = np.concatenate([np.arange(N), rng.integers(0, N, count - N)])
    dst = np.concatenate([rng.permutation(N), rng.integers(0, N, count - N)])
    return np.stack([src, dst], axis=1)


def bare_walk(edges: np.ndarray, start: int) -> int:
    return walk_ending([(int(a), int(b)) for a, b in edges], start, lambda p: True)


# 1,100 paths sit in 1,152 slots (2,048 before); 1,152 fill theirs to the last
OPERANDS = {"pad_52": 1100, "no_pad": 1152}


def serve(ds, monkeypatch, edges: np.ndarray):
    """The graph loaded as the filtered-count tests load theirs, counted by
    the sparse form until a test says otherwise."""
    monkeypatch.setattr(cnf, "GRAPH_PREWARM", False)
    monkeypatch.setattr(cnf, "TPU_GRAPH_COUNT_EDGES", 1)
    monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", N - 1)
    sess = Session.owner(NS, DB)
    ds.execute("DEFINE TABLE person SCHEMALESS; DEFINE TABLE knows SCHEMALESS", sess)
    ds.execute("INSERT INTO person $rows RETURN NONE", sess, {"rows": [person(i) for i in range(N)]})
    rows = [{"in": Thing("person", int(a)), "out": Thing("person", int(b))} for a, b in edges]
    (res,) = ds.execute("INSERT RELATION INTO knows $rows RETURN NONE", sess, {"rows": rows})
    assert res["status"] == "OK", res
    return sess


def composed(ds) -> dict:
    (op,) = ds.graph_mirrors._csc.values()
    return op


@pytest.mark.parametrize("riders", [1, 9], ids=["lanes8", "lanes16"])
@pytest.mark.parametrize("ending", ["bare", "weighted"])
@pytest.mark.parametrize("operand", sorted(OPERANDS))
def test_a_composed_operand_of_any_slot_count_counts_what_the_walk_and_the_dense_form_count(
        ds, monkeypatch, operand, ending, riders):
    paths = OPERANDS[operand]
    edges = random_edges(paths, seed=paths)
    sess = serve(ds, monkeypatch, edges)
    names = ["Gus", "Fay", "Eli"]  # the three commonest: a third, a third and a sixth of the persons
    bound = [{"p": (i * 37) % N, "fn": names[i % 3]} for i in range(riders + 1)]
    requests = [(BY_NAME if ending == "weighted" else BARE, b) for b in bound]
    pairs = [(int(a), int(b)) for a, b in edges]
    want = [walk_ending(pairs, b["p"], (lambda p, fn=b["fn"]: p["firstName"] == fn) if ending == "weighted"
                        else (lambda p: True)) for b in bound]
    assert max(want) > 0
    sparse, _ = serve_batch(ds, sess, monkeypatch, requests)
    assert [a[0] for a in sparse] == want and {a[1]["form"] for a in sparse} == {"csc"}
    assert {a[1].get("operand") for a in sparse} == {"composed"}
    csrc = np.asarray(composed(ds)["csrc"])
    assert csrc.shape == (1152,) and int((csrc == composed(ds)["n_pad"]).sum()) == 1152 - paths
    lanes = [s["labels"]["lanes"] for s in tracing.get_trace(f"rider-{riders}")["spans"] if s["name"] == "dispatch_launch"]
    assert lanes == ["8" if riders == 1 else "16"]
    tracing.store_reset()
    monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", 16384)
    dense, _ = serve_batch(ds, sess, monkeypatch, requests)
    assert [a[0] for a in dense] == want and {a[1]["form"] for a in dense} == {"dense"}


@pytest.mark.parametrize("riders", [1, 9], ids=["lanes8", "lanes16"])
@pytest.mark.parametrize("operand", sorted(OPERANDS))
def test_a_record_level_operand_of_any_slot_count_counts_what_the_walk_and_the_dense_form_count(
        monkeypatch, operand, riders):
    paths = OPERANDS[operand]
    edges = random_edges(paths, seed=paths + 1)
    seed_sets = [{(i * 37) % N: 1 + i, (i * 11 + 5) % N: 3} for i in range(riders + 1)]
    want = [walk_count(N, edges, s, 3) for s in seed_sets]
    gm, persons = mirrors_of(N, edges)
    dense, _ = serve_mirrors(gm, persons, seed_sets, 3)
    assert dense == want and forms() == {"dense": riders + 1}
    monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", N - 1)
    monkeypatch.setattr(gm, "_csc_pair", lambda *a, **k: None)
    records, _ = serve_mirrors(gm, persons, seed_sets, 3)
    assert records == want and forms() == {"dense": riders + 1, "csc": riders + 1}
    # each record has one near and one far end: both mirrors hold `paths` entries
    for tb, ft in (("person", "knows"), ("knows", "person")):
        cptr, csrc = gm.get(NS, DB, tb, keys.DIR_OUT, ft).device_csc()
        assert csrc.shape == (1152,) and int((np.asarray(csrc) == cptr.shape[0] - 1).sum()) == 1152 - paths
    lanes = {int(e["shape"].split("x")[0]) for e in compile_log.events() if e["subsystem"] == "graph_csc"}
    assert lanes == ({8} if riders == 1 else {8, 16})


# ------------------------------------------------------------------ shapes
def shape_key(op: dict, lanes: int = 8) -> tuple:
    """As a count of three pairs from one start person is served: the seed's
    row of the operator at the operator's row pad, and one swept hop."""
    hop = ((op["cptr"], op["csrc"]),)
    return graph_csr._csc_shape_key(lanes, op["row_pad"], op["n_pad"], (hop,), ((op["indptr"],),))


def served_compiles() -> list:
    return [e["shape"] for e in compile_log.events() if e["mode"] != "prewarm"]


@pytest.mark.parametrize("start, relates, slots", [(1100, 52, [1152]), (1150, 3, [1152, 1280]), (1152, 1, [1152, 1280]),
                                                   (2040, 9, [2048, 2304])],
                         ids=["inside", "across", "off_the_boundary", "across_a_power_of_two"])
def test_a_relate_inside_a_quantum_keeps_the_compiled_shape_and_one_across_it_changes_csrc_alone(
        ds, monkeypatch, start, relates, slots):
    edges = random_edges(start, seed=start)
    sess = serve(ds, monkeypatch, edges)
    assert ask(ds, sess, BARE, {"p": 0}, "first")[0] == bare_walk(edges, 0)
    keys_seen = [shape_key(composed(ds))]
    for i in range(relates):
        (res,) = ds.execute(f"RELATE person:{i}->knows->person:{i + 1}", sess)
        assert res["status"] == "OK", res
        edges = np.concatenate([edges, [(i, i + 1)]])
        assert ask(ds, sess, BARE, {"p": 0}, f"after-{i}")[0] == bare_walk(edges, 0)
        keys_seen.append(shape_key(composed(ds)))
    distinct = sorted(set(keys_seen), key=keys_seen.index)
    # (lanes, row pad, n_cap, (cptr, csrc) a hop, (indptr,)): only the csrc entries may move (these RELATEs
    # lengthen no row past the power of two over the longest)
    assert [k[3] for k in distinct] == [(513, s) for s in slots]
    (rest,) = {k[:3] + k[4:] for k in distinct}
    assert rest == (8, composed(ds)["row_pad"], 512, (513,)) and rest[1] < next_pow2(cnf.TPU_GRAPH_FRONTIER_PAD)
    assert keys_seen == sorted(keys_seen)  # and never back
    # what the served counts compiled: one program a slot count
    assert served_compiles() == ["x".join(str(s) for s in k) for k in distinct]


def test_the_warm_up_compiles_the_slot_count_the_served_count_asks_for(monkeypatch):
    monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", 1)
    edges = random_edges(1100, seed=3)
    gm, persons = mirrors_of(N, edges)
    gm.warm_count_kernels(NS, DB)
    warmed = compile_log.events()
    assert warmed and {e["mode"] for e in warmed} == {"prewarm"} and {e["subsystem"] for e in warmed} == {"graph_csc"}
    assert telemetry.counters_matching("prewarm_errors") == {}
    # a key is lanes x frontier x n_cap x (cptr, csrc a hop) x (indptr,): every hop of every program, the
    # composed person pair's and the record-level knows->person->knows pair's, sweeps 1,152 slots, none 2,048
    swept = [int(s) for e in warmed for s in re.findall(r"\d+", e["shape"].split("x")[3])[1::2]]
    assert swept and set(swept) == {1152}
    for riders in (1, 9):
        seed_sets = [{i % N: 1 + i} for i in range(riders + 1)]
        got, _ = serve_mirrors(gm, persons, seed_sets, 3)
        assert got == [walk_count(N, edges, s, 3) for s in seed_sets]
    assert compile_log.events() == warmed  # served from what the warm-up compiled


def test_graftcheck_audits_one_slot_count_that_is_no_power_of_two():
    """Lowered with the contract's other shapes by tests/test_graph_count_lanes.py; here, that it is the rule's."""
    from scripts.graftcheck import registry

    (contract,) = registry.resolve_contracts(["graph_csc"])
    (shape,) = [s for s in contract["shapes"] if "paths" in s]
    assert path_slots(shape["paths"]) == 1152 and shape["lanes"] == 8
    _, (csc_hops, *_) = contract["build"](shape)
    assert [tuple(a.shape for a in pair) for hop in csc_hops for pair in hop] == [((257,), (1152,))]


# ------------------------------------------------------------------ the labels
def test_graph_csc_build_says_the_paths_and_the_slots_of_both_operands(ds, monkeypatch):
    edges = random_edges(1100, seed=4)
    sess = serve(ds, monkeypatch, edges)
    with tracing.request("count", trace_id="composed"):
        (res,) = ds.execute(BARE, sess, {"q": {"p": 0}})
    assert res["status"] == "OK", res
    (build,) = [s for s in tracing.get_trace("composed")["spans"] if s["name"] == "graph_csc_build"]
    assert (build["labels"]["paths"], build["labels"]["slots"]) == ("1100", "1152")
    assert int(build["labels"]["bytes"]) == 4 * (1152 + 513 + 513)
    m = ds.graph_mirrors.get(NS, DB, "person", keys.DIR_OUT, "knows")
    with tracing.request("records", trace_id="records"):
        cptr, csrc = m.device_csc()
    (build,) = [s for s in tracing.get_trace("records")["spans"] if s["name"] == "graph_csc_build"]
    assert (build["labels"]["paths"], build["labels"]["slots"]) == ("1100", "1152") and csrc.shape == (1152,)
    assert int(build["labels"]["bytes"]) == cptr.nbytes + csrc.nbytes
