"""The plain references against brute force, and the controls against the limits."""

import json
import os

import numpy as np
import pytest

from harness import manifest as mf

DEPS = mf.load_modules(mf.BENCH_DIR, "deployments", "KIND")
VEC, GRAPH = DEPS["vector_knn"], DEPS["graph_count"]


def cfg_of(name):
    return mf.load_json(mf.BENCH_DIR, "configs", name)


def test_generators_repeat_from_the_seed_and_take_large_seeds():
    cfg = cfg_of("vec1m768")
    sizes = {"rows": 70_000, "pool": 8, "centres": 16}
    a = VEC.generate(cfg, sizes, 2**31 + 11)
    b = VEC.generate(cfg, sizes, 2**31 + 11)
    c = VEC.generate(cfg, sizes, 2**31 + 12)
    assert a["corpus"].dtype == np.float32 and a["corpus"].shape == (70_000, 768)
    assert (a["corpus"] == b["corpus"]).all() and (a["queries"] == b["queries"]).all()
    # the corpus is the configuration's fixed data set; the run's seed draws the queries
    assert (a["corpus"] == c["corpus"]).all() and not (a["queries"] == c["queries"]).all()


def test_the_knows_graph_is_symmetric_simple_and_skewed():
    cfg = cfg_of("snbsf1")
    g = GRAPH.generate(cfg, {"nodes": 2000, "pairs": 30_000, "pool": 64}, 2**31 + 11)
    h = GRAPH.generate(cfg, {"nodes": 2000, "pairs": 30_000, "pool": 64}, 2**31 + 11)
    assert (g["pairs"] == h["pairs"]).all() and (g["starts"] == h["starts"]).all()
    a, b = g["pairs"][:, 0], g["pairs"][:, 1]
    assert g["pairs"].shape == (60_000, 2) and g["pairs"].max() < 2000 and (a != b).all()
    keys = a * 2000 + b
    assert np.unique(keys).size == 60_000  # no pair twice
    assert set(keys.tolist()) == set((b * 2000 + a).tolist())  # each pair both ways
    deg = np.bincount(a, minlength=2000)
    assert deg.mean() == 30.0 and np.median(deg) < 0.7 * deg.mean() and deg.max() > 8 * deg.mean()
    assert (deg[g["starts"]] > 0).all()  # every start person knows someone


def test_exact_neighbours_agree_with_brute_force():
    rng = np.random.default_rng(0)
    corpus = rng.standard_normal((70_000, 32)).astype(np.float32)  # three blocks: both merge paths run
    qs = corpus[:6] + 0.01
    got = VEC.exact_neighbours(corpus, qs, 8)
    d = ((qs[:, None, :].astype(np.float64) - corpus[None, :, :]) ** 2).sum(-1)
    want = np.argsort(d, axis=1)[:, :8]
    assert (np.sort(got, axis=1) == np.sort(want, axis=1)).all()


def test_path_counts_agree_with_the_matrix_power():
    rng = np.random.default_rng(0)
    pairs = rng.integers(0, 30, size=(200, 2))
    adj = np.zeros((30, 30), dtype=np.int64)
    np.add.at(adj, (pairs[:, 0], pairs[:, 1]), 1)
    starts = np.array([0, 7, 7, 29])
    assert (GRAPH.path_counts(pairs, 30, starts, 3) == np.linalg.matrix_power(adj, 3)[starts].sum(axis=1)).all()
    assert (GRAPH.path_counts(pairs, 30, starts, 1) == adj[starts].sum(axis=1)).all()
    assert (GRAPH.path_counts(pairs, 30, starts, 2) == (adj @ adj)[starts].sum(axis=1)).all()


def _vec_records(ref, d2, k=10, n=64):
    return [
        {"status": "OK", "q": q, "ids": [int(i) for i in ref["ids"][q, :k]],
         "values": {"d": [float(x) for x in np.sqrt(d2[q, :k])]}}
        for q in range(n)
    ]


@pytest.fixture(scope="module")
def vec_ref():
    cfg = cfg_of("vec1m768")
    data = VEC.generate(cfg, {"rows": 65_536, "pool": 64, "centres": 256}, 5)
    return cfg, VEC.reference(cfg, data)


def test_vector_check_passes_the_reference_itself(vec_ref):
    cfg, ref = vec_ref
    out = VEC.check(cfg, ref, _vec_records(ref, ref["d2"]))
    assert all((v >= lim) if rel == ">=" else (v <= lim) for _, v, rel, lim in out["numbers"])
    assert out["metrics"]["recall_at_10"] == 1.0


def test_vector_control_int8_is_not_correct(vec_ref):
    """The control: the reference in the program's place, its corpus held in
    int8. Its distances must fail the limit the configuration's file sets."""
    cfg, ref = vec_ref
    out = VEC.check(cfg, ref, _vec_records(ref, ref["d2_control"]))
    by_name = {n: (v, rel, lim) for n, v, rel, lim in out["numbers"]}
    v, _, lim = by_name["distance_rms_rel"]
    assert v > lim, (v, lim)
    assert out["control"]["distance_rms_rel_int8"] == pytest.approx(v)


def test_vector_check_fails_low_recall_and_strange_ids(vec_ref):
    cfg, ref = vec_ref
    recs = _vec_records(ref, ref["d2"])
    for r in recs:
        r["ids"] = r["ids"][:8] + [10**9, 10**9 + 1]
    by_name = {n: v for n, v, _, _ in VEC.check(cfg, ref, recs)["numbers"]}
    assert by_name["recall_at_10"] == pytest.approx(0.8) and by_name["unmatched_id_share"] == pytest.approx(0.2)


def test_graph_control_bf16_is_not_correct():
    cfg = cfg_of("snbsf1")
    data = GRAPH.generate(cfg, {"nodes": 300, "pairs": 4500, "pool": 64}, 5)
    ref = GRAPH.reference(cfg, data)
    sound = [{"status": "OK", "q": q, "values": {"c": [int(ref["counts"][q])]}} for q in range(64)]
    control = [{"status": "OK", "q": q, "values": {"c": [int(ref["counts_control"][q])]}} for q in range(64)]
    assert GRAPH.check(cfg, ref, sound)["numbers"][0][1] == 0
    assert GRAPH.check(cfg, ref, control)["numbers"][0][1] > 32
    assert GRAPH.as_bfloat16(np.array([256, 257, 1_000_001])).tolist() == [256, 256, 999_424]
