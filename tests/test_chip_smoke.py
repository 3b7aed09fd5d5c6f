"""chip_smoke.py rehearsed on the CPU backend, and the compile-cache rule.

The smoke's load, query and check functions run here at 8,192 x 768 rows
and a graph just large enough for the 3-hop count chain to cross
cnf.TPU_GRAPH_COUNT_EDGES (500 nodes, mean out-degree 40). The virtual
8-device mesh makes the served strategies the `-sharded` ones. The device
check is the one thing this rehearsal cannot pass, and it must refuse.
"""

import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

SMALL = chip_smoke.Sizes(
    rows=8192, exact_rows=8192, nodes=500, edges=20_000, warm=4, clients=8
)


@pytest.fixture(scope="module")
def rehearsal():
    """One full run of every phase; returns the engine's own account."""
    from surrealdb_tpu import bg, compile_log, telemetry

    telemetry.reset()
    compile_log.reset()
    bg.reset()
    info = {"platform": "cpu", "device_kind": "cpu", "device_count": 8}
    return chip_smoke.run(3, SMALL, info)


def test_rehearsal_passes_every_check_but_the_device_ones(rehearsal):
    # run() already compared every kNN answer with the NumPy top-10 and
    # every count with the NumPy path count, and raised on a mismatch
    chip_smoke.check_engine(rehearsal)
    served = rehearsal["strategies"]
    assert any(s.startswith("ivf") for s in served)
    assert any(s.startswith("exact-") for s in served)
    assert not any(s.endswith("-host") for s in served)
    assert rehearsal["dispatch"]["dispatches"] > 0
    assert any(
        e["subsystem"].startswith("graph_") for e in rehearsal["compile_events"]
    )


def test_device_check_refuses_a_cpu_backend(rehearsal, capsys):
    with pytest.raises(chip_smoke.SmokeFailure, match="not 'tpu'"):
        chip_smoke.require_tpu("cpu", chip_smoke.ROWS)
    # the CPU backend reports no memory_stats: the corpus cannot be shown
    # to sit on the device
    with pytest.raises(chip_smoke.SmokeFailure, match="bytes_in_use"):
        chip_smoke.check_device_memory(rehearsal, 8192 * chip_smoke.DIM * 2)
    # main() stops at the device check: non-zero, and no result on stdout
    assert chip_smoke.main(["--rows", "8192"]) != 0
    assert capsys.readouterr().out == ""


def test_sizes_are_held_on_the_chip():
    """On a chip only `item` may be cut, and never below 262,144 rows; the
    sizes that ran are stated (the `passed` line carries them)."""
    chip_smoke.require_tpu("tpu", chip_smoke.MIN_ROWS)
    with pytest.raises(chip_smoke.SmokeFailure, match="below 262144"):
        chip_smoke.require_tpu("tpu", 8192)
    assert chip_smoke.Sizes().stated() == {
        "rows": 1_000_000, "dim": 768, "exact_rows": 262_144,
        "nodes": 10_000, "edges": 1_000_000, "rows_cut": None,
    }
    assert chip_smoke.Sizes(rows=524_288).stated()["rows_cut"] == "524288 of 1000000"
    # nothing but the seed and the row cut is reachable from the command line
    with pytest.raises(SystemExit):
        chip_smoke.parse_args(["--edges", "10"])


def test_a_passing_run_ends_on_the_ok_line(monkeypatch, capsys):
    """main() past its checks: the last stdout line is one JSON object with
    exactly `ok` and `device` ({platform, kind, count}) and nothing beside
    them; the sizes are on the `passed` line before it."""
    import json

    import jax

    monkeypatch.setattr(chip_smoke, "require_tpu", lambda backend, rows: None)
    monkeypatch.setattr(chip_smoke, "run", lambda seed, sizes, info: {})
    monkeypatch.setattr(chip_smoke, "check_device_memory", lambda obs, nbytes: None)
    assert chip_smoke.main(["--rows", "524288"]) == 0
    *_, passed, last = capsys.readouterr().out.splitlines()
    dev = jax.devices()[0]
    assert json.loads(last) == {
        "ok": True,
        "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }
    assert json.loads(passed)["size"]["rows_cut"] == "524288 of 1000000"


def _plant(obs, **changes):
    planted = copy.deepcopy(obs)
    for path, value in changes.items():
        node = planted
        *parents, leaf = path.split("__")
        for p in parents:
            node = node[p]
        node[leaf] = value
    return planted


@pytest.mark.parametrize(
    "changes, message",
    [
        ({"strategies__ivf-host": 1}, "ivf-host"),
        ({"strategies__exact-host": 3}, "exact-host"),
        ({"dispatch__retries": 1}, "dispatch.retries"),
        ({"dispatch__splits": 2}, "dispatch.splits"),
        ({"dispatch__failures": 1}, "dispatch.failures"),
        ({"dispatch__dispatches": 0}, "dispatches is 0"),
        ({"prewarm_errors": 1.0}, "prewarm_errors"),
        ({"statement_errors": 1.0}, "statement_errors"),
        ({"tpu_disable": True}, "TPU_DISABLE"),
        ({"compile_events": []}, "no compile event"),
        (
            {"bg_bad": [{"kind": "ivf_train", "target": "item.item_emb",
                         "state": "failed", "stalled": False,
                         "error": "XlaRuntimeError: RESOURCE_EXHAUSTED"}]},
            "ivf_train",
        ),
    ],
)
def test_a_planted_fault_fails_the_checks(rehearsal, changes, message):
    with pytest.raises(chip_smoke.SmokeFailure, match=message):
        chip_smoke.check_engine(_plant(rehearsal, **changes))


def test_a_planted_compile_error_fails_the_checks(rehearsal):
    planted = copy.deepcopy(rehearsal)
    planted["compile_events"][0]["error"] = "XlaRuntimeError"
    with pytest.raises(chip_smoke.SmokeFailure, match="failed: XlaRuntimeError"):
        chip_smoke.check_engine(planted)


def test_a_count_served_on_the_host_fails_the_checks(monkeypatch):
    """A 3-hop count that fell back to the host walk still equals NumPy;
    the smoke must see that no device dispatch served it."""
    import numpy as np

    from surrealdb_tpu import cnf
    from surrealdb_tpu.net.server import serve

    chip_smoke.check_counts_dispatched(9, 9)
    with pytest.raises(chip_smoke.SmokeFailure, match="served on the host"):
        chip_smoke.check_counts_dispatched(9, 0)

    # the real thing: neither the chain's work estimate nor a hop's
    # frontier ever reaches its device threshold
    monkeypatch.setattr(cnf, "TPU_GRAPH_COUNT_EDGES", 1 << 40)
    monkeypatch.setattr(cnf, "TPU_GRAPH_ONDEVICE_THRESHOLD", 1 << 40)
    rng = np.random.default_rng(5)
    pairs = rng.integers(0, SMALL.nodes, size=(SMALL.edges, 2))
    srv = serve("memory", port=0, auth_enabled=False).start_background()
    wire = chip_smoke.Wire(srv)
    try:
        with pytest.raises(chip_smoke.SmokeFailure, match="served on the host"):
            chip_smoke.phase_graph(
                srv, wire, lambda phase, **f: None, SMALL.nodes, pairs, rng, 2
            )
    finally:
        wire.close()
        srv.shutdown()
        srv.ds.close()


def test_an_err_row_fails_the_checks():
    ok = {"status": "OK", "result": [], "time": "1ms"}
    chip_smoke.check_rows_ok([ok], "SELECT 1")
    err = {"status": "ERR", "result": "device launch failed", "time": "1ms"}
    with pytest.raises(chip_smoke.SmokeFailure, match="ERR"):
        chip_smoke.check_rows_ok([ok, err], "SELECT 1")


def test_references_agree_with_brute_force():
    import numpy as np

    rng = np.random.default_rng(0)
    pairs = rng.integers(0, 30, size=(200, 2))
    adj = np.zeros((30, 30), dtype=np.int64)
    np.add.at(adj, (pairs[:, 0], pairs[:, 1]), 1)
    a3 = np.linalg.matrix_power(adj, 3)
    want = chip_smoke.path_counts(pairs, 30, [0, 7])
    for s in (0, 7):
        assert want[s] == {1: int(adj[s].sum()), 3: int(a3[s].sum())}
    corpus = rng.standard_normal((500, chip_smoke.DIM)).astype(np.float32)
    qs = corpus[:4] + 0.01
    d = ((qs[:, None, :] - corpus[None, :, :]) ** 2).sum(-1)
    assert (chip_smoke.exact_topk(corpus, qs, 5) == np.argsort(d, axis=1)[:, :5]).all()


def test_compile_cache_rule(monkeypatch):
    """Env set -> the code sets nothing; env unset -> <checkout>/.jax_cache."""
    import jax

    from surrealdb_tpu import cnf, device

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setattr(cnf, "JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        jax.config.update("jax_compilation_cache_dir", None)
        assert device.compile_cache_dir() is None
        device.configure_compile_cache()
        assert jax.config.jax_compilation_cache_dir is None

        monkeypatch.setattr(cnf, "JAX_COMPILATION_CACHE_DIR", None)
        assert device.compile_cache_dir() == os.path.join(ROOT, ".jax_cache")
        device.configure_compile_cache()
        assert jax.config.jax_compilation_cache_dir == os.path.join(ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
