"""What the observability planes expose, held from outside: the planes
observe (stats, accounting, telemetry, tracing, compile_log, events,
bundle, bg, profiler) and nothing proposes. A plane that is taken out
leaves by every door at once (route on both front ends, cluster op,
capability target, INFO FOR ROOT, bundle section, background service),
and two planes that meter the same statement agree on what it was.
"""

import argparse
import base64
import http.client
import json
import os
import subprocess
import sys
from functools import partial

import pytest

from surrealdb_tpu import accounting, cnf, stats
from surrealdb_tpu.dbs.session import Session

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def ok(resp):
    assert resp["status"] == "OK", resp
    return resp["result"]


def _route(ds, monkeypatch, loop):
    from surrealdb_tpu.net.server import Server

    monkeypatch.setattr(cnf, "NET_LOOP", loop)
    ds.execute(
        "DEFINE USER nsu ON NAMESPACE PASSWORD 'pw' ROLES EDITOR;",
        Session.owner("test", None),
    )
    srv = Server(ds, port=0, auth_enabled=True).start_background()
    try:
        assert srv.loop_mode is loop
        c = http.client.HTTPConnection(srv.host, srv.port)
        answers = []
        for path in ("/advisor", "/no-such-plane"):
            c.request("GET", path, headers={
                "Authorization": "Basic " + base64.b64encode(b"nsu:pw").decode(),
                "surreal-ns": "test",
            })
            r = c.getresponse()
            answers.append((r.status, json.loads(r.read())))
        c.close()
    finally:
        srv.shutdown()
    assert answers[0] == answers[1] and answers[0][0] == 404, answers


def _cluster_op(ds, monkeypatch):
    from surrealdb_tpu.cluster import rpc

    assert "advisor" not in rpc._OPS
    gone = rpc.handle(ds, {"op": "advisor"})["error"]
    assert gone == rpc.handle(ds, {"op": "no-such-op"})["error"].replace(
        "no-such-op", "advisor"
    )


def _capability_target(ds, monkeypatch):
    from surrealdb_tpu.dbs.capabilities import HTTP_ROUTES, from_env_and_args
    from surrealdb_tpu.err import SurrealError

    assert "advisor" not in HTTP_ROUTES
    with pytest.raises(SurrealError, match="invalid http target 'advisor'"):
        from_env_and_args(argparse.Namespace(deny_http="advisor"))
    # a plane that stays is still a target
    assert not from_env_and_args(
        argparse.Namespace(deny_http="statements")
    ).allows_http_route("statements")


def _info_and_bundle(ds, monkeypatch):
    from surrealdb_tpu.bundle import SECTIONS, debug_bundle

    system = ok(ds.execute("INFO FOR ROOT", Session.owner())[0])["system"]
    assert set(system) == {
        "slow_queries", "errors", "traces", "statements", "tenants", "bundle",
    }
    assert "advisor" not in SECTIONS
    b = debug_bundle(ds, full_traces=0)
    assert set(SECTIONS) <= set(b) and "advisor" not in b


def _bg_services(ds, monkeypatch):
    # in a process of its own: the registry is process-wide, and other
    # tests' servers and SDK clients leave their services in it. The
    # group-commit flusher is spawned by a write and exits when it has
    # lingered idle, so whether it is live at one instant is not a fact
    # about the tree; every other service lives as long as its owner
    code = (
        "import json\n"
        "from surrealdb_tpu import bg\n"
        "from surrealdb_tpu.kvs.ds import Datastore\n"
        "from surrealdb_tpu.net.server import Server\n"
        "def live():\n"
        "    return sorted({t['kind'] for t in bg.snapshot()['live']\n"
        "                   if t['service'] and t['kind'] != 'group_commit'})\n"
        "ds = Datastore('memory')\n"
        "alone = live()\n"
        "srv = Server(ds, port=0).start_background()\n"
        "served = live()\n"
        "srv.shutdown(); ds.close()\n"
        "print(json.dumps({'alone': alone, 'served': served}))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    # spelled out, so the next plane that spawns a thread shows in a diff
    assert got["alone"] == ["profiler"]
    assert got["served"] == [
        "net_exec", "net_loop", "net_notify", "profiler", "tick",
    ]


@pytest.mark.parametrize(
    "door",
    [
        pytest.param(partial(_route, loop=False), id="route-threaded"),
        pytest.param(partial(_route, loop=True), id="route-loop"),
        pytest.param(_cluster_op, id="cluster-op"),
        pytest.param(_capability_target, id="capability-target"),
        pytest.param(_info_and_bundle, id="info-and-bundle"),
        pytest.param(_bg_services, id="bg-services"),
    ],
)
def test_no_advisor_behind_any_door(ds, monkeypatch, door):
    door(ds, monkeypatch)


@pytest.fixture()
def fresh_planes():
    """Both stores are process-wide: empty before, and left empty."""
    stats.reset()
    accounting.reset()
    yield
    stats.reset()
    accounting.reset()


def test_scan_heavy_fingerprint_reads_the_same_in_stats_and_accounting(
    ds, fresh_planes
):
    """A window of scans over an unindexed predicate: the statement store
    says what plan the shape ran (its mix dominated by a scan), the tenant
    store what it cost (rows scanned), both under ONE fingerprint."""
    s = Session.owner("t", "t")
    ok(ds.execute("DEFINE TABLE advt SCHEMALESS", s)[0])
    rows = [{"id": i, "val": int(i % 97)} for i in range(128)]
    ok(ds.execute("INSERT INTO advt $rows RETURN NONE", s, {"rows": rows})[0])
    calls = 4
    for _ in range(calls):
        ok(ds.execute("SELECT id FROM advt WHERE val > 50", s)[0])
    ent = next(
        e for e in stats.statements(limit=50)
        if e["kind"] == "SelectStatement" and "advt" in e["sql"]
    )
    assert ent["calls"] == calls and "cost" not in ent
    mix = ent["plan_mix"]
    scans = sum(
        n for k, n in mix.items()
        if k in ("row", "columnar-scan", "columnar-pipeline")
    )
    assert scans >= calls and scans * 2 > sum(mix.values()), mix
    tenant = next(
        t for t in accounting.top(limit=10, fp_limit=16)
        if (t["ns"], t["db"]) == ("t", "t")
    )
    drill = {e["fingerprint"]: e for e in tenant["by_fp"]}
    assert ent["fingerprint"] in drill, (ent["fingerprint"], drill)
    assert drill[ent["fingerprint"]]["rows_scanned"] >= calls * len(rows)
