"""Elastic cluster: membership change, background shard rebalance,
anti-entropy repair, and HLC last-writer-wins convergence (ISSUE 14 —
surrealdb_tpu/cluster/{membership,repair,hlc}.py).

The contracts under test:

- the HLC itself: monotonic mints, remote-stamp observation, total order;
- ring-range addressing: range owners == owners_of_key for every record;
- join/leave/replace: epoch bumps on every member, background migration
  streams the moving records (counted), reads stay byte-identical to a
  single node before/during/after, and the handoff window's dual-read
  never misses a record;
- the r12 degraded-write caveat CLOSED: a replica that missed an acked
  write while dead converges via read-repair or the anti-entropy sweep
  WITHOUT the record being rewritten, with counters proving which path;
- concurrent same-record UPDATEs on different replicas converge to the
  LWW winner;
- the new failpoint sites (cluster.hlc.stamp, cluster.migrate.stream,
  cluster.migrate.cutover, cluster.repair.sweep) arm through the standard
  spec and trip visibly;
- the new event kinds are registered and emitted; membership epoch reaches
  the bundle engine section, where a stale-epoch member reads behind its
  peers.
"""

import time

import pytest

import jax.numpy  # noqa: F401 — concurrent lazy first-import races otherwise

from surrealdb_tpu import cnf, events, faults, telemetry
from surrealdb_tpu import key as skeys
from surrealdb_tpu.cluster import ClusterConfig, attach, hlc
from surrealdb_tpu.cluster import membership as mship
from surrealdb_tpu.cluster import repair
from surrealdb_tpu.cluster.placement import HashRing, placement_key
from surrealdb_tpu.dbs.session import Session
from surrealdb_tpu.kvs.ds import Datastore
from surrealdb_tpu.net.server import Server, serve


def ok(resp):
    assert resp["status"] == "OK", resp
    return resp["result"]


def counter_sum(name):
    return sum(telemetry.counters_matching(name).values())


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# ------------------------------------------------------------------ harness
class Cluster:
    """N in-process nodes wired into one replicated hash ring, plus the
    single-node twin; kill/restart/spawn support for elasticity tests."""

    def __init__(self, n: int = 3, secret: str = "elastic-secret"):
        self.secret = secret
        self.servers = [
            serve("memory", port=0, auth_enabled=False).start_background()
            for _ in range(n)
        ]
        self.nodes = [
            {"id": f"n{i + 1}", "url": srv.url}
            for i, srv in enumerate(self.servers)
        ]
        self.datastores = [srv.httpd.RequestHandlerClass.ds for srv in self.servers]
        for i, ds in enumerate(self.datastores):
            attach(ds, ClusterConfig(self.nodes, f"n{i + 1}", secret=secret))
        self.ref = Datastore("memory")
        self.s = Session.owner("t", "t")
        self.rf = max(min(cnf.CLUSTER_RF, n), 1)
        self.by_id = {
            f"n{i + 1}": ds for i, ds in enumerate(self.datastores)
        }
        self._extra = []  # (server, ds) spawned by join tests

    @property
    def coord(self):
        return self.datastores[0]

    def both(self, sql, vars=None):
        a = self.ref.execute(sql, self.s, dict(vars) if vars else None)
        b = self.coord.execute(sql, self.s, dict(vars) if vars else None)
        assert [r["status"] for r in a] == [r["status"] for r in b], (sql, a, b)
        assert [r["result"] for r in a] == [r["result"] for r in b], (sql, a, b)
        return [r["result"] for r in b]

    def kill(self, i: int):
        self.servers[i].shutdown()
        # release the listening socket so restart() can rebind the port
        # (a plain shutdown leaves it open — the hang-shape chaos tests
        # want; elasticity tests want the process-died shape)
        self.servers[i].httpd.server_close()

    def restart(self, i: int):
        """Bring a killed node's HTTP server back on the SAME port with the
        SAME datastore (its in-memory shard survives — the stale-rejoin
        shape)."""
        old = self.servers[i]
        srv = Server(
            self.datastores[i], port=old.port, auth_enabled=False
        ).start_background()
        self.servers[i] = srv
        return srv

    def spawn(self, node_id: str):
        """A fresh empty node ready to join: its config lists the current
        membership plus itself."""
        srv = serve("memory", port=0, auth_enabled=False).start_background()
        ds = srv.httpd.RequestHandlerClass.ds
        node = {"id": node_id, "url": srv.url}
        attach(ds, ClusterConfig(self.nodes + [node], node_id, secret=self.secret))
        self._extra.append((srv, ds))
        self.by_id[node_id] = ds
        return node, ds

    def mark_up(self, node_id: str):
        """Short-circuit the probe pumps after a restart (tests must not
        wait out the probe backoff)."""
        for ds in list(self.by_id.values()):
            cl = getattr(ds, "cluster", None)
            if cl is not None and cl.client is not None:
                cl.client._mark(node_id, up=True)
                cl.client._breaker_success(node_id)

    def close(self):
        for srv in self.servers:
            try:
                srv.shutdown()
            except Exception:
                pass
        for srv, _ in self._extra:
            try:
                srv.shutdown()
            except Exception:
                pass
        for ds in self.datastores + [ds for _, ds in self._extra]:
            ds.close()
        self.ref.close()


@pytest.fixture()
def cluster2():
    saved = cnf.CLUSTER_RPC_TIMEOUT_SECS
    cnf.CLUSTER_RPC_TIMEOUT_SECS = 3.0
    c = Cluster(2)
    yield c
    c.close()
    cnf.CLUSTER_RPC_TIMEOUT_SECS = saved


@pytest.fixture()
def cluster3():
    saved = cnf.CLUSTER_RPC_TIMEOUT_SECS
    cnf.CLUSTER_RPC_TIMEOUT_SECS = 3.0
    c = Cluster(3)
    yield c
    c.close()
    cnf.CLUSTER_RPC_TIMEOUT_SECS = saved


def seed(c, n=30):
    c.both("DEFINE TABLE item SCHEMALESS")
    for i in range(n):
        c.both(f"CREATE item:{i} SET n = {i}, grp = {i % 3}")
    return n


# ================================================================== HLC
def test_hlc_monotonic_and_total_order():
    a = hlc.now("n1")
    b = hlc.now("n1")
    assert b > a
    # a regressing wall clock cannot mint a smaller stamp: observe a stamp
    # far in the future, the next mint lands at-or-after it
    future = (a[0] + 60_000, 7, "nX")
    hlc.observe(future)
    c = hlc.now("n1")
    assert c > future, (c, future)
    # encode/decode round trip; malformed stamps decode to None
    assert hlc.decode(hlc.encode(c)) == c
    assert hlc.decode(None) is None and hlc.decode([1, 2]) is None
    # wins(): present beats missing; two missing never win
    assert hlc.wins(c, None) and not hlc.wins(None, c)
    assert not hlc.wins(None, None)


def test_write_path_stamps_records(cluster2):
    c = cluster2
    c.both("DEFINE TABLE st SCHEMALESS")
    ok(c.coord.execute("CREATE st:1 SET v = 1", c.s)[0])
    ring = c.coord.cluster.ring
    holders = ring.owners_of("st", 1, c.rf)
    for nid in holders:
        ds = c.by_id[nid]
        txn = ds.transaction(False)
        try:
            meta = txn.get_record_meta("t", "t", "st", 1)
        finally:
            txn.cancel()
        assert meta is not None and hlc.decode(meta["hlc"]) is not None, (nid, meta)
        # each replica mints its OWN stamp (its own node id)
        assert hlc.decode(meta["hlc"])[2] == nid
    # the single-node twin stays stamp-free (zero overhead off-cluster)
    txn = c.ref.transaction(False)
    try:
        assert txn.get_record_meta("t", "t", "st", 1) is None
    finally:
        txn.cancel()


def test_delete_leaves_tombstone(cluster2):
    c = cluster2
    c.both("DEFINE TABLE tmb SCHEMALESS")
    ok(c.coord.execute("CREATE tmb:1 SET v = 1", c.s)[0])
    ok(c.coord.execute("DELETE tmb:1", c.s)[0])
    ring = c.coord.cluster.ring
    nid = ring.owners_of("tmb", 1, c.rf)[0]
    txn = c.by_id[nid].transaction(False)
    try:
        meta = txn.get_record_meta("t", "t", "tmb", 1)
        doc = txn.get_record("t", "t", "tmb", 1)
    finally:
        txn.cancel()
    assert doc is None and meta is not None and meta.get("dead") is True, meta


def test_hlc_stamp_failpoint_fails_write_pre_commit(cluster2):
    c = cluster2
    c.both("DEFINE TABLE fp SCHEMALESS")
    # armed everywhere: every replica's stamp fails, the statement errors
    faults.enable("cluster.hlc.stamp", "error")
    r = c.coord.execute("CREATE fp:1 SET v = 1", c.s)[0]
    assert r["status"] == "ERR" and "cluster.hlc.stamp" in str(r["result"]), r
    faults.disable("cluster.hlc.stamp")
    # the failed write landed NOWHERE (clean pre-commit failure)
    got = ok(c.coord.execute("SELECT VALUE v FROM fp", c.s)[0])
    assert got == []
    snap = faults.snapshot()
    assert snap["sites"]["cluster.hlc.stamp"]["trips"] >= 1
    # ONE trip (count=1): depending on which replica it lands on the
    # statement either errors (the reporter's stamp failed) or acks
    # degraded (a non-reporter copy diverged) — never a silent wrong answer
    faults.enable("cluster.hlc.stamp", "error", count=1)
    r = c.coord.execute("CREATE fp:2 SET v = 2", c.s)[0]
    assert r["status"] == "ERR" or r.get("degraded") is True, r


# ================================================================== ranges
def test_ring_range_owners_match_owner_walk():
    ring = HashRing(["a", "b", "c"], vnodes=16)
    for i in range(200):
        key = placement_key("tb", i)
        idx = ring.range_of_key(key)
        assert ring.range_owners(idx, 2) == ring.owners_of_key(key, 2), i
    # every range index is in bounds and covers the whole space
    assert ring.n_ranges() == len(ring._points)


# ================================================================== join
def test_join_streams_shards_and_serves_identically(cluster2):
    c = cluster2
    n = seed(c)
    node, ds3 = c.spawn("n3")
    rows0 = counter_sum("cluster_migration_rows")
    ev0 = events.last_seq()
    ch = mship.join(c.coord, node, wait=True, timeout=60)
    assert ch.epoch == 2
    # every member (including the joiner) agrees on the new epoch
    for nid, ds in c.by_id.items():
        assert ds.cluster.membership.epoch == 2, nid
        assert ds.cluster.membership.state == "stable", nid
    # migration actually moved rows, visible in the counter and the
    # migration progress object
    assert counter_sum("cluster_migration_rows") > rows0
    mig = c.coord.cluster.migration.view()
    assert mig["state"] == "done" and mig["rows_streamed"] > 0, mig
    # the joiner holds a real share and the merged read is byte-identical
    local3 = ok(ds3.execute_local("SELECT VALUE n FROM item", c.s)[0])
    assert len(local3) > 0
    c.both("SELECT VALUE n FROM item ORDER BY n")
    c.both("SELECT grp, count() FROM item GROUP BY grp ORDER BY grp")
    # timeline: join + migration events landed, kinds registered
    kinds = {e["kind"] for e in events.since(ev0)}
    assert "cluster.member_join" in kinds
    assert "cluster.migration_start" in kinds and "cluster.migration_done" in kinds
    # a post-join sweep finds the replicas already converged
    rep = repair.sweep_once(ds3)
    assert rep["repaired"] == 0 and not rep["errors"], rep


def test_reads_complete_during_handoff_window(cluster2):
    """Dual-read: with the window OPEN (prepared, nothing streamed yet) a
    scatter read still returns every record — the joiner holds nothing,
    the old owners still answer."""
    c = cluster2
    seed(c, 24)
    node, ds3 = c.spawn("n3")
    epoch = c.coord.cluster.membership.epoch + 1
    payload = {
        "nodes": c.nodes + [node], "epoch": epoch,
        "prev_nodes": c.nodes, "prev_epoch": epoch - 1, "phase": "prepare",
    }
    for ds in [c.coord, c.datastores[1], ds3]:
        mship.handle_update(ds, dict(payload))
    assert c.coord.cluster.membership.state == "migrating"
    # reads during the window: byte-identical, nothing missed
    c.both("SELECT VALUE n FROM item ORDER BY n")
    # writes during the window dual-write: the record lands on next-ring
    # owners too, so it survives the cutover without being streamed
    c.both("CREATE item:900 SET n = 900, grp = 0")
    got = c.both("SELECT VALUE n FROM item WHERE n = 900")
    assert got[0] == [900]
    # finish the change: stream + cutover
    for src in ("n1", "n2"):
        req = {"epoch": epoch, "live": ["n1", "n2", "n3"]}
        ds = c.by_id[src]
        mship.migrate_ranges(ds, req)
    for ds in [c.coord, c.datastores[1], ds3]:
        mship.handle_update(ds, {"phase": "commit", "epoch": epoch})
    assert c.coord.cluster.membership.epoch == epoch
    c.both("SELECT VALUE n FROM item ORDER BY n")


def test_leave_rehomes_ranges(cluster3):
    c = cluster3
    seed(c)
    ch = mship.leave(c.coord, "n3", wait=True, timeout=60)
    assert ch.epoch == 2
    assert c.coord.cluster.membership.view()["nodes"] == ["n1", "n2"]
    # every record still fully replicated across the survivors
    c.both("SELECT VALUE n FROM item ORDER BY n")
    rep = repair.sweep_once(c.coord)
    assert not rep["errors"], rep


def test_replace_dead_node_zero_wrong_answers(cluster3):
    """The recovery story: kill a member, join a replacement in ONE epoch;
    no read is ever wrong, acked writes survive, the replacement ends up
    holding a real share."""
    c = cluster3
    seed(c)
    want = ok(c.ref.execute("SELECT VALUE n FROM item ORDER BY n", c.s)[0])
    c.kill(1)  # n2 dies with its shard
    # a degraded write while n2 is down: acked by the live replicas
    r = c.coord.execute("UPDATE item:3 SET n = 303", c.s)[0]
    assert r["status"] == "OK", r
    want = sorted([x for x in want if x != 3] + [303])
    node, ds4 = c.spawn("n4")
    ch = mship.replace(c.coord, "n2", node, wait=True, timeout=60)
    assert ch.epoch == 2
    view = c.coord.cluster.membership.view()
    assert set(view["nodes"]) == {"n1", "n3", "n4"}
    got = ok(c.coord.execute("SELECT VALUE n FROM item ORDER BY n", c.s)[0])
    assert got == want, (got, want)
    assert len(ok(ds4.execute_local("SELECT VALUE n FROM item", c.s)[0])) > 0
    # the corpse is out of the transport: no more probes/calls to it
    assert "n2" not in c.coord.cluster.client.node_ids()


def test_migrate_stream_failpoint_aborts_and_is_retryable(cluster2):
    """A failed migration must not wedge the cluster mid-handoff: the
    prepared window rolls back on every member (abort broadcast), reads
    keep answering complete throughout, and the SAME change succeeds on
    retry under a fresh epoch."""
    c = cluster2
    seed(c, 20)
    node, ds3 = c.spawn("n3")
    faults.enable("cluster.migrate.stream", "error")
    with pytest.raises(mship.MembershipError):
        mship.join(c.coord, node, wait=True, timeout=60)
    faults.disable("cluster.migrate.stream")
    mig = c.coord.cluster.migration.view()
    assert mig["state"] == "failed" and mig["error"], mig
    assert faults.snapshot()["sites"]["cluster.migrate.stream"]["trips"] >= 1
    # the abort rolled every member back to stable on the OLD epoch
    for ds in (c.coord, c.datastores[1]):
        assert ds.cluster.membership.state == "stable"
        assert ds.cluster.membership.epoch == 1
    c.both("SELECT VALUE n FROM item ORDER BY n")
    # ...and the change is retryable: the same join now lands (epoch 2)
    ch = mship.join(c.coord, node, wait=True, timeout=60)
    assert ch.epoch == 2
    assert c.coord.cluster.membership.view()["nodes"] == ["n1", "n2", "n3"]
    c.both("SELECT VALUE n FROM item ORDER BY n")


def test_conflicting_prepare_refused():
    """Two coordinators racing DIFFERENT proposals under one epoch: the
    second prepare must refuse, not silently ack the first proposal."""
    m = mship.Membership([{"id": "n1", "url": "http://x:1"},
                          {"id": "n2", "url": "http://x:2"}], vnodes=8)
    m.prepare([{"id": "n1", "url": "http://x:1"},
               {"id": "n2", "url": "http://x:2"},
               {"id": "n3", "url": "http://x:3"}], 2)
    # same epoch, same node set: idempotent re-prepare is fine
    m.prepare([{"id": "n1", "url": "http://x:1"},
               {"id": "n2", "url": "http://x:2"},
               {"id": "n3", "url": "http://x:3"}], 2)
    # same epoch, DIFFERENT node set: refused
    with pytest.raises(mship.MembershipError, match="conflicting prepare"):
        m.prepare([{"id": "n1", "url": "http://x:1"},
                   {"id": "n2", "url": "http://x:2"},
                   {"id": "n4", "url": "http://x:4"}], 2)


def test_cutover_failpoint_leaves_member_on_old_epoch(cluster2):
    """A member whose cutover fails stays on the old epoch, and the
    federated bundle shows it beside its peers' new one."""
    c = cluster2
    seed(c, 12)
    node, ds3 = c.spawn("n3")
    # arm ONLY on n2: its commit fails once, n1/n3 cut over
    ok_nodes = {"n1", "n3"}
    epoch = 2
    payload = {
        "nodes": c.nodes + [node], "epoch": epoch,
        "prev_nodes": c.nodes, "prev_epoch": 1, "phase": "prepare",
    }
    for ds in [c.coord, c.datastores[1], ds3]:
        mship.handle_update(ds, dict(payload))
    for src in ("n1", "n2"):
        mship.migrate_ranges(
            c.by_id[src], {"epoch": epoch, "live": ["n1", "n2", "n3"]}
        )
    m0 = counter_sum("cluster_epoch_mismatch_total")
    for nid, ds in (("n1", c.coord), ("n2", c.datastores[1]), ("n3", ds3)):
        if nid == "n2":
            faults.enable("cluster.migrate.cutover", "error", count=1)
            with pytest.raises(Exception):
                mship.handle_update(ds, {"phase": "commit", "epoch": epoch})
            faults.disable("cluster.migrate.cutover")
        else:
            mship.handle_update(ds, {"phase": "commit", "epoch": epoch})
    assert c.coord.cluster.membership.epoch == epoch
    assert c.datastores[1].cluster.membership.epoch == 1  # stuck
    # cross-epoch traffic is counted, and the federated bundle carries
    # every member's own epoch: the stale one reads 1 beside the others' 2
    c.coord.execute("SELECT VALUE n FROM item", c.s)
    assert counter_sum("cluster_epoch_mismatch_total") > m0
    from surrealdb_tpu.cluster.federation import federated_bundle

    fb = federated_bundle(c.coord, trace_limit=2, full_traces=0)
    epochs = {
        nid: b["engine"]["cluster"]["epoch"] for nid, b in fb["nodes"].items()
    }
    assert epochs == {"n1": epoch, "n2": 1, "n3": epoch}, epochs
    # recover n2 so teardown is clean: replay the commit
    mship.handle_update(c.datastores[1], {"phase": "commit", "epoch": epoch})


# ================================================================== repair
def test_r12_caveat_degraded_write_converges_via_antientropy(cluster3):
    """THE regression test this PR exists for: RF=2, kill a replica, ack a
    write degraded, restart the node — the stale copy converges within a
    bounded number of sweeps WITHOUT the record being rewritten, and
    cluster_antientropy_repaired_total proves the path."""
    c = cluster3
    c.both("DEFINE TABLE cav SCHEMALESS")
    ok(c.coord.execute("CREATE cav:1 SET v = 'v0'", c.s)[0])
    ring = c.coord.cluster.ring
    holders = ring.owners_of("cav", 1, 2)
    victim = holders[1]
    victim_i = int(victim[1:]) - 1
    c.kill(victim_i)
    # the degraded ack: the live replica applies, the dead one misses it
    r = c.coord.execute("UPDATE cav:1 SET v = 'v1'", c.s)[0]
    assert r["status"] == "OK", r
    stale = ok(c.by_id[victim].execute_local("SELECT VALUE v FROM cav", c.s)[0])
    assert stale == ["v0"]  # provably stale while down
    c.restart(victim_i)
    c.mark_up(victim)
    # NO read of cav:1 through the cluster (that would read-repair it);
    # the sweep alone must converge it
    a0 = counter_sum("cluster_antientropy_repaired_total")
    converged = False
    for _ in range(3):  # bounded number of sweeps
        for nid in holders:
            repair.sweep_once(c.by_id[nid])
        got = ok(c.by_id[victim].execute_local("SELECT VALUE v FROM cav", c.s)[0])
        if got == ["v1"]:
            converged = True
            break
    assert converged, got
    assert counter_sum("cluster_antientropy_repaired_total") > a0
    # and the sweep's range accounting moved
    assert counter_sum("cluster_repair_ranges") > 0


def test_read_repair_converges_diverged_copy(cluster3):
    """The OTHER path closing the caveat: a divergence observed by a read
    back-fills the stale replica in the background,
    cluster_read_repair_total counting it."""
    c = cluster3
    c.both("DEFINE TABLE rr SCHEMALESS")
    ok(c.coord.execute("CREATE rr:1 SET v = 'a'", c.s)[0])
    holders = c.coord.cluster.ring.owners_of("rr", 1, 2)
    # newer write lands on the SECOND replica only (behind the back)
    ok(c.by_id[holders[1]].execute_local("UPDATE rr:1 SET v = 'b'", c.s)[0])
    r0 = counter_sum("cluster_read_repair_total")
    got = ok(c.coord.execute("SELECT VALUE v FROM rr", c.s)[0])
    assert got == ["b"]  # LWW serves the newest write immediately
    deadline = time.time() + 10
    vals = None
    while time.time() < deadline:
        vals = [
            ok(c.by_id[n].execute_local("SELECT VALUE v FROM rr", c.s)[0])
            for n in holders
        ]
        if all(v == ["b"] for v in vals):
            break
        time.sleep(0.05)
    assert all(v == ["b"] for v in vals), vals
    assert counter_sum("cluster_read_repair_total") > r0


def test_concurrent_updates_converge_lww(cluster2):
    """Concurrent same-record UPDATEs applied in opposite orders on two
    replicas converge to ONE winner after a sweep — no consensus layer."""
    c = cluster2
    c.both("DEFINE TABLE cc SCHEMALESS")
    ok(c.coord.execute("CREATE cc:1 SET v = 0", c.s)[0])
    holders = c.coord.cluster.ring.owners_of("cc", 1, 2)
    # simulate the interleave: replica A saw (x then y), replica B saw
    # (y then x) — the copies differ, each stamped locally
    ok(c.by_id[holders[0]].execute_local("UPDATE cc:1 SET v = 'x'", c.s)[0])
    ok(c.by_id[holders[1]].execute_local("UPDATE cc:1 SET v = 'y'", c.s)[0])
    for nid in holders:
        repair.sweep_once(c.by_id[nid])
    vals = [
        ok(c.by_id[n].execute_local("SELECT VALUE v FROM cc", c.s)[0])
        for n in holders
    ]
    assert vals[0] == vals[1], vals  # converged...
    assert vals[0] in (["x"], ["y"])  # ...to one of the writes (the later)
    # deletes converge too: tombstone beats the stale copy
    ok(c.by_id[holders[0]].execute_local("DELETE cc:1", c.s)[0])
    for nid in holders:
        repair.sweep_once(c.by_id[nid])
    vals = [
        ok(c.by_id[n].execute_local("SELECT VALUE v FROM cc", c.s)[0])
        for n in holders
    ]
    assert vals == [[], []], vals


def test_sweep_failpoint_and_clean_sweep_resets_pushdowns(cluster2):
    c = cluster2
    seed(c, 12)
    # armed sweep site: the peer leg raises, the report carries the error
    faults.enable("cluster.repair.sweep", "error", count=1)
    rep = repair.sweep_once(c.coord)
    assert rep["errors"], rep
    faults.disable("cluster.repair.sweep")
    # simulate a degraded write: pushdowns stand down...
    telemetry.inc("cluster_failover_total", op="write")
    ex = c.coord.cluster.executor
    assert ex._write_degradation() > ex._degradation0
    # ...until a CLEAN sweep proves convergence and resets the watermark
    rep = repair.sweep_once(c.coord)
    assert rep["repaired"] == 0 and not rep["errors"], rep
    assert ex._write_degradation() == ex._degradation0


def test_bundle_carries_elastic_plane(cluster2):
    from surrealdb_tpu.bundle import debug_bundle

    c = cluster2
    seed(c, 6)
    repair.sweep_once(c.coord)
    b = debug_bundle(c.coord)
    cl = b["engine"]["cluster"]
    assert cl["epoch"] == 1
    assert cl["membership"]["state"] == "stable"
    assert cl["repair"] is not None and cl["repair"]["ranges"] > 0
    # the epoch gauge is on /metrics for the federated scrape
    assert telemetry.gauges_matching("cluster_membership_epoch")


def test_new_event_kinds_registered():
    for kind in (
        "cluster.member_join", "cluster.member_leave",
        "cluster.migration_start", "cluster.migration_done",
        "cluster.read_repair", "cluster.antientropy_repair",
    ):
        assert kind in events.KINDS, kind


def test_failpoint_spec_arms_new_sites():
    faults.configure(
        "cluster.migrate.stream=error:1.0:1,cluster.repair.sweep=latency-1"
    )
    snap = faults.snapshot()
    assert "cluster.migrate.stream" in snap["sites"]
    assert "cluster.repair.sweep" in snap["sites"]
