"""Cross-query dispatch coalescing + PARALLEL (VERDICT r2 item 2;
reference: core/src/dbs/iterator.rs:569-710 PARALLEL pipeline)."""

import threading
import time

import numpy as np
import pytest

from surrealdb_tpu import cnf
from surrealdb_tpu.dbs.dispatch import DispatchQueue
from surrealdb_tpu.dbs.session import Session


# ------------------------------------------------------------------ unit
def test_queue_single_request_no_extra_latency():
    q = DispatchQueue()
    out = q.submit("k", 3, lambda xs: [x * 2 for x in xs])
    assert out == 6
    st = q.stats()
    assert (st["submitted"], st["dispatches"], st["batched"]) == (1, 1, 0)


def test_queue_coalesces_while_leader_busy():
    q = DispatchQueue()
    release = threading.Event()
    started = threading.Event()
    results = {}

    def slow_runner(xs):
        started.set()
        release.wait(5)
        return [x * 10 for x in xs]

    def submit(i):
        results[i] = q.submit("k", i, slow_runner)

    leader = threading.Thread(target=submit, args=(0,))
    leader.start()
    assert started.wait(5)
    # queue 6 followers while the leader's batch is "on device"
    followers = [threading.Thread(target=submit, args=(i,)) for i in range(1, 7)]
    for t in followers:
        t.start()
    while q.stats()["submitted"] < 7:
        time.sleep(0.005)
    release.set()
    leader.join(5)
    for t in followers:
        t.join(5)
    assert results == {i: i * 10 for i in range(7)}
    st = q.stats()
    assert st["submitted"] == 7
    assert st["dispatches"] == 2  # leader alone, then all followers together
    assert st["batched"] == 5


def test_queue_error_propagates_to_all_waiters():
    q = DispatchQueue()
    release = threading.Event()
    started = threading.Event()
    errors = []

    def bad_runner(xs):
        started.set()
        release.wait(5)
        raise ValueError("kernel exploded")

    def submit(i):
        try:
            q.submit("k", i, bad_runner)
        except ValueError as e:
            errors.append(str(e))

    ts = [threading.Thread(target=submit, args=(0,))]
    ts[0].start()
    assert started.wait(5)
    ts.append(threading.Thread(target=submit, args=(1,)))
    ts[1].start()
    while q.stats()["submitted"] < 2:
        time.sleep(0.005)
    release.set()
    for t in ts:
        t.join(5)
    assert errors == ["kernel exploded", "kernel exploded"]
    # bucket is released: a fresh request still works
    assert q.submit("k", 4, lambda xs: [x + 1 for x in xs]) == 5


def test_queue_keys_do_not_cross_batch():
    q = DispatchQueue()
    a = q.submit(("knn", 10), 1, lambda xs: [("a", x) for x in xs])
    b = q.submit(("knn", 20), 1, lambda xs: [("b", x) for x in xs])
    assert a == ("a", 1) and b == ("b", 1)
    assert q.stats()["dispatches"] == 2


def test_two_phase_runner_overlaps_batches():
    """A two-phase runner hands the bucket over after LAUNCH: the next
    batch's launch phase runs while the previous batch's collect is still
    blocked (double buffering; VERDICT r3 weak #4)."""
    q = DispatchQueue()
    first_collect_release = threading.Event()
    second_launched = threading.Event()
    results = {}

    def runner_first(xs):
        def collect():
            # blocked "download": the second batch must launch meanwhile
            assert second_launched.wait(5), "second batch never launched during collect"
            return [x * 2 for x in xs]

        return collect

    def runner_second(xs):
        second_launched.set()
        return [x * 3 for x in xs]

    def submit(i, runner):
        results[i] = q.submit("k", i, runner)

    t1 = threading.Thread(target=submit, args=(1, runner_first))
    t1.start()
    time.sleep(0.05)  # let t1 become leader and enter collect
    t2 = threading.Thread(target=submit, args=(2, runner_second))
    t2.start()
    t1.join(10)
    t2.join(10)
    assert results == {1: 2, 2: 6}


def test_two_phase_collect_error_propagates():
    q = DispatchQueue()

    def runner(xs):
        def collect():
            raise ValueError("download failed")

        return collect

    with pytest.raises(ValueError, match="download failed"):
        q.submit("k", 1, runner)
    # bucket released after the failure
    assert q.submit("k", 4, lambda xs: [x + 1 for x in xs]) == 5


# ------------------------------------------------------------------ engine
@pytest.fixture
def ds():
    from surrealdb_tpu.kvs.ds import Datastore

    d = Datastore("memory")
    yield d
    d.close()


@pytest.fixture
def sess():
    s = Session.owner()
    s.ns, s.db = "test", "test"
    return s


def _seed_vectors(ds, sess, n=64, dim=8):
    ds.execute(
        "DEFINE TABLE v SCHEMALESS; "
        f"DEFINE INDEX iv ON v FIELDS emb HNSW DIMENSION {dim} DIST EUCLIDEAN",
        sess,
    )
    rng = np.random.default_rng(0)
    vecs = rng.standard_normal((n, dim)).astype(np.float32)
    rows = [{"id": i, "emb": vecs[i].tolist()} for i in range(n)]
    out = ds.execute("INSERT INTO v $rows", sess, vars={"rows": rows})
    assert out[-1]["status"] == "OK"
    return vecs


def test_concurrent_knn_queries_share_dispatches(ds, sess, monkeypatch):
    """Q concurrent kNN SELECTs produce far fewer device dispatches than Q
    (the VERDICT item-2 'done' condition)."""
    monkeypatch.setattr(cnf, "TPU_KNN_ONDEVICE_THRESHOLD", 1)
    vecs = _seed_vectors(ds, sess)

    # slow the kernel so concurrent queries overlap deterministically
    from surrealdb_tpu.ops import distances as D

    real = D.knn_search

    def slow_knn(*a, **kw):
        time.sleep(0.05)
        return real(*a, **kw)

    monkeypatch.setattr(D, "knn_search", slow_knn)

    nq = 8
    results = {}
    barrier = threading.Barrier(nq)

    def worker(i):
        barrier.wait()
        out = ds.execute(
            "SELECT id FROM v WHERE emb <|3|> $q", sess, vars={"q": vecs[i].tolist()}
        )
        assert out[-1]["status"] == "OK"
        results[i] = [str(r["id"]) for r in out[-1]["result"]]

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(nq)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(15)

    assert len(results) == nq
    for i in range(nq):
        assert results[i][0] == f"v:{i}"  # nearest neighbour of vecs[i] is itself
    st = ds.dispatch.stats()
    assert st["submitted"] == nq
    assert st["dispatches"] < nq  # coalescing happened
    assert st["batched"] == nq - st["dispatches"]


def test_coalesced_batch_matches_sequential(ds, sess, monkeypatch):
    """Results from a coalesced batch are identical to sequential runs."""
    monkeypatch.setattr(cnf, "TPU_KNN_ONDEVICE_THRESHOLD", 1)
    vecs = _seed_vectors(ds, sess, n=32)
    seq = {}
    for i in range(6):
        out = ds.execute(
            "SELECT id FROM v WHERE emb <|4|> $q", sess, vars={"q": vecs[i].tolist()}
        )
        seq[i] = [str(r["id"]) for r in out[-1]["result"]]

    from surrealdb_tpu.ops import distances as D

    real = D.knn_search

    def slow_knn(*a, **kw):
        time.sleep(0.03)
        return real(*a, **kw)

    monkeypatch.setattr(D, "knn_search", slow_knn)
    conc = {}
    barrier = threading.Barrier(6)

    def worker(i):
        barrier.wait()
        out = ds.execute(
            "SELECT id FROM v WHERE emb <|4|> $q", sess, vars={"q": vecs[i].tolist()}
        )
        conc[i] = [str(r["id"]) for r in out[-1]["result"]]

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(15)
    assert conc == seq


# ------------------------------------------------------------------ PARALLEL
def test_parallel_multi_source_select_matches_sequential(ds, sess):
    ds.execute(
        "DEFINE TABLE a SCHEMALESS; DEFINE TABLE b SCHEMALESS; "
        "INSERT INTO a [{id: 1, x: 1}, {id: 2, x: 2}]; "
        "INSERT INTO b [{id: 1, x: 10}, {id: 2, x: 20}]",
        sess,
    )
    seq = ds.execute("SELECT x FROM a, b ORDER BY x", sess)[-1]["result"]
    par = ds.execute("SELECT x FROM a, b ORDER BY x PARALLEL", sess)[-1]["result"]
    assert par == seq == [{"x": 1}, {"x": 2}, {"x": 10}, {"x": 20}]


def test_parallel_shows_in_explain(ds, sess):
    ds.execute("DEFINE TABLE a SCHEMALESS; DEFINE TABLE b SCHEMALESS", sess)
    out = ds.execute("SELECT * FROM a, b PARALLEL EXPLAIN", sess)[-1]["result"]
    ops = [r["operation"] for r in out]
    assert "Parallel" in ops
    out2 = ds.execute("SELECT * FROM a, b EXPLAIN", sess)[-1]["result"]
    assert "Parallel" not in [r["operation"] for r in out2]


def test_transient_runner_failure_retried_once():
    """A batch whose runner raises a transient device error (the local
    runtime's RESOURCE_EXHAUSTED on an oversized launch) is retried once
    before failing every rider."""
    from surrealdb_tpu.dbs.dispatch import DispatchQueue

    q = DispatchQueue()
    calls = {"n": 0}

    def runner(payloads):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating 2.4G")
        return [p * 10 for p in payloads]

    assert q.submit("k", 4, runner) == 40
    assert calls["n"] == 2
    assert q.stats()["retries"] == 1


def test_transient_collect_failure_retried_once():
    from surrealdb_tpu.dbs.dispatch import DispatchQueue

    q = DispatchQueue()
    calls = {"n": 0}

    def runner(payloads):
        calls["n"] += 1
        if calls["n"] == 1:
            def bad_collect():
                raise RuntimeError("UNAVAILABLE: transfer failed")
            return bad_collect
        return [p + 1 for p in payloads]

    assert q.submit("k", 5, runner) == 6
    assert calls["n"] == 2


def test_deterministic_failure_not_retried():
    """Non-transient errors (bad payloads, engine bugs) fail immediately
    without re-executing the batch."""
    from surrealdb_tpu.dbs.dispatch import DispatchQueue

    q = DispatchQueue()
    calls = {"n": 0}

    def runner(payloads):
        calls["n"] += 1
        raise ValueError("bad shape")

    import pytest as _pytest

    with _pytest.raises(ValueError):
        q.submit("k", 1, runner)
    assert calls["n"] == 1
    assert q.stats()["retries"] == 0


def test_persistent_failure_still_fails():
    from surrealdb_tpu.dbs.dispatch import DispatchQueue

    q = DispatchQueue()

    def runner(payloads):
        raise RuntimeError("UNAVAILABLE: always broken")

    import pytest as _pytest

    with _pytest.raises(RuntimeError, match="always broken"):
        q.submit("k", 1, runner)


# ------------------------------------------------- split-retry / pipelining
class ResourceExhaustedRunner:
    """Fake device with a hard batch-width capacity: any launch wider than
    `cap` fails like an oversized allocation on a real chip. Records every
    attempted launch width."""

    def __init__(self, cap: int, mul: int = 10):
        self.cap = cap
        self.mul = mul
        self.launches: list = []
        self._lock = threading.Lock()

    def __call__(self, payloads):
        with self._lock:
            self.launches.append(len(payloads))
        if len(payloads) > self.cap:
            raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating scratch")
        return [p * self.mul for p in payloads]


def _coalesce_batch(q, n, runner, key="k"):
    """Build one n-wide coalesced batch behind a blocked width-1 leader;
    returns ({i: result}, {i: error}) for riders 1..n."""
    release, started = threading.Event(), threading.Event()

    def slow_ok(xs):
        started.set()
        release.wait(5)
        return [("lead", x) for x in xs]

    results, errors = {}, {}

    def submit(i, r):
        try:
            results[i] = q.submit(key, i, r)
        except Exception as e:  # noqa: BLE001
            errors[i] = e

    lead = threading.Thread(target=submit, args=(0, slow_ok))
    lead.start()
    assert started.wait(5)
    riders = [threading.Thread(target=submit, args=(i, runner)) for i in range(1, n + 1)]
    for t in riders:
        t.start()
    while q.stats()["submitted"] < n + 1:
        time.sleep(0.005)
    release.set()
    lead.join(10)
    for t in riders:
        t.join(10)
    assert results.pop(0) == ("lead", 0)
    return results, errors


def test_split_retry_bisects_oversized_batch(monkeypatch):
    """A RESOURCE_EXHAUSTED full batch is bisected down to widths the
    device can serve — never re-executed at the width that just failed —
    and EVERY rider ends with its own result."""
    monkeypatch.setattr(cnf, "DISPATCH_RETRY_BACKOFF_SECS", 0.0)
    from surrealdb_tpu.dbs.dispatch import DispatchQueue

    q = DispatchQueue(split_floor=1, pipeline_depth=1)
    fake = ResourceExhaustedRunner(cap=2)
    results, errors = _coalesce_batch(q, 8, fake)

    assert errors == {}
    assert results == {i: i * 10 for i in range(1, 9)}
    # the full width fails once; afterwards the dispatcher only shrinks
    assert fake.launches[0] in (1, 8)  # leader's own width-1 batch uses slow_ok
    wide = [w for w in fake.launches if w == 8]
    assert len(wide) == 1, f"full width re-executed: {fake.launches}"
    assert sorted(fake.launches) == [2, 2, 2, 2, 4, 4, 8]
    st = q.stats()
    assert st["splits"] == 3  # 8 -> 4+4 -> (2+2)x2
    assert st["failures"] == 0


def test_split_retry_floor_retries_whole(monkeypatch):
    """At or below the split floor a transiently-failed batch retries
    whole, once — no pointless bisection of narrow batches."""
    monkeypatch.setattr(cnf, "DISPATCH_RETRY_BACKOFF_SECS", 0.0)
    from surrealdb_tpu.dbs.dispatch import DispatchQueue

    q = DispatchQueue(split_floor=8, pipeline_depth=1)
    calls = {"n": 0}

    def flaky(payloads):
        calls["n"] += 1
        if calls["n"] == 1 and len(payloads) > 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: transient")
        return [p * 10 for p in payloads]

    results, errors = _coalesce_batch(q, 6, flaky)
    assert errors == {} and results == {i: i * 10 for i in range(1, 7)}
    st = q.stats()
    assert st["splits"] == 0 and st["retries"] == 1


def test_split_retry_deterministic_half_not_reexecuted(monkeypatch):
    """During a split-retry, a half that fails DETERMINISTICALLY fails its
    own riders immediately (no further re-execution); the other half still
    succeeds independently."""
    monkeypatch.setattr(cnf, "DISPATCH_RETRY_BACKOFF_SECS", 0.0)
    from surrealdb_tpu.dbs.dispatch import DispatchQueue

    q = DispatchQueue(split_floor=1, pipeline_depth=1)
    widths = []

    def runner(payloads):
        widths.append(len(payloads))
        if len(payloads) == 4:
            raise RuntimeError("RESOURCE_EXHAUSTED: oversized")
        # payloads 1..2 land in the first half after the bisect
        if any(p == 1 for p in payloads):
            raise ValueError("bad shape")  # deterministic
        return [p * 10 for p in payloads]

    results, errors = _coalesce_batch(q, 4, runner)
    assert results == {3: 30, 4: 40}
    assert set(errors) == {1, 2}
    assert all(isinstance(e, ValueError) for e in errors.values())
    assert widths.count(4) == 1  # the failed width never re-ran
    st = q.stats()
    assert st["splits"] == 1 and st["failures"] == 1


def test_deterministic_wide_batch_fails_without_reexecution():
    """A deterministic error on a WIDE batch must not trigger the split
    path at all — the batch fails once, every rider sees the error."""
    from surrealdb_tpu.dbs.dispatch import DispatchQueue

    q = DispatchQueue(split_floor=1, pipeline_depth=1)
    calls = {"n": 0}

    def broken(payloads):
        calls["n"] += 1
        raise ValueError("engine bug")

    results, errors = _coalesce_batch(q, 6, broken)
    assert results == {} and set(errors) == set(range(1, 7))
    assert calls["n"] == 1
    assert q.stats()["splits"] == 0


def test_width_cap_chains_batches():
    """An oversized queue dispatches as back-to-back width-capped batches
    (compiled-shape reuse), in FIFO order, with every rider served."""
    from surrealdb_tpu.dbs.dispatch import DispatchQueue

    q = DispatchQueue(max_width=4)
    results, errors = _coalesce_batch(q, 10, lambda xs: [x * 10 for x in xs])
    assert errors == {} and results == {i: i * 10 for i in range(1, 11)}
    widths = q.width_distribution()
    assert widths == {1: 1, 4: 2, 2: 1}  # leader, then 4+4+2 chained
    st = q.stats()
    assert st["dispatches"] == 4 and st["batched"] == 7


def test_pipeline_depth_bounds_inflight_batches():
    """At most `pipeline_depth` batches are launched-but-uncollected per
    bucket: the depth+1'th leader blocks on the semaphore until a collect
    completes — and proceeds as soon as one does."""
    from surrealdb_tpu.dbs.dispatch import DispatchQueue

    q = DispatchQueue(max_width=1, pipeline_depth=2)
    launched = {i: threading.Event() for i in (1, 2, 3)}
    release = {i: threading.Event() for i in (1, 2, 3)}

    def make_runner(i):
        def runner(xs):
            launched[i].set()

            def collect():
                assert release[i].wait(10)
                return [x * 10 for x in xs]

            return collect

        return runner

    results = {}
    ts = []
    for i in (1, 2, 3):
        t = threading.Thread(
            target=lambda i=i: results.__setitem__(i, q.submit("k", i, make_runner(i)))
        )
        t.start()
        ts.append(t)
        if i < 3:
            assert launched[i].wait(5)  # serialize arrival order
    # batches 1 and 2 are in flight (collect pending); batch 3 must wait
    assert not launched[3].wait(0.3)
    release[1].set()  # finish batch 1's collect -> slot frees
    assert launched[3].wait(5)
    release[2].set()
    release[3].set()
    for t in ts:
        t.join(10)
    assert results == {1: 10, 2: 20, 3: 30}
    assert q.stats()["pipeline_wait_s"] > 0


def test_a_family_names_its_own_pipeline_depth():
    """`submit(..., depth=1)`: that key's bucket is one deep whatever the
    knob says (a sweep whose cost does not grow with its riders), so the
    riders that arrive while a batch is in flight ride the NEXT one
    together; a key that names no depth keeps the knob's."""
    from surrealdb_tpu.dbs.dispatch import DispatchQueue

    q = DispatchQueue(pipeline_depth=4)
    launched, release, widths = threading.Event(), threading.Event(), []

    def runner(xs):
        widths.append(len(xs))
        launched.set()

        def collect():
            assert release.wait(10)
            return [x * 10 for x in xs]

        return collect

    results = {}
    ts = [threading.Thread(target=lambda: results.__setitem__(1, q.submit("sweep", 1, runner, depth=1)))]
    ts[0].start()
    assert launched.wait(5)
    for i in (2, 3, 4):
        ts.append(threading.Thread(target=lambda i=i: results.__setitem__(i, q.submit("sweep", i, runner, depth=1))))
        ts[-1].start()
    t_end = time.time() + 5
    while q.stats()["submitted"] < 4 and time.time() < t_end:
        time.sleep(0.001)
    time.sleep(0.2)
    assert widths == [1]  # the bucket is one deep: nothing launches beside the batch in flight
    release.set()
    for t in ts:
        t.join(10)
    assert widths == [1, 3] and results == {1: 10, 2: 20, 3: 30, 4: 40}
    assert q.submit("other", 5, lambda xs: [x * 10 for x in xs]) == 50
    assert {k: b.depth for k, b in q._buckets.items()} == {"sweep": 1, "other": 4}


def test_collect_phase_transient_failure_split_retried(monkeypatch):
    """A transient failure in the COLLECT phase of a wide two-phase batch
    goes through the same bisection as a launch failure."""
    monkeypatch.setattr(cnf, "DISPATCH_RETRY_BACKOFF_SECS", 0.0)
    from surrealdb_tpu.dbs.dispatch import DispatchQueue

    q = DispatchQueue(split_floor=1, pipeline_depth=1)
    state = {"first": True}

    def runner(payloads):
        if state["first"] and len(payloads) == 4:
            state["first"] = False

            def bad_collect():
                raise RuntimeError("RESOURCE_EXHAUSTED: transfer failed")

            return bad_collect
        return [p * 10 for p in payloads]

    results, errors = _coalesce_batch(q, 4, runner)
    assert errors == {} and results == {i: i * 10 for i in range(1, 5)}
    assert q.stats()["splits"] == 1


# ------------------------------------------------------------------ a one-deep bucket that gathers
def _gathering(launch_s: float):
    """A queue, the widths its batches launched at, a submit into its one
    gathering bucket, and the waits its leaders made for riders."""
    from surrealdb_tpu.dbs.dispatch import SWEEP_DEPTH

    q, widths, waits = DispatchQueue(), [], []

    def runner(xs):
        widths.append(len(xs))
        time.sleep(launch_s)  # the launch phase: what a rider is worth waiting for
        return lambda: [x * 10 for x in xs]

    def submit(x):
        got = q.submit("g", x, runner, depth=SWEEP_DEPTH, gather=True)
        bucket = q._buckets["g"]
        if not hasattr(bucket.arrived, "counted"):
            wait = bucket.arrived.wait

            def counted(timeout=None):
                waits.append(timeout)
                return wait(timeout)

            bucket.arrived.wait = bucket.arrived.counted = counted
        return got

    return q, widths, waits, submit


def _together(submit, xs, gap_s: float = 0.0):
    """Submit `xs` from a thread each, `gap_s` apart; their results."""
    results = {}
    ts = []
    for x in xs:
        ts.append(threading.Thread(target=lambda x=x: results.__setitem__(x, submit(x))))
        ts[-1].start()
        time.sleep(gap_s)
    for t in ts:
        t.join(10)
    return results


def test_a_gathering_leader_waits_for_the_rider_of_the_batch_before_last():
    q, widths, waits, submit = _gathering(0.1)
    assert submit(1) == 10  # alone; the bucket exists from here, and its waits are counted
    # 2 leads alone and holds the bucket through its launch; 3 and 4 queue behind it and ride together
    assert _together(submit, [2, 3, 4], gap_s=0.03) == {2: 20, 3: 30, 4: 40}
    assert widths == [1, 1, 2] and waits == []
    bucket = q._buckets["g"]
    assert bucket.widths == [1, 2] and bucket.launch_s() == pytest.approx(0.1, rel=0.5)
    assert submit(5) == 50 and waits == []  # the batch before last was 1 wide: nobody to wait for
    assert bucket.widths == [2, 1]
    # the batch before last was 2 wide: 6 waits for a second rider, and 7, a moment later, rides with it
    assert _together(submit, [6, 7], gap_s=0.03) == {6: 60, 7: 70}
    assert widths == [1, 1, 2, 1, 2] and len(waits) >= 1 and 0 < waits[0] <= bucket.launch_s() * 1.5
    assert q.stats()["dispatches"] == 5 and q.stats()["submitted"] == 7


def test_a_rider_that_does_not_come_costs_one_launch_phase_once():
    q, widths, waits, submit = _gathering(0.1)
    submit(1)
    _together(submit, [2, 3, 4], gap_s=0.03)
    submit(5)
    bucket = q._buckets["g"]
    assert bucket.widths == [2, 1] and waits == []
    t0 = time.perf_counter()
    assert submit(6) == 60  # expects a second rider, as the batch before last had: none comes
    waited = time.perf_counter() - t0 - 0.1
    assert len(waits) == 1 and waited >= 0.5 * waits[0] and bucket.widths == [1, 1]
    for x in (7, 8, 9):
        assert submit(x) == x * 10  # and nobody waits again: the widths that launched are what is expected
    assert len(waits) == 1 and widths == [1, 1, 2, 1, 1, 1, 1, 1]


def test_a_lone_session_never_waits_and_only_a_one_deep_bucket_gathers():
    q, widths, waits, submit = _gathering(0.0)
    assert [submit(x) for x in range(6)] == [x * 10 for x in range(6)]
    assert waits == [] and widths == [1] * 6
    run = lambda xs: [x * 10 for x in xs]
    assert q.submit("two_deep", 1, run, depth=2, gather=True) == 10
    assert q.submit("the_knob_s", 1, run, gather=True) == 10
    assert q.submit("plain", 1, run, depth=1) == 10
    assert {k: (b.depth, b.gather) for k, b in q._buckets.items()} == {
        "g": (1, True), "two_deep": (2, False), "the_knob_s": (cnf.DISPATCH_PIPELINE_DEPTH, False), "plain": (1, False)}


@pytest.mark.parametrize("launches, cost", [
    ([], 0.0), ([1.5], 1.5), ([1.5, 0.002], 0.002),  # a first launch that compiled: the shortest of fewer than three
    ([1.5, 0.002, 0.004], 0.004), ([0.002, 0.003, 0.004, 0.9, 0.001], 0.003),  # neither the longest nor the shortest
], ids=["none", "one", "two", "three", "five"])
def test_what_a_launch_costs_a_gathering_bucket_leaves_out_its_longest_and_shortest(launches, cost):
    from surrealdb_tpu.dbs.dispatch import _Bucket

    b = _Bucket(1, gather=True)
    b.launches = launches
    assert b.launch_s() == pytest.approx(cost)


# ------------------------------------------------------------------ the gathering's own counters
def _gather_counts(q) -> tuple:
    s = q.stats()
    return s["gather_waits"], s["gather_met"], s["gather_wait_s"]


def test_a_rider_that_comes_is_a_wait_that_was_met():
    q, widths, waits, submit = _gathering(0.1)
    submit(1)
    _together(submit, [2, 3, 4], gap_s=0.03)
    submit(5)
    assert _gather_counts(q) == (0, 0, 0.0)  # nobody has had anyone to wait for yet
    _together(submit, [6, 7], gap_s=0.03)  # 6 expects a second rider and 7 comes
    n, met, wait_s = _gather_counts(q)
    assert (n, met) == (1, 1) and widths[-1] == 2
    assert 0.0 < wait_s < q._buckets["g"].launch_s() * 1.5  # it came before the deadline: less than the launch phase


def test_a_rider_that_has_left_is_a_wait_that_was_not_met():
    q, widths, waits, submit = _gathering(0.1)
    submit(1)
    _together(submit, [2, 3, 4], gap_s=0.03)
    submit(5)
    bound = q._buckets["g"].launch_s()
    submit(6)  # expects a second rider: none comes
    n, met, wait_s = _gather_counts(q)
    assert (n, met) == (1, 0) and wait_s >= 0.5 * bound
    for x in (7, 8, 9):
        submit(x)
    assert _gather_counts(q) == (n, met, wait_s)  # the wait is paid once


@pytest.mark.parametrize("kwargs", [
    {"depth": 1, "gather": True}, {"depth": 1}, {"depth": 2, "gather": True}, {},
], ids=["gathering", "one_deep", "two_deep", "the_knob_s"])
def test_a_lone_session_counts_no_wait_in_any_bucket(kwargs):
    q = DispatchQueue()
    run = lambda xs: (lambda: [x * 10 for x in xs])
    assert [q.submit("k", x, run, **kwargs) for x in range(6)] == [x * 10 for x in range(6)]
    assert _gather_counts(q) == (0, 0, 0.0)


def test_stats_with_the_gather_counters_still_diff_numerically():
    q, widths, waits, submit = _gathering(0.05)
    s0 = q.stats()
    submit(1)
    _together(submit, [2, 3, 4], gap_s=0.02)
    submit(5)
    submit(6)
    s1 = q.stats()
    assert set(s0) == set(s1) and {"gather_waits", "gather_met", "gather_wait_s"} <= set(s1)
    d = {k: s1[k] - s0[k] for k in s1}  # what benchmarks/run.py and the slow-query record do
    assert all(isinstance(v, (int, float)) and not isinstance(v, bool) for v in s1.values())
    assert isinstance(s1["gather_waits"], int) and isinstance(s1["gather_met"], int)
    assert d["gather_waits"] == 1 and d["gather_met"] == 0 and d["gather_wait_s"] > 0
    assert d["gather_met"] <= d["gather_waits"] <= d["dispatches"]


def test_the_gather_counters_lose_no_update_under_many_sessions():
    """Twenty-four closed-loop sessions on one gathering bucket, the
    interpreter switching threads every 10 us: every leader's wait is
    counted once, under the lock the other counters share."""
    import sys

    from surrealdb_tpu.dbs.dispatch import SWEEP_DEPTH

    q, gathered = DispatchQueue(), []
    real = DispatchQueue._gather

    def spy(b):
        got = real(b)
        gathered.append(got)
        return got

    q._gather = spy
    run = lambda xs: (lambda: [x + 1 for x in xs])
    sessions, rounds, out = 24, 25, {}

    def session(i):
        out[i] = [q.submit("g", i * rounds + r, run, depth=SWEEP_DEPTH, gather=True) for r in range(rounds)]

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(target=session, args=(i,)) for i in range(sessions)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ts)
    assert out == {i: [i * rounds + r + 1 for r in range(rounds)] for i in range(sessions)}
    s, waited = q.stats(), [g for g in gathered if g is not None]
    assert s["submitted"] == sessions * rounds == sum(w * n for w, n in q.width_distribution().items())
    assert len(gathered) == s["dispatches"]
    assert s["gather_waits"] == len(waited) and s["gather_met"] == sum(met for _, met in waited)
    assert s["gather_wait_s"] == pytest.approx(sum(t for t, _ in waited))
