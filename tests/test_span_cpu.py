"""The interpreter's books (ISSUE 49): a TAGGED request's root, `statement`,
`ws_encode` and the dispatch phases its own thread led say how much of their
time was that thread's CPU (`cpu_ms` beside `dur_ms`, on a span only in the
trace of the request whose own thread ran it; no other span reads the clock), and
`DispatchQueue.stats()` carries the CPU seconds of the executor pool, of the
event loop and of the launch phases, without reading a clock itself. An
untagged request reads `time.thread_time()` nowhere but at the executor's two
sites that were there. The seven benchmark readers run over their hand-made
`ctx` cases here too, taken by name from `benchmarks/tests/`."""

import json
import os
import socket
import sys
import threading
import time
import urllib.request

import pytest

from surrealdb_tpu import cnf, telemetry, tracing
from surrealdb_tpu.dbs.dispatch import DispatchQueue
from surrealdb_tpu.dbs.session import Session

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (BENCH, os.path.join(BENCH, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import test_bench_host_cpu as bench_cases  # noqa: E402

# the readers over their hand-made `ctx` cases (no rehearsal: that is the benchmark's own suite's)
readers = bench_cases.readers
test_the_manifest_has_the_seven_together_in_order_after_those_that_were_there = (
    bench_cases.test_the_manifest_has_the_seven_together_in_order_after_those_that_were_there)
test_a_counter_reader_over_a_hand_made_window = bench_cases.test_a_counter_reader_over_a_hand_made_window
test_the_two_per_statement_counters_are_the_busy_share_over_another_denominator = (
    bench_cases.test_the_two_per_statement_counters_are_the_busy_share_over_another_denominator)
test_exec_cpu_ms_is_the_root_and_the_encode_of_the_requests_own_thread = (
    bench_cases.test_exec_cpu_ms_is_the_root_and_the_encode_of_the_requests_own_thread)
test_lock_wait_ms_counts_a_riders_copy_as_sleep_and_a_leaders_own_as_work = (
    bench_cases.test_lock_wait_ms_counts_a_riders_copy_as_sleep_and_a_leaders_own_as_work)
test_lock_wait_ms_takes_the_sleeps_inside_the_root_and_once = (
    bench_cases.test_lock_wait_ms_takes_the_sleeps_inside_the_root_and_once)
test_the_parents_program_gives_the_seven_nothing = bench_cases.test_the_parents_program_gives_the_seven_nothing

CPU_KEYS = ("cpu_exec_s", "cpu_loop_s", "launch_cpu_s", "launch_cpu_of_s")
SQL = "SELECT * FROM person WHERE name = 'x'"


def _named(doc, name):
    return [s for s in doc["spans"] if s["name"] == name]


def _spin(cpu_s):
    """Burn `cpu_s` seconds of THIS thread's CPU (by its own clock: a loaded machine cannot starve it)."""
    c0 = time.thread_time()
    while time.thread_time() - c0 < cpu_s:
        pass


@pytest.fixture()
def loaded(ds):
    out = ds.execute("CREATE person:1 SET name = 'x'; CREATE person:2 SET name = 'y';", Session.owner("t", "t"))
    assert all(r["status"] == "OK" for r in out)
    tracing.store_reset()
    return ds


@pytest.fixture()
def clock_reads(monkeypatch):
    """Every call of `time.thread_time` while the fixture lives (tracing.py, telemetry.py, dispatch.py,
    the executor and net/loop.py all reach it through the module)."""
    calls = []
    real = time.thread_time

    def counted():
        calls.append(threading.get_ident())
        return real()

    monkeypatch.setattr(time, "thread_time", counted)
    return calls


# ------------------------------------------------------------------ a tagged request, and any other
@pytest.mark.parametrize("name, carries", [
    ("ws_rpc", True), ("statement", True),  # the root's two readings; the executor's two, which were there
    # every other span of the executor path reads no clock, tagged or not: the read is a system call, and
    # a tagged request is the sample the span metrics are taken from
    ("execute", False), ("plan_fetch", False), ("select_setup", False), ("stmt_accounting", False),
])
def test_a_tagged_requests_root_and_statement_carry_their_cpu_and_no_other_span(loaded, name, carries):
    with tracing.request("ws_rpc", trace_id="cpu-tagged"):
        assert loaded.execute(SQL, Session.owner("t", "t"))[-1]["status"] == "OK"
    spans = _named(tracing.get_trace("cpu-tagged"), name)
    assert spans
    for s in spans:
        assert ("cpu_ms" in s) is carries
        if carries:
            assert isinstance(s["cpu_ms"], float) and 0.0 <= s["cpu_ms"] <= s["dur_ms"] + 1.0


def test_an_untagged_request_reads_the_clock_at_the_executors_two_sites_alone(loaded, clock_reads):
    with tracing.request("ws_rpc") as tr:
        tr.force = True  # kept, so that its spans can be looked at
        helper = (tracing.cpu_now(), tracing.cpu_since(tracing.cpu_now()))
        assert loaded.execute(SQL + "; " + SQL, Session.owner("t", "t"))[-1]["status"] == "OK"
    assert helper == (None, None)
    assert len(clock_reads) == 2 * 2  # dbs/executor.py, twice a statement, as before this PR
    doc = tracing.get_trace(tr.trace_id)
    assert len(doc["spans"]) >= 8 and not [s for s in doc["spans"] if "cpu_ms" in s]


def test_outside_any_trace_the_helpers_read_nothing(clock_reads):
    assert tracing.cpu_now() is None and tracing.cpu_since(None) is None
    tracing.note_cpu(None, "statement", 0.001)
    with telemetry.span("statement", kind="Probe"):
        pass
    assert clock_reads == []


def test_a_tagged_request_reads_the_clock_at_its_roots_ends_and_the_executors_two_a_statement(loaded, clock_reads):
    """About fifty reads a tagged request (one at each end of every span) cost 0.5 ms on the chip's host
    and shifted `exec.host_ms`: the spans between the root and a dispatch read none."""
    with tracing.request("ws_rpc", trace_id="cpu-few"):
        assert loaded.execute(SQL + "; " + SQL, Session.owner("t", "t"))[-1]["status"] == "OK"
        assert len(clock_reads) == 1 + 2 * 2
        assert tracing.cpu_now() is not None  # a site that wants `cpu_ms` asks for it
    assert len(clock_reads) == 2 + 2 * 2 + 1
    assert len(tracing.get_trace("cpu-few")["spans"]) >= 12


@pytest.mark.parametrize("how, low, high", [("sleep", 0.0, 5.0), ("spin", 10.0, None)])
def test_cpu_ms_tells_a_sleep_from_a_spin(how, low, high):
    with tracing.request("ws_rpc", trace_id=f"cpu-{how}"):
        t0, cpu0 = time.perf_counter(), tracing.cpu_now()
        time.sleep(0.02) if how == "sleep" else _spin(0.02)
        tracing.record_span_into(tracing.current(), "knn_prepare", {}, t0, time.perf_counter() - t0,
                                 cpu=tracing.cpu_since(cpu0))
    for name in ("knn_prepare", "ws_rpc"):
        (s,) = _named(tracing.get_trace(f"cpu-{how}"), name)
        assert s["dur_ms"] >= 19.0 and low <= s["cpu_ms"] <= (high if high is not None else s["dur_ms"] + 1.0)


def test_the_statement_span_takes_the_executors_two_readings(clock_reads):
    """note_cpu(): the newest span of that name under the caller's position, in a tagged trace alone."""
    with tracing.request("ws_rpc", trace_id="cpu-note"):
        at = tracing.current()
        for spin in (0.0, 0.004):
            with telemetry.span("statement", kind="Probe"):
                _spin(spin)
        with telemetry.span("execute"):
            with telemetry.span("statement", kind="Nested"):  # another position's
                pass
        n = len(clock_reads)
        tracing.note_cpu(at, "statement", 0.004)
        tracing.note_cpu(at, "no_such_span", 0.004)
        assert len(clock_reads) == n  # what its caller measured anyway: no read
    first, second, nested = _named(tracing.get_trace("cpu-note"), "statement")
    assert "cpu_ms" not in first and second["cpu_ms"] == 4.0 and "cpu_ms" not in nested
    with tracing.request("ws_rpc") as tr:
        tr.force = True
        with telemetry.span("statement", kind="Probe"):
            pass
        tracing.note_cpu(tracing.current(), "statement", 0.004)
    assert not [s for s in tracing.get_trace(tr.trace_id)["spans"] if "cpu_ms" in s]


def test_a_span_stamped_onto_another_trace_gets_the_cpu_only_if_its_caller_gives_it():
    """record_span_into(cpu=) is the one way in: a leader that stamps a span onto a rider hands it none."""
    with tracing.request("ws_rpc", trace_id="cpu-rider"):
        rider_ctx = tracing.current()
    with tracing.request("ws_rpc", trace_id="cpu-leader"):
        t0, cpu0 = time.perf_counter(), tracing.cpu_now()
        tracing.record_span_into(rider_ctx, "dispatch_launch", {}, t0, 0.001)
        tracing.record_span_into(tracing.current(), "dispatch_launch", {}, t0, 0.001, cpu=tracing.cpu_since(cpu0))
        tr = tracing.current().trace
    assert [s[7] for s in rider_ctx.trace.spans if s[2] == "dispatch_launch"] == [None]
    assert [s[7] is not None for s in tr.spans if s[2] == "dispatch_launch"] == [True]


def test_to_chrome_carries_cpu_ms_where_the_span_has_it(loaded):
    with tracing.request("ws_rpc", trace_id="cpu-chrome"):
        loaded.execute(SQL, Session.owner("t", "t"))
        tracing.record_span_into(tracing.current(), "dispatch_launch", {"batch": 2}, time.perf_counter(), 0.001)
    doc = tracing.get_trace("cpu-chrome")
    events = {e["name"]: e for e in tracing.to_chrome(doc)["traceEvents"]}
    (root,) = _named(doc, "ws_rpc")
    assert events["ws_rpc"]["args"]["cpu_ms"] == root["cpu_ms"] and "cpu_ms" in events["statement"]["args"]
    assert "cpu_ms" not in events["dispatch_launch"]["args"]  # absent where it was not measured, never 0


# ------------------------------------------------------------------ a served batch
def _batch(tagged_leader=True):
    """One batch of three on a held queue: the docs of its leader and of its two riders. (A first request
    holds the bucket; three more queue behind it and leave together, one of them leading.)"""
    q = DispatchQueue()
    release, started = threading.Event(), threading.Event()

    def runner(xs):
        started.set()
        release.wait(5)
        _spin(0.003)
        outs = [x * 10 for x in xs]

        def collect():
            _spin(0.002)
            return outs

        return collect

    results = {}

    def submit(i):
        tid = None if (i == 0 or not tagged_leader) and i != 3 else f"batch-{i}"
        with tracing.request("ws_rpc", trace_id=tid) as tr:
            tr.force = True
            results[i] = (tr.trace_id, q.submit("k", i, runner))

    first = threading.Thread(target=submit, args=(0,))
    first.start()
    assert started.wait(5)
    rest = [threading.Thread(target=submit, args=(i,)) for i in (1, 2, 3)]
    for t in rest:
        t.start()
        while q.stats()["submitted"] < 1 + rest.index(t) + 1:
            time.sleep(0.002)
    release.set()
    for t in [first] + rest:
        t.join(10)
    assert {i: r[1] for i, r in results.items()} == {i: i * 10 for i in range(4)}
    st = q.stats()
    assert (st["dispatches"], st["batched"]) == (2, 2)
    return [tracing.get_trace(results[i][0]) for i in (1, 2, 3)], st


@pytest.mark.parametrize("name", ["dispatch_launch", "dispatch_collect"])
def test_the_leaders_copy_carries_the_cpu_and_a_riders_copy_carries_none(name):
    docs, st = _batch()
    copies = [s for d in docs for s in _named(d, name)]
    assert len(copies) == 3 and all(s["labels"]["batch"] == "3" for s in copies)
    own = [s for s in copies if "cpu_ms" in s]
    assert len(own) == 1  # request 1's thread led: it was at the queue's head
    assert own[0] in _named(docs[0], name) and 1.5 <= own[0]["cpu_ms"] <= own[0]["dur_ms"] + 1.0
    # the same reading is in the queue's books: a tagged leader's launch is sampled (3 ms of spinning in it)
    assert 0.0025 <= st["launch_cpu_s"] <= st["launch_cpu_of_s"] + 0.002 <= st["launch_s"] + 0.004


def test_the_leaders_own_device_wait_says_what_of_it_the_thread_ran():
    """Of the spans `host.lock_wait_ms` takes as sleep the leader's own `dispatch_ready_wait` carries its
    CPU (a backend may run the program on the waiting thread); no queue wait does, and no rider's copy."""
    docs, _ = _batch()
    for name, own in (("dispatch_queue_wait", []), ("dispatch_ready_wait", [0])):
        copies = [s for d in docs for s in _named(d, name)]
        assert len(copies) == 3 and [i for i, s in enumerate(copies) if "cpu_ms" in s] == own


def test_an_untagged_leader_gives_nobody_a_cpu_reading():
    docs, _ = _batch(tagged_leader=False)
    assert docs[2]["trace_id"] == "batch-3"  # request 3 is tagged, and rode
    for d in docs:
        assert _named(d, "dispatch_launch") and not [s for s in d["spans"] if s["name"].startswith("dispatch_") and "cpu_ms" in s]
    (root,) = _named(docs[2], "ws_rpc")
    assert "cpu_ms" in root and root["cpu_ms"] < 0.5 * root["dur_ms"]  # it slept through somebody's launch


# ------------------------------------------------------------------ stats()
def test_stats_has_the_three_sums_and_reads_no_clock(monkeypatch):
    q = DispatchQueue()
    before = q.stats()
    assert set(CPU_KEYS) <= set(before) and all(isinstance(before[k], float) for k in CPU_KEYS)
    assert q.submit("k", 1, lambda xs: [_spin(0.002) or x for x in xs]) == 1

    def no_clock():
        raise AssertionError("stats() read time.thread_time")

    monkeypatch.setattr(time, "thread_time", no_clock)
    after = q.stats()
    monkeypatch.undo()
    assert after["launch_cpu_s"] >= before["launch_cpu_s"] + 0.0015  # a queue's first launch is sampled
    assert all(after[k] >= before[k] for k in CPU_KEYS)


def test_an_untagged_launch_reads_the_clock_once_in_a_while(clock_reads, monkeypatch):
    """On the chip's host two reads round every launch cost a one-session cell 5% of its p50: the untagged
    launches are sampled (one in `CPU_SAMPLE_EVERY_S`), a tagged leader's never skipped."""
    from surrealdb_tpu.dbs import dispatch

    monkeypatch.setattr(dispatch, "CPU_SAMPLE_EVERY_S", 3600.0)
    q = DispatchQueue()
    for i in range(50):
        q.submit("k", i, lambda xs: list(xs))
    assert len(clock_reads) == 2  # the queue's first launch, and none of the 49 after it
    st = q.stats()
    assert 0.0 < st["launch_cpu_of_s"] < st["launch_s"]
    with tracing.request("ws_rpc", trace_id="cpu-sampled"):
        q.submit("k", 0, lambda xs: list(xs))
    assert len(clock_reads) == 2 + 2 + 2  # the root's two and the launch's two (this runner has no collect)
    monkeypatch.setattr(dispatch, "CPU_SAMPLE_EVERY_S", 0.0)  # the interval has passed
    n = len(clock_reads)
    q.submit("k", 1, lambda xs: list(xs))
    assert len(clock_reads) == n + 2


def test_the_slots_table_keeps_what_an_ended_thread_burned():
    before = telemetry.cpu_seconds().get("probe", 0.0)
    slot = telemetry.cpu_slot("probe")
    slot[0] = 1e6  # one writer a slot: the thread stores its clock, no lock, no shared `+=`
    assert telemetry.cpu_seconds()["probe"] == pytest.approx(before + 1e6)
    telemetry.cpu_slot_end("probe", slot)
    telemetry.cpu_slot_end("probe", slot)  # once
    telemetry.reset()  # monotone: a reset of the registry leaves the sums alone
    assert telemetry.cpu_seconds()["probe"] == pytest.approx(before + 1e6)


def test_a_thread_reads_its_clock_once_in_a_while_and_when_it_ends(clock_reads, monkeypatch):
    """On the chip's host the read is a system call and the clock ticks every 10 ms: a task and a pass
    end far more often than a reading is worth (`CPU_SLOT_EVERY_S`)."""
    monkeypatch.setattr(telemetry, "CPU_SLOT_EVERY_S", 3600.0)
    slot = telemetry.cpu_slot("probe-notes")
    for _ in range(1000):
        telemetry.cpu_slot_note(slot)
    assert len(clock_reads) == 1 and slot[0] > 0.0
    monkeypatch.setattr(telemetry, "CPU_SLOT_EVERY_S", 0.0)  # the interval has passed
    telemetry.cpu_slot_note(slot)
    assert len(clock_reads) == 2
    telemetry.cpu_slot_end("probe-notes", slot)
    assert len(clock_reads) == 3 and telemetry.cpu_seconds()["probe-notes"] >= slot[0] > 0.0


# ------------------------------------------------------------------ over the wire
@pytest.fixture(scope="module")
def served():
    """A loop-served WebSocket session over a small table: tagged and untagged frames, and the window's
    two stats() snapshots round them."""
    from surrealdb_tpu.net import ws as wsproto
    from surrealdb_tpu.net.server import serve

    mp = pytest.MonkeyPatch()
    mp.setattr(cnf, "SLOW_QUERY_THRESHOLD_SECS", 0.0)  # every statement leaves a slow-query record
    mp.setattr(cnf, "TRACE_SAMPLE", 1.0)  # and every trace is kept
    mp.setattr(telemetry, "CPU_SLOT_EVERY_S", 0.0)  # a thread leaves its clock after every task and pass
    telemetry.reset()
    tracing.store_reset()
    srv = serve("memory", port=0, auth_enabled=False).start_background()
    try:
        assert cnf.NET_LOOP
        s = Session.owner("t", "t")
        assert srv.ds.execute("CREATE person:1 SET name = 'x'; CREATE person:2 SET name = 'y';", s)[-1]["status"] == "OK"
        sock = socket.create_connection((srv.host, srv.port))
        bs = wsproto.BufferedSocket(sock, wsproto.client_handshake(sock, f"{srv.host}:{srv.port}", "/rpc"))

        def rpc(req):
            sock.sendall(wsproto.encode_frame(wsproto.OP_TEXT, json.dumps(req).encode(), mask=True))
            return json.loads(wsproto.read_frame(bs)[1])

        rpc({"id": 1, "method": "use", "params": ["t", "t"]})
        snaps = [srv.ds.dispatch.stats()]
        t0 = time.perf_counter()
        for i in range(40):
            req = {"id": 2 + i, "method": "query", "params": [SQL]}
            if i % 2 == 0:
                req["trace"] = f"cpu-wire-{i}"
            assert rpc(req)["result"][-1]["status"] == "OK"
            if i % 10 == 9:
                snaps.append(srv.ds.dispatch.stats())
        wall = time.perf_counter() - t0
        from test_wire_spans import _complete

        doc = _complete("cpu-wire-0")
        with urllib.request.urlopen(f"http://{srv.host}:{srv.port}/trace/cpu-wire-0", timeout=30) as r:
            shown = json.loads(r.read())
        sock.close()
        untagged = [d for d in (tracing.get_trace(t) for t in tracing.trace_ids())
                    if d["name"] == "ws_rpc" and not d["trace_id"].startswith("cpu-wire-") and _named(d, "statement")]
        yield {"doc": doc, "shown": shown, "snaps": snaps, "wall": wall, "untagged": untagged,
               "slow": telemetry.slow_queries(),
               "threads": cnf.NET_EXECUTORS + cnf.NET_LOOPS}
    finally:
        srv.shutdown()
        srv.ds.close()
        mp.undo()


@pytest.mark.parametrize("key", CPU_KEYS)
def test_a_sum_is_monotone_over_served_requests(served, key):
    xs = [s[key] for s in served["snaps"]]
    assert xs == sorted(xs)


def test_the_pool_and_the_loop_burned_cpu_and_no_more_than_their_threads_could(served):
    first, last = served["snaps"][0], served["snaps"][-1]
    d_exec, d_loop = last["cpu_exec_s"] - first["cpu_exec_s"], last["cpu_loop_s"] - first["cpu_loop_s"]
    assert d_exec > 0.0 and d_loop > 0.0
    # the slots are the process's: a server another test left running idles, and idling burns nothing
    assert d_exec + d_loop <= served["wall"] * served["threads"]


def test_over_the_wire_the_workers_spans_carry_cpu_and_the_loops_do_not(served):
    doc = served["doc"]
    for name in ("ws_rpc", "statement", "ws_encode"):
        assert all("cpu_ms" in s for s in _named(doc, name)) and _named(doc, name), name
    # the loop thread's four (its CPU is `cpu_loop_s`), and the worker's containers, which read no clock
    for name in ("ws_decode", "ws_admit_wait", "ws_exec_wait", "ws_write", "rpc_method", "execute"):
        assert _named(doc, name) and not [s for s in _named(doc, name) if "cpu_ms" in s], name
    (root,), (encode,) = _named(doc, "ws_rpc"), _named(doc, "ws_encode")
    assert 0.0 < root["cpu_ms"] <= root["dur_ms"] + 1.0 and 0.0 <= encode["cpu_ms"] <= encode["dur_ms"] + 1.0


def test_get_trace_shows_cpu_ms_a_span(served):
    shown = served["shown"]
    by_name = {s["name"]: s for s in shown["spans"]}
    assert by_name["ws_rpc"]["cpu_ms"] == _named(served["doc"], "ws_rpc")[0]["cpu_ms"]
    assert "cpu_ms" in by_name["statement"] and "cpu_ms" not in by_name["ws_decode"]


def test_an_untagged_frames_spans_carry_none(served):
    assert len(served["untagged"]) >= 10
    assert not [s for d in served["untagged"] for s in d["spans"] if "cpu_ms" in s]


def test_a_slow_statements_dispatch_delta_holds_the_pools_and_the_loops_cpu(served):
    records = [e for e in served["slow"] if e.get("sql", "").startswith("SELECT * FROM person")]
    assert len(records) >= 40
    # what the OTHER threads burned during the statement's stretch (its own worker writes its slot when
    # the task ends): one session at a time, so next to nothing here; never negative
    for e in records:
        assert set(CPU_KEYS) <= set(e["dispatch"]) and all(0.0 <= e["dispatch"][k] < 1.0 for k in CPU_KEYS)
