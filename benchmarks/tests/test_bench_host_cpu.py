"""The seven readers of ISSUE 49, the interpreter's books (`host.cpu_busy_share`,
`host.cpu_loop_share`, `exec.cpu_ms_per_stmt`, `wire.cpu_ms_per_stmt`,
`dispatch.launch_cpu_share`, `exec.cpu_ms`, `host.lock_wait_ms`): each over a
hand-made `ctx`, the manifest entries after those that were there (together
and in order, not "at the end": the next PR appends), and in a traced CPU
rehearsal of a graph cell and a vector cell the seven on the last line, the
two per-statement counters adding up to the busy share. (A new file: a program
PR edits none of the benchmark's. The repo's tier 1 runs the hand-made cases
too: `tests/test_span_cpu.py` takes them from here by name.)"""

import pytest

from harness import manifest as mf
from test_bench_rehearsal import fresh_program_state, rehearse, well_formed  # noqa: F401

ENTRIES = [
    ("host.cpu_busy_share", "ratio", "lower", "program_counter", "host runtime", "stmt_per_s"),
    ("host.cpu_loop_share", "ratio", "lower", "program_counter", "host runtime", "p50_ms"),
    ("exec.cpu_ms_per_stmt", "ms", "lower", "program_counter", "parse/plan + executor", "stmt_per_s"),
    ("wire.cpu_ms_per_stmt", "ms", "lower", "program_counter", "wire", "stmt_per_s"),
    ("dispatch.launch_cpu_share", "ratio", "higher", "program_counter", "dispatch", "stmt_per_s"),
    ("exec.cpu_ms", "ms", "lower", "program_span", "parse/plan + executor", "p50_ms"),
    ("host.lock_wait_ms", "ms", "lower", "program_span", "host runtime", "p50_ms"),
]
NAMES = [e[0] for e in ENTRIES]
STATES = {"fed_s": 6.0, "launching_s": 14.0, "handoff_s": 2.0, "empty_s": 8.0}  # a window of 30 s
# what the parent's stats() has: the state clock, and none of the three CPU sums
OLD = {"submitted": 15000, "dispatches": 4000, "batched": 11000, "retries": 0, "splits": 0, "failures": 0,
       "launch_s": 8.0, "collect_s": 3.0, "pipeline_wait_s": 0.0, "ready_wait_s": 2.0, "fetch_s": 1.0, **STATES}
NEW = {**OLD, "cpu_exec_s": 21.0, "cpu_loop_s": 3.0, "launch_cpu_s": 0.6, "launch_cpu_of_s": 0.8}  # 300 launches sampled


@pytest.fixture(scope="module")
def readers():
    return mf.load_modules(mf.BENCH_DIR, "layer_metrics", "NAME")


def window(dispatch, records=15000):
    return {"window": {"dispatch": dispatch, "records": [{}] * records}, "tagged": []}


def span(name, start_ms, dur_ms, cpu_ms=None, parent=1):
    s = {"id": 9, "parent": parent, "name": name, "labels": {}, "start_ms": start_ms, "dur_ms": dur_ms, "error": None}
    return s if cpu_ms is None else {**s, "cpu_ms": cpu_ms}


def doc(*spans, dur_ms=9.0, cpu_ms=1.0):
    return {"trace_id": "t", "ts": 0.0, "spans": [span("ws_rpc", 0.0, dur_ms, cpu_ms, parent=None), *spans]}


def tagged(*docs):
    return {"tagged": [{"record": {"t0": 100.0, "t1": 100.013}, "doc": d} for d in docs]}


def rider(**root):
    """A request that rode someone else's batch: asleep from its submit (1.0) to the collect's end (7.0)
    but for the leader's chores between launch and collect (5.0-5.5); woken 0.4 ms later."""
    return doc(span("dispatch_queue_wait", 1.0, 2.0), span("dispatch_launch", 3.0, 2.0),
               span("dispatch_collect", 5.5, 1.5), span("dispatch_ready_wait", 5.5, 1.2),
               span("dispatch_fetch", 6.7, 0.3), span("dispatch_wake", 7.0, 0.4),
               span("ws_encode", 9.0, 0.06, 0.05), **root)


def leader(**root):
    """The request whose thread led: its launch and collect carry its CPU, its sleeps are a short queue
    wait (the gathering) and the device's, which says what of it the thread ran after all."""
    return doc(span("dispatch_queue_wait", 1.0, 0.5), span("dispatch_launch", 1.5, 2.0, 1.6),
               span("dispatch_collect", 3.6, 1.5, 0.2), span("dispatch_ready_wait", 3.6, 1.2, 0.05),
               span("dispatch_fetch", 4.8, 0.3), span("ws_encode", 9.0, 0.06, 0.05), **root)


def test_the_manifest_has_the_seven_together_in_order_after_those_that_were_there(readers):
    manifest = mf.load()
    assert mf.problems(manifest) == []
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(NAMES[0])
    assert names[at:at + 7] == NAMES and at > names.index("graph.reach_rows_share")
    assert all(names.count(n) == 1 for n in NAMES)
    for entry, (name, unit, better, source, layer, moves) in zip(manifest["per_layer"][at:at + 7], ENTRIES):
        # no `workloads`: every cell has an interpreter
        assert entry == {"name": name, "unit": unit, "better": better, "source": source, "layer": layer, "moves": moves}
        r = readers[name]
        assert (r.NAME, r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (name, unit, layer, moves, source)
    # this PR only adds: the outside subtractions stay
    assert {"exec.host_ms", "wire.ms", "wire.write_ms", "host.gc_ms", "stmt.unattributed_ms",
            "dispatch.launching_share"} <= set(names[:at])


@pytest.mark.parametrize("name, value", [
    ("host.cpu_busy_share", (21.0 + 3.0) / 30.0),
    ("host.cpu_loop_share", 3.0 / 30.0),
    ("exec.cpu_ms_per_stmt", 21.0 * 1e3 / 15000),
    ("wire.cpu_ms_per_stmt", 3.0 * 1e3 / 15000),
    ("dispatch.launch_cpu_share", 0.6 / 0.8),
])
def test_a_counter_reader_over_a_hand_made_window(readers, name, value):
    read = readers[name].read
    assert read(window(NEW)) == pytest.approx(value)
    assert read(window(OLD)) is None  # the parent's program: the state clock without the CPU sums
    # a window of no length, of no completed request, of no launch: nothing to divide by, and no 0
    assert read(window({**NEW, **dict.fromkeys(STATES, 0.0), "launch_cpu_of_s": 0.0}, records=0)) is None


def test_the_two_per_statement_counters_are_the_busy_share_over_another_denominator(readers):
    ctx = window(NEW, records=15000)
    per_stmt = readers["exec.cpu_ms_per_stmt"].read(ctx) + readers["wire.cpu_ms_per_stmt"].read(ctx)
    stmt_per_s = 15000 / sum(STATES.values())
    assert per_stmt * stmt_per_s / 1e3 == pytest.approx(readers["host.cpu_busy_share"].read(ctx))


def test_exec_cpu_ms_is_the_root_and_the_encode_of_the_requests_own_thread(readers):
    read = readers["exec.cpu_ms"].read
    assert read(tagged(rider(cpu_ms=1.0))) == pytest.approx(1.05)
    assert read(tagged(rider(cpu_ms=1.0), leader(cpu_ms=2.8), leader(cpu_ms=3.0))) == pytest.approx(6.95 / 3)
    # the MEAN: on a clock that ticks every 10 ms most requests read 0 and the sum is what counts
    assert read(tagged(*[rider(cpu_ms=0.0)] * 8, rider(cpu_ms=10.0), rider(cpu_ms=10.0))) == pytest.approx(2.05)
    # a leader's launch is inside its root: counted once, with the root
    assert read(tagged(leader(cpu_ms=2.8))) == pytest.approx(2.85)
    # a root without `cpu_ms` (the parent's program; an untagged trace) is left out, not read as 0
    assert read(tagged(rider(cpu_ms=None), rider(cpu_ms=1.0))) == pytest.approx(1.05)
    for nothing in (tagged(), tagged(rider(cpu_ms=None)), tagged(doc(cpu_ms=None), doc(cpu_ms=None))):
        assert read(nothing) is None


def test_lock_wait_ms_counts_a_riders_copy_as_sleep_and_a_leaders_own_as_work(readers):
    read = readers["host.lock_wait_ms"].read
    # rider: 9.0 - 1.0 of CPU - asleep [1.0, 5.0] + [5.5, 7.0] = 5.5 -> 2.5 (the wake, the chores, the rest)
    assert read(tagged(rider())) == pytest.approx(2.5)
    # leader: 9.0 - 2.8 of CPU - asleep [1.0, 1.5] + [3.6, 4.8] less the 0.05 it ran in the device's wait
    # = 1.65 -> 4.55: its launch and collect are work
    assert read(tagged(leader(cpu_ms=2.8))) == pytest.approx(4.55)
    assert read(tagged(rider(), leader(cpu_ms=2.8), leader(cpu_ms=2.8))) == pytest.approx((2.5 + 4.55 + 4.55) / 3)
    # the MEAN: nine requests that read no tick and one that read one are 1 ms of CPU each
    assert read(tagged(*[rider(cpu_ms=0.0)] * 9, rider(cpu_ms=10.0))) == pytest.approx(9.0 - 1.0 - 5.5)
    # a root without `cpu_ms` counts for nothing
    assert read(tagged(rider(cpu_ms=None), rider())) == pytest.approx(2.5)
    for nothing in (tagged(), tagged(rider(cpu_ms=None)), tagged(leader(cpu_ms=None))):
        assert read(nothing) is None


def test_lock_wait_ms_takes_the_sleeps_inside_the_root_and_once(readers):
    read = readers["host.lock_wait_ms"].read
    # the copy of the leader's semaphore wait starts before this rider had submitted: left out
    early = doc(span("dispatch_pipeline_wait", 0.2, 2.8), span("dispatch_queue_wait", 2.0, 1.0))
    assert read(tagged(early)) == pytest.approx(9.0 - 1.0 - 1.0)
    # a collect that ends after the root (cannot happen; a reader clips anyway) and overlapping sleeps
    over = doc(span("dispatch_queue_wait", 6.0, 2.0), span("dispatch_collect", 7.0, 5.0), span("ws_write", 9.1, 3.0))
    assert read(tagged(over)) == pytest.approx(9.0 - 1.0 - 3.0)
    # a request that dispatches nothing: all of its wall but its CPU
    assert read(tagged(doc(span("plan_fetch", 0.1, 0.2), span("statement", 0.4, 8.0, 0.9)))) == pytest.approx(8.0)
    # CPU inside a span counted as sleep reads negative, and is not clamped
    assert read(tagged(doc(span("dispatch_queue_wait", 0.0, 8.5), cpu_ms=1.0))) == pytest.approx(-0.5)


def test_the_parents_program_gives_the_seven_nothing(readers):
    """The parent's keys and the parent's spans (no `cpu_ms` anywhere): every one of the seven is left
    out of the line, none raises, none reads 0."""
    ctx = {**window(OLD), **tagged(rider(cpu_ms=None), leader(cpu_ms=None))}
    for s in (s for t in ctx["tagged"] for s in t["doc"]["spans"]):
        s.pop("cpu_ms", None)
    assert {n: readers[n].read(ctx) for n in NAMES} == dict.fromkeys(NAMES)


@pytest.mark.parametrize("workload", ["snbsf1.hop3_c8", "vec1m768.knn_c1"])
def test_a_traced_rehearsal_reports_the_seven_and_the_cpu_adds_up(workload, capsys):
    manifest = mf.load()
    line, phases = rehearse(workload, True, capsys)
    well_formed(line, manifest, workload, True)
    assert line["correct"] is True, phases["check"]
    assert set(NAMES) <= set(line["metrics"])
    got = {n: line["metrics"][n]["value"] for n in NAMES}
    assert 0.0 < got["host.cpu_loop_share"] < got["host.cpu_busy_share"]
    assert got["exec.cpu_ms_per_stmt"] > 0.0 and got["wire.cpu_ms_per_stmt"] > 0.0 and got["exec.cpu_ms"] > 0.0
    # two clocks around one stretch; on THIS machine's clock, which ticks in nanoseconds, a one-session
    # cell's share is under 1 too (on the chip's host it is not a reading there: the reader says why)
    assert 0.0 < got["dispatch.launch_cpu_share"] <= 1.05
    # one session on this machine: nothing to wait for, so the books close to the few microseconds a leader
    # runs inside its own `dispatch_queue_wait` (submit to launch is its own bookkeeping), which the reader
    # takes as sleep
    assert got["host.lock_wait_ms"] >= -0.03
    win = phases["window"]
    wall = sum(win["dispatch"][k] for k in STATES)
    per_stmt = got["exec.cpu_ms_per_stmt"] + got["wire.cpu_ms_per_stmt"]
    assert per_stmt * win["completed"] / wall / 1e3 == pytest.approx(got["host.cpu_busy_share"])
    # the slice's delta has the keys too: the harness differences every numeric key of stats()
    assert {"cpu_exec_s", "cpu_loop_s", "launch_cpu_s", "launch_cpu_of_s"} <= set(phases["traced"]["slice_dispatch"])
