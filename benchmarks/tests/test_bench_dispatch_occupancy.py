"""The seven dispatch readers of ISSUE 35 (`dispatch.fed_share`,
`.launching_share`, `.handoff_share`, `.empty_share`, `.ready_wait_ms`,
`.fetch_ms`, `.wake_ms`): each over a hand-made `ctx`, the manifest entries
after those that were there (together and in order, not "at the end": the
next PR appends), and in a traced CPU rehearsal of a graph cell and a vector
cell the seven on the last line, the four state sums adding up to the window
and the collect to its two halves. (A new file: a program PR edits none of
the benchmark's.)"""

import pytest

from harness import manifest as mf
from test_bench_rehearsal import fresh_program_state, rehearse, well_formed  # noqa: F401
from test_bench_served_spans import ctx_of
from test_bench_snbsf3 import doc

ENTRIES = [
    ("dispatch.fed_share", "ratio", "higher", "program_counter", "stmt_per_s"),
    ("dispatch.launching_share", "ratio", "lower", "program_counter", "stmt_per_s"),
    ("dispatch.handoff_share", "ratio", "lower", "program_counter", "p95_ms"),
    ("dispatch.empty_share", "ratio", "lower", "program_counter", "stmt_per_s"),
    ("dispatch.ready_wait_ms", "ms", "lower", "program_counter", "p50_ms"),
    ("dispatch.fetch_ms", "ms", "lower", "program_counter", "p50_ms"),
    ("dispatch.wake_ms", "ms", "lower", "program_span", "p95_ms"),
]
NAMES = [e[0] for e in ENTRIES]
SHARES, STATES = NAMES[:4], ("fed_s", "launching_s", "handoff_s", "empty_s")
# what the parent's stats() has: the window's delta of a program older than the state clock
OLD = {"submitted": 400, "dispatches": 200, "batched": 200, "retries": 0, "splits": 0, "failures": 0,
       "launch_s": 0.8, "collect_s": 0.3, "pipeline_wait_s": 0.0}


@pytest.fixture(scope="module")
def readers():
    return mf.load_modules(mf.BENCH_DIR, "layer_metrics", "NAME")


def window(**dispatch):
    return {"window": {"dispatch": dispatch}, "tagged": []}


def wake(dur_ms, start_ms=5.0):
    return {"id": 9, "parent": 5, "name": "dispatch_wake", "labels": {}, "start_ms": start_ms, "dur_ms": dur_ms, "error": None}


def test_the_manifest_has_the_seven_together_in_order_after_those_that_were_there(readers):
    manifest = mf.load()
    assert mf.problems(manifest) == []
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(NAMES[0])
    assert names[at:at + 7] == NAMES and at > names.index("knn_subset_roofline")
    assert all(names.count(n) == 1 for n in NAMES)
    for entry, (name, unit, better, source, moves) in zip(manifest["per_layer"][at:at + 7], ENTRIES):
        # no `workloads`: every cell dispatches
        assert entry == {"name": name, "unit": unit, "better": better, "source": source, "layer": "dispatch", "moves": moves}
        r = readers[name]
        assert (r.NAME, r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (name, unit, "dispatch", moves, source)
    # this PR only adds: the dispatch metrics that were there stay
    assert {"dispatch.width_mean", "dispatch.queue_wait_ms", "dispatch.launch_ms", "dispatch.collect_ms",
            "kernel.window_ms_per_dispatch"} <= set(names[:at])


@pytest.mark.parametrize("sums", [
    (0.6, 13.5, 1.2, 14.7),  # a host-bound cell
    (29.9, 0.04, 0.03, 0.03),  # a kernel-bound one
    (0.0, 0.0, 0.0, 30.0),  # no load
    (3.0, 0.0, 0.0, 0.0),
], ids=["host_bound", "kernel_bound", "no_load", "all_fed"])
def test_the_four_shares_sum_to_one(readers, sums):
    ctx = window(**OLD, **dict(zip(STATES, sums)), ready_wait_s=0.2, fetch_s=0.1)
    shares = [readers[n].read(ctx) for n in SHARES]
    assert sum(shares) == pytest.approx(1.0, abs=1e-12)
    assert shares == pytest.approx([s / sum(sums) for s in sums])


@pytest.mark.parametrize("name", SHARES)
def test_a_share_is_none_without_the_clock_or_without_a_second(readers, name):
    read = readers[name].read
    assert read(window(**OLD)) is None  # the parent's program
    full = dict(zip(STATES, (1.0, 2.0, 3.0, 4.0)))
    for missing in STATES:
        assert read(window(**OLD, **{k: v for k, v in full.items() if k != missing})) is None
    assert read(window(**OLD, **dict.fromkeys(STATES, 0.0))) is None  # a window of no length
    assert read(window(**OLD, **full)) is not None


@pytest.mark.parametrize("name, key", [("dispatch.ready_wait_ms", "ready_wait_s"), ("dispatch.fetch_ms", "fetch_s")])
def test_the_two_halves_of_a_collect_a_dispatch(readers, name, key):
    read = readers[name].read
    assert read(window(**OLD, **{key: 0.25})) == pytest.approx(1.25)  # 0.25 s over 200 dispatches
    assert read(window(**OLD, **{key: 0.0})) == 0.0  # a closure that names no outputs: fetch_s 0 is a reading
    assert read(window(**OLD)) is None  # the parent's program
    assert read(window(**{**OLD, "dispatches": 0}, **{key: 0.0})) is None


def test_the_halves_add_up_to_the_collect(readers):
    ctx = window(**OLD, ready_wait_s=0.22, fetch_s=0.08)
    both = readers["dispatch.ready_wait_ms"].read(ctx) + readers["dispatch.fetch_ms"].read(ctx)
    assert both == pytest.approx(OLD["collect_s"] / OLD["dispatches"] * 1e3)


def test_wake_ms_is_the_median_of_each_requests_wake_spans_summed(readers):
    read = readers["dispatch.wake_ms"].read
    # a statement of three dispatches wakes three times; one of one, once
    ctx = ctx_of(doc(wake(0.1), wake(0.2, 15.0), wake(0.3, 25.0)), doc(wake(0.05)), doc(wake(0.2)))
    assert read(ctx) == pytest.approx(0.2)
    assert read(ctx_of(doc(wake(0.4)), doc())) == pytest.approx(0.4)  # a doc without the span is left out
    for nothing in (ctx_of(), ctx_of(doc()), ctx_of(doc(), doc())):
        assert read(nothing) is None


def test_a_program_without_the_clock_reports_only_the_span_metric(readers):
    """What the parent's program gives the seven: `dispatch.wake_ms` (its span
    is PR 24's) and nothing else."""
    ctx = {**window(**OLD), **ctx_of(doc(wake(0.07)))}
    got = {n: readers[n].read(ctx) for n in NAMES}
    assert got == {**dict.fromkeys(NAMES[:6]), "dispatch.wake_ms": pytest.approx(0.07)}


@pytest.mark.parametrize("workload", ["snbsf1.hop3_c8", "vec1m768.knn_c1"])
def test_a_traced_rehearsal_reports_the_seven_and_the_window_adds_up(workload, capsys):
    manifest = mf.load()
    line, phases = rehearse(workload, True, capsys)
    well_formed(line, manifest, workload, True)
    assert line["correct"] is True, phases["check"]
    assert set(NAMES) <= set(line["metrics"])
    got = {n: line["metrics"][n]["value"] for n in NAMES}
    assert sum(got[n] for n in SHARES) == pytest.approx(1.0, abs=1e-9)
    assert all(0.0 <= got[n] <= 1.0 for n in SHARES) and got["dispatch.fed_share"] > 0.0
    assert got["dispatch.ready_wait_ms"] > 0.0 and got["dispatch.fetch_ms"] > 0.0 and got["dispatch.wake_ms"] > 0.0
    win, in_slice = phases["window"], phases["traced"]["slice_dispatch"]
    # the window's wall time as the program counted it, against the harness's clock (on the chip they
    # differ by 0.1 ms of 30 s; here the harness's thread shares one interpreter lock with the server's
    # between its stamp and its stats() call, at each end)
    assert sum(win["dispatch"][k] for k in STATES) == pytest.approx(win["seconds"], abs=0.05)
    for d in (win["dispatch"], in_slice):
        assert d["ready_wait_s"] + d["fetch_s"] == pytest.approx(d["collect_s"], rel=0.02, abs=2e-4)
