"""The dispatcher keeps the device's books (ISSUE 35): every second between
two `stats()` snapshots is counted once, as fed, launching, handing off or
empty; a collect splits into the device's wait and the read-back where its
closure says which arrays it reads (`outputs`). Scripted two-phase runners:
sleeps for the phases, a fake array whose `block_until_ready` sleeps for the
device. The real collect closures run on the CPU backend."""

import threading
import time

import numpy as np
import pytest

from surrealdb_tpu import bg, cnf, telemetry, tracing
from surrealdb_tpu.dbs.dispatch import DispatchQueue

STATES = ("fed_s", "launching_s", "handoff_s", "empty_s")
# Lower bounds only: a sleep never returns early, and since the four sums add
# up to the wall time (checked in `between`), a second booked under the wrong
# state leaves its own state short.


@pytest.fixture(autouse=True)
def fresh(monkeypatch):
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    monkeypatch.setattr(cnf, "DISPATCH_RETRY_BACKOFF_SECS", 0.0)
    telemetry.reset()
    tracing.store_reset()
    yield
    tracing.store_reset()


class FakeArray:
    """What a collect closure reads: ready on the device after `wait_s`."""

    def __init__(self, wait_s: float = 0.0, error: BaseException = None):
        self.wait_s, self.error, self.calls = wait_s, error, []

    def copy_to_host_async(self):
        self.calls.append("copy")

    def block_until_ready(self):
        self.calls.append("wait")
        time.sleep(self.wait_s)
        if self.error is not None:
            raise self.error
        return self


def two_phase(launch_s=0.0, ready_s=0.0, fetch_s=0.0, outputs=True, started=None, gate=None):
    """A runner whose launch phase takes `launch_s` and whose collect finds the
    device ready after `ready_s` and reads back for `fetch_s`; a payload's
    result is its double. With `gate`, the launch waits for it (and says so
    through `started`): whatever is submitted meanwhile queues behind."""

    def runner(payloads):
        if started is not None:
            started.set()
        if gate is not None:
            assert gate.wait(30)
        time.sleep(launch_s)

        def collect():
            time.sleep(fetch_s if outputs else ready_s + fetch_s)
            return [p * 2 for p in payloads]

        if outputs:
            collect.outputs = (FakeArray(ready_s),)
        return collect

    return runner


def snapshot(q):
    """(stats, the clock just before, the clock just after)."""
    a = time.perf_counter()
    st = q.stats()
    return st, a, time.perf_counter()


def between(q, before, after):
    """The state sums' deltas of two snapshots, after checking that they add
    up to the wall time between them (within 1 ms of what the clock reads
    round the two calls)."""
    (s0, a0, b0), (s1, a1, b1) = before, after
    d = {k: s1[k] - s0[k] for k in s1}
    total = sum(d[k] for k in STATES)
    assert a1 - b0 - 0.001 <= total <= b1 - a0 + 0.001, (total, a1 - b0, b1 - a0)
    assert all(d[k] >= -1e-6 for k in STATES), d
    return d


def quiet(q):
    return (q._queued, q._launching, q._inflight) == (0, 0, 0)


def run_threads(*fns):
    """Run each of `fns` on a thread of its own; returns what each returned
    or raised, in order."""
    out = [None] * len(fns)

    def body(i, fn):
        try:
            out[i] = fn()
        except BaseException as e:  # noqa: BLE001 - handed to the test
            out[i] = e

    ts = [threading.Thread(target=body, args=(i, fn)) for i, fn in enumerate(fns)]
    for t in ts:
        t.start()
    return ts, out


def join_all(ts):
    for t in ts:
        t.join(60)
    assert not any(t.is_alive() for t in ts)


def batch_behind_a_leader(q, n, runner, traced=False):
    """`n` riders (payloads 1..n) that ride ONE dispatch of `runner`: a lead
    request (payload 0) holds the bucket in its gated launch while they
    queue. Returns what the riders got (a result or the exception, in
    order) and what the lead got."""
    started, gate = threading.Event(), threading.Event()

    def held(payloads):
        if payloads != [0]:
            return runner(payloads)
        started.set()
        assert gate.wait(30)
        return two_phase()(payloads)

    def rider(i):
        def go():
            if not traced:
                return q.submit("k", i, held)
            with tracing.request("req", trace_id=f"rider-{i}"), telemetry.span("statement"):
                return q.submit("k", i, held)

        return go

    t0, out0 = run_threads(rider(0))
    assert started.wait(30)
    ts, out = run_threads(*[rider(i) for i in range(1, n + 1)])
    deadline = time.monotonic() + 30
    while q._queued < n and time.monotonic() < deadline:
        time.sleep(0.002)
    assert (q._queued, q._launching, q._inflight) == (n, 1, 0)
    gate.set()
    join_all(t0 + ts)
    return out, out0[0]


# ------------------------------------------------------------------ the clock
def test_every_second_of_a_lone_dispatch_lands_in_its_scripted_state():
    q = DispatchQueue()
    s0 = snapshot(q)
    time.sleep(0.05)  # nothing submitted yet
    assert q.submit("k", 21, two_phase(launch_s=0.04, ready_s=0.06, fetch_s=0.03)) == 42
    s1 = snapshot(q)
    d = between(q, s0, s1)
    assert d["launching_s"] >= 0.04 - 0.002
    assert d["fed_s"] >= 0.06 - 0.002
    # a lone request's read-back: the device is done and nothing else waits
    assert d["empty_s"] >= 0.05 + 0.03 - 0.002
    assert d["handoff_s"] <= 0.02  # submit to the launch's head, on one thread
    assert d["ready_wait_s"] >= 0.06 - 0.002 and d["fetch_s"] >= 0.03 - 0.002
    assert d["ready_wait_s"] + d["fetch_s"] == pytest.approx(d["collect_s"], abs=2e-4)
    assert quiet(q)
    # a quiet queue goes on counting, as empty
    time.sleep(0.02)
    d = between(q, s1, snapshot(q))
    assert d["empty_s"] >= 0.02 - 0.002 and d["fed_s"] == d["launching_s"] == d["handoff_s"] == 0.0


def test_a_promoted_leader_waiting_for_the_pipeline_with_nothing_in_flight_is_a_handoff():
    """Depth 1: the promoted leader sits on the depth semaphore while the
    batch in front reads back. The states take precedence in the table's
    order: a queued request counts as launching, then fed, while those last."""
    q = DispatchQueue(pipeline_depth=1)
    started, gate = threading.Event(), threading.Event()
    s0 = snapshot(q)
    first = two_phase(launch_s=0.03, ready_s=0.04, fetch_s=0.07, started=started, gate=gate)
    ta, outa = run_threads(lambda: q.submit("k", 1, first))
    assert started.wait(30)
    tb, outb = run_threads(lambda: q.submit("k", 2, two_phase(launch_s=0.01, ready_s=0.01)))
    deadline = time.monotonic() + 30
    while q._queued < 1 and time.monotonic() < deadline:
        time.sleep(0.001)
    gate.set()
    join_all(ta + tb)
    assert (outa, outb) == ([2], [4])
    d = between(q, s0, snapshot(q))
    assert d["handoff_s"] >= 0.07 - 0.003
    assert d["fed_s"] >= 0.04 + 0.01 - 0.003
    assert d["launching_s"] >= 0.03 + 0.01 - 0.003
    assert d["empty_s"] <= 0.05  # a thread's start, a poll of 1 ms: nothing scripted
    assert d["dispatches"] == 2 and d["pipeline_wait_s"] >= 0.07 - 0.003
    assert quiet(q)


def test_the_queue_starts_every_copy_before_it_waits_for_any_array_and_reads_after():
    q, order = DispatchQueue(), []
    a, b = FakeArray(), FakeArray()
    a.calls = b.calls = order

    def runner(payloads):
        def collect():
            order.append("read")
            return list(payloads)

        collect.outputs = (a, b)
        return collect

    assert q.submit("k", 7, runner) == 7
    assert order == ["copy", "copy", "wait", "wait", "read"]


def test_a_synchronous_runner_is_launching_for_its_whole_run():
    q = DispatchQueue()
    s0 = snapshot(q)

    def runner(payloads):
        time.sleep(0.04)
        return [p + 1 for p in payloads]

    assert q.submit("k", 1, runner) == 2
    d = between(q, s0, snapshot(q))
    assert d["launching_s"] >= 0.04 - 0.002
    assert d["fed_s"] == d["ready_wait_s"] == d["fetch_s"] == d["collect_s"] == 0.0
    assert quiet(q)
    # with no collect, the dispatch's end is the launch's: /metrics has the seconds
    fam = {dict(k)["state"]: v for k, v in telemetry.counters_matching("dispatch_device_seconds").items()}
    assert fam["launching"] == pytest.approx(d["launching_s"], abs=1e-3) and fam["fed"] == 0.0


def test_two_buckets_share_one_clock():
    """The device is one: a batch in flight on one key keeps the queue fed
    while another key launches."""
    q = DispatchQueue()
    started = threading.Event()
    s0 = snapshot(q)
    ta, outa = run_threads(lambda: q.submit("a", 1, two_phase(ready_s=0.08, started=started)))
    assert started.wait(30)
    time.sleep(0.01)
    assert q.submit("b", 2, two_phase(launch_s=0.03)) == 4
    join_all(ta)
    d = between(q, s0, snapshot(q))
    assert d["fed_s"] >= 0.08 - 0.003 and d["launching_s"] <= 0.01
    assert quiet(q)


# ------------------------------------------------------------------ failure paths
def _boom(payloads):
    raise ValueError("bad payload")


def _boom_in_collect(payloads):
    def collect():
        raise ValueError("bad download")

    collect.outputs = (FakeArray(),)
    return collect


def _device_fails_while_waited_for(payloads):
    def collect():
        raise AssertionError("never read back")

    collect.outputs = (FakeArray(0.01, error=ValueError("device fault")),)
    return collect


def _too_few(payloads):
    def collect():
        return [0] * (len(payloads) - 1)

    collect.outputs = (FakeArray(),)
    return collect


def _too_few_at_once(payloads):
    return [0] * (len(payloads) + 1)


def _transient_when_wide(phase):
    """RESOURCE_EXHAUSTED for any batch wider than one, in the launch or in
    the collect; alone, a payload is doubled."""

    def runner(payloads):
        wide = len(payloads) > 1
        if wide and phase == "launch":
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

        def collect():
            if wide:
                raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
            return [p * 2 for p in payloads]

        collect.outputs = (FakeArray(0.002),)
        return collect

    return runner


@pytest.mark.parametrize("runner, outcome, retried", [
    (two_phase(0.002, 0.002, 0.002), "ok", False),
    (two_phase(0.002, 0.002, 0.002, outputs=False), "ok", False),
    (_boom, ValueError, False),
    (_boom_in_collect, ValueError, False),
    (_device_fails_while_waited_for, ValueError, False),
    (_too_few, RuntimeError, False),
    (_too_few_at_once, RuntimeError, False),
    (_transient_when_wide("launch"), "ok", True),
    (_transient_when_wide("collect"), "ok", True),
], ids=["success", "success_without_outputs", "launch_fails", "collect_fails", "ready_wait_fails",
        "wrong_number_of_results", "wrong_number_from_a_sync_runner", "transient_launch_split_retry",
        "transient_collect_split_retry"])
def test_the_counts_are_zero_when_the_queue_is_quiet_again(runner, outcome, retried):
    q = DispatchQueue(split_floor=1)
    s0 = snapshot(q)
    got, lead = batch_behind_a_leader(q, 3, runner)
    assert lead == 0
    if outcome == "ok":
        assert got == [2, 4, 6]
    else:
        assert all(isinstance(g, outcome) for g in got), got
    assert quiet(q)
    d = between(q, s0, snapshot(q))
    assert d["ready_wait_s"] + d["fetch_s"] == pytest.approx(d["collect_s"], abs=2e-4)
    # a wide batch is bisected down to three singles, each re-executed whole
    assert (d["retries"], d["splits"]) == ((2, 2) if retried else (0, 0))
    assert d["failures"] == (0 if outcome == "ok" else 1)


def test_a_lone_transient_failure_retries_whole_and_leaves_no_count():
    q, calls = DispatchQueue(), []

    def runner(payloads):
        calls.append(len(payloads))
        if len(calls) == 1:
            raise RuntimeError("UNAVAILABLE: busy")
        return two_phase(ready_s=0.002)(payloads)

    assert q.submit("k", 4, runner) == 8
    assert calls == [1, 1] and quiet(q) and q.stats()["retries"] == 1


# ------------------------------------------------------------------ spans, histograms, the counter family
def spans_of(rider):
    return {s["name"]: s for s in tracing.get_trace(f"rider-{rider}")["spans"]}


def test_every_rider_gets_the_two_spans_inside_its_collect():
    q = DispatchQueue()
    got, _ = batch_behind_a_leader(q, 3, two_phase(0.002, 0.03, 0.02), traced=True)
    assert got == [2, 4, 6]
    waits = set()
    for i in (1, 2, 3):
        s = spans_of(i)
        col, wait, fetch = s["dispatch_collect"], s["dispatch_ready_wait"], s["dispatch_fetch"]
        assert wait["labels"] == fetch["labels"] == col["labels"] == {"batch": "3"}
        assert wait["parent"] == fetch["parent"] == col["parent"]
        end = col["start_ms"] + col["dur_ms"]
        assert wait["start_ms"] == col["start_ms"]
        assert fetch["start_ms"] == pytest.approx(wait["start_ms"] + wait["dur_ms"], abs=0.002)
        assert fetch["start_ms"] + fetch["dur_ms"] <= end + 0.002
        assert wait["dur_ms"] >= 30 - 2 and fetch["dur_ms"] >= 20 - 2
        # each narrower than the whole: a reader that names an instant by the narrowest span over it names a half
        assert max(wait["dur_ms"], fetch["dur_ms"]) < col["dur_ms"]
        waits.add(wait["dur_ms"])
    assert len(waits) == 1  # one batch, one stamp
    durs = telemetry.snapshot()["durations"]
    assert durs["dispatch_ready_wait"]["count"] == durs["dispatch_fetch"]["count"] == durs["dispatch_collect"]["count"] == 2


def test_a_closure_without_outputs_has_its_whole_collect_counted_as_the_wait():
    q = DispatchQueue()
    s0 = snapshot(q)
    with tracing.request("req", trace_id="rider-0"), telemetry.span("statement"):
        assert q.submit("k", 5, two_phase(0.002, 0.02, 0.02, outputs=False)) == 10
    d = between(q, s0, snapshot(q))
    assert d["fetch_s"] == 0.0 and d["ready_wait_s"] >= 0.04 - 0.002
    assert d["ready_wait_s"] == pytest.approx(d["collect_s"], abs=2e-4)
    assert d["fed_s"] >= 0.04 - 0.002  # in flight until its results are there
    s = spans_of(0)
    assert "dispatch_fetch" not in s and s["dispatch_ready_wait"]["dur_ms"] >= 40 - 2
    durs = telemetry.snapshot()["durations"]
    assert durs["dispatch_ready_wait"]["count"] == 1 and "dispatch_fetch" not in durs


def test_metrics_carry_the_four_states_as_one_counter_family():
    q = DispatchQueue()
    time.sleep(0.02)
    assert q.submit("k", 1, two_phase(0.02, 0.03, 0.01)) == 2
    st = q.stats()
    fam = {dict(k)["state"]: v for k, v in telemetry.counters_matching("dispatch_device_seconds").items()}
    assert sorted(fam) == ["empty", "fed", "handoff", "launching"]
    # fed at the collect's end: whatever ran since is the next dispatch's to report
    assert fam["fed"] == pytest.approx(st["fed_s"], abs=1e-4) and fam["fed"] >= 0.03 - 0.002
    assert fam["launching"] == pytest.approx(st["launching_s"], abs=1e-4)
    assert 0.02 - 0.002 <= fam["empty"] <= st["empty_s"]
    text = telemetry.render_prometheus()
    assert 'surreal_dispatch_device_seconds_total{state="fed"}' in text
    assert "surreal_dispatch_ready_wait_duration_seconds_count 1" in text
    assert "surreal_dispatch_fetch_duration_seconds_count 1" in text


def test_stats_is_scalars_only_and_a_slow_statements_record_carries_the_new_keys(monkeypatch):
    from surrealdb_tpu.kvs.ds import Datastore

    new = set(STATES) | {"ready_wait_s", "fetch_s"}
    st = DispatchQueue().stats()
    assert new | {"collect_s", "launch_s", "dispatches"} <= set(st)
    assert all(isinstance(v, (int, float)) for v in st.values())
    monkeypatch.setattr(cnf, "SLOW_QUERY_THRESHOLD_SECS", 0.0)
    ds = Datastore("memory")
    try:
        ds.execute("RETURN 1;")
        delta = telemetry.slow_queries()[-1]["dispatch"]
    finally:
        ds.close()
    # how the statement's own stretch split over the four states: here, all of it empty
    assert new <= set(delta) and delta["empty_s"] > 0.0 and delta["fed_s"] == 0.0


# ------------------------------------------------------------------ the real closures
def _is_device_array(a):
    import jax

    return isinstance(a, jax.Array)


def test_the_graph_count_collect_names_its_output():
    import jax.numpy as jnp

    from surrealdb_tpu.idx.graph_csr import _collect_counts

    out = jnp.arange(8, dtype=jnp.int32)
    collect = _collect_counts(out, 3, 8)
    assert collect.outputs == (out,) and collect.launch_labels == {"lanes": 8}
    assert collect() == [0, 1, 2]


def _corpus(n=512, d=16, seed=3):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, d)).astype(np.float32)


def test_the_exact_launch_carries_its_outputs_through_zipped_and_a_dispatch_splits_on_them():
    import jax.numpy as jnp

    from surrealdb_tpu.idx.knn import _exact_device_launch, _zipped

    x = _corpus()
    qs = x[:3] + 0.001
    collect = _exact_device_launch(qs, jnp.asarray(x), np.ones(len(x), dtype=bool), "euclidean", 4)
    assert len(collect.outputs) == 2 and all(_is_device_array(a) for a in collect.outputs)
    finish = _zipped(collect)
    assert finish.outputs is collect.outputs
    q = DispatchQueue()
    with tracing.request("req", trace_id="rider-0"), telemetry.span("statement"):
        dists, slots = q.submit("k", qs[0], lambda batch: _zipped(_exact_device_launch(
            np.stack(batch), jnp.asarray(x), np.ones(len(x), dtype=bool), "euclidean", 4)))
    assert int(slots[0]) == 0 and len(dists) == 4
    assert {"dispatch_ready_wait", "dispatch_fetch", "dispatch_collect"} <= set(spans_of(0))
    assert quiet(q)
    assert bg.wait_idle(60)


def test_the_ivf_launch_carries_its_outputs_through_zipped():
    import jax.numpy as jnp

    from surrealdb_tpu.idx.ivf import IvfState, default_nprobe
    from surrealdb_tpu.idx.knn import _zipped

    x = _corpus(2048)
    ivf = IvfState.train(x, np.ones(len(x), dtype=bool))
    nprobe = default_nprobe(ivf.nlists, 64)
    collect = ivf.search_batch_launch(x[:70], jnp.asarray(x), "euclidean", 4, nprobe)  # 70 queries: two tiles of 64
    assert len(collect.outputs) == 4 and all(_is_device_array(a) for a in collect.outputs)
    finish = _zipped(collect)
    assert finish.outputs is collect.outputs
    rows = finish()
    assert len(rows) == 70 and all(int(r[0]) == i for i, (_, r) in enumerate(rows))
    assert bg.wait_idle(60)


def test_zipped_over_a_closure_that_names_nothing_names_nothing():
    from surrealdb_tpu.idx.knn import _zipped

    finish = _zipped(lambda: (np.zeros((1, 2)), np.zeros((1, 2))))
    assert finish.outputs is None and len(finish()) == 1
