"""Observability surface: labeled histograms + Prometheus exposition,
dispatch retry/error accounting, the structured slow-query log, the txn
leak detector, and the /metrics + /slow HTTP round trip
(reference: src/telemetry/mod.rs metrics + RPC/HTTP instrumentation)."""

import gc
import json
import re
import warnings

import pytest

from surrealdb_tpu import cnf, telemetry
from surrealdb_tpu.dbs.dispatch import DispatchQueue

# one exposition sample: name{labels} value  (labels optional)
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"  # metric name
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|\\[\\\"n])*\""  # first label
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|\\[\\\"n])*\")*\})?"  # more labels
    r" (?:[0-9.eE+-]+|\+Inf|-Inf|NaN)$"
)
_TYPE_RE = re.compile(r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|histogram)$")


def assert_valid_exposition(text: str) -> None:
    """Every line is a # TYPE comment or a well-formed sample."""
    for line in text.rstrip("\n").split("\n"):
        assert _TYPE_RE.match(line) or _SAMPLE_RE.match(line), f"bad line: {line!r}"


# ------------------------------------------------------------------ histograms
def test_histogram_bucketing_and_exposition():
    telemetry.reset()
    buckets = (1, 10, 100)
    for v in (0.5, 1, 5, 10, 50, 1000):
        telemetry.observe_hist("obs_test_sizes", v, buckets=buckets, path="x")
    text = telemetry.render_prometheus()
    assert_valid_exposition(text)
    # cumulative le counts: ≤1 -> 2 (0.5 and the boundary value 1), ≤10 -> 4,
    # ≤100 -> 5, +Inf -> 6 (the 1000 overflow)
    assert 'surreal_obs_test_sizes_bucket{path="x",le="1"} 2' in text
    assert 'surreal_obs_test_sizes_bucket{path="x",le="10"} 4' in text
    assert 'surreal_obs_test_sizes_bucket{path="x",le="100"} 5' in text
    assert 'surreal_obs_test_sizes_bucket{path="x",le="+Inf"} 6' in text
    assert 'surreal_obs_test_sizes_count{path="x"} 6' in text
    assert 'surreal_obs_test_sizes_sum{path="x"} 1066.500000' in text
    assert "# TYPE surreal_obs_test_sizes histogram" in text


def test_duration_observe_feeds_histogram_and_summary():
    telemetry.reset()
    telemetry.observe("obs_test_phase", 0.02, phase="launch")
    telemetry.observe("obs_test_phase", 0.2, phase="launch")
    text = telemetry.render_prometheus()
    assert_valid_exposition(text)
    assert 'surreal_obs_test_phase_duration_seconds_count{phase="launch"} 2' in text
    snap = telemetry.snapshot()
    d = snap["durations"]['obs_test_phase{phase="launch"}']
    assert d["count"] == 2 and d["max_s"] == pytest.approx(0.2)


def test_label_escaping_and_snapshot_rendering():
    """Counters with labels must render valid label syntax (not a stringified
    Python dict) and escape quotes/backslashes/newlines."""
    telemetry.reset()
    telemetry.inc("obs_test_errs", kind='say "hi"\\there\nnow')
    text = telemetry.render_prometheus()
    assert_valid_exposition(text)
    assert 'kind="say \\"hi\\"\\\\there\\nnow"' in text
    key = next(k for k in telemetry.snapshot()["counters"] if k.startswith("obs_test_errs"))
    assert "{'" not in key and key.startswith('obs_test_errs{kind="')


# ------------------------------------------------------------------ dispatch accounting
def test_dispatch_transient_retry_counted_by_cause():
    telemetry.reset()
    q = DispatchQueue()
    calls = {"n": 0}

    def flaky(payloads):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("UNAVAILABLE: device is not responding")
        return [p * 2 for p in payloads]

    assert q.submit("k", 21, flaky) == 42
    st = q.stats()
    assert st["retries"] == 1 and st["failures"] == 0
    assert telemetry.get_counter("dispatch_retries", cause="UNAVAILABLE") == 1
    text = telemetry.render_prometheus()
    assert 'surreal_dispatch_retries_total{cause="UNAVAILABLE"} 1' in text


def test_dispatch_deterministic_failure_counted_and_raised():
    telemetry.reset()
    q = DispatchQueue()

    def broken(payloads):
        raise ValueError("bad payload shape")

    with pytest.raises(ValueError):
        q.submit("k", 1, broken)
    st = q.stats()
    assert st["failures"] == 1 and st["retries"] == 0
    assert telemetry.get_counter("dispatch_failures", error="ValueError") == 1


def test_dispatch_batch_size_histogram_observed():
    telemetry.reset()
    q = DispatchQueue()
    q.submit("k", 3, lambda ps: [p + 1 for p in ps])
    snap = telemetry.snapshot()
    assert snap["histograms"]["dispatch_batch_size"]["count"] == 1
    assert "surreal_dispatch_batch_size_bucket" in telemetry.render_prometheus()


# ------------------------------------------------------------------ slow-query log
def test_slow_query_ring_buffer(ds, monkeypatch):
    telemetry.reset()
    monkeypatch.setattr(cnf, "SLOW_QUERY_THRESHOLD_SECS", 0.0)
    ds.execute("CREATE slowt:1 SET v = 1; SELECT * FROM slowt;")
    ds.execute("THROW 'boom';")  # an ERR statement is captured too
    entries = telemetry.slow_queries()
    assert len(entries) >= 2
    for e in entries:
        assert {"ts", "sql", "kind", "duration_s", "plan", "dispatch", "error"} <= set(e)
    kinds = [e["kind"] for e in entries]
    assert "CreateStatement" in kinds and "SelectStatement" in kinds
    assert any(e["error"] for e in entries)  # the failing SELECT kept its error
    ok = next(e for e in entries if e["kind"] == "CreateStatement")
    assert ok["error"] is None and ok["duration_s"] >= 0
    assert telemetry.get_counter("slow_queries", kind="CreateStatement") >= 1


def test_slow_query_ring_is_bounded():
    telemetry.reset()
    for i in range(telemetry._SLOW_LOG_SIZE + 16):
        telemetry.record_slow_query({"ts": i, "sql": "x", "kind": "T"})
    got = telemetry.slow_queries()
    assert len(got) == telemetry._SLOW_LOG_SIZE
    assert got[-1]["ts"] == telemetry._SLOW_LOG_SIZE + 15  # newest survives


# ------------------------------------------------------------------ txn leak detector
def test_txn_leak_detector_counts_and_warns(ds, monkeypatch):
    telemetry.reset()
    # outside pytest the detector warns instead of raising; force that path
    # so the warning is assertable
    monkeypatch.delenv("PYTEST_CURRENT_TEST", raising=False)
    txn = ds.transaction(True)
    txn.set(b"\x00leak", b"v")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        del txn
        gc.collect()
    assert telemetry.get_counter("unfinished_txns") == 1
    assert any(issubclass(x.category, ResourceWarning) for x in w)


def test_txn_completed_is_not_flagged(ds):
    telemetry.reset()
    txn = ds.transaction(True)
    txn.set(b"\x00ok", b"v")
    txn.commit()
    del txn
    rd = ds.transaction(False)
    rd.cancel()
    del rd
    gc.collect()
    assert telemetry.get_counter("unfinished_txns") == 0


# ------------------------------------------------------------------ HTTP round trip
def test_metrics_and_slow_endpoints_roundtrip(monkeypatch):
    import http.client

    from surrealdb_tpu.net.server import serve

    monkeypatch.setattr(cnf, "SLOW_QUERY_THRESHOLD_SECS", 0.0)
    telemetry.reset()
    # a real coalesced dispatch + a cause-labeled retry (telemetry is
    # process-global, so this shows up on the served /metrics)
    q = DispatchQueue()
    calls = {"n": 0}

    def flaky(ps):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("DEADLINE_EXCEEDED: execution timed out")
        return list(ps)

    q.submit("k", 1, flaky)

    srv = serve("memory", port=0, auth_enabled=False).start_background()
    try:
        conn = http.client.HTTPConnection(srv.host, srv.port)
        hdrs = {"surreal-ns": "t", "surreal-db": "t"}
        conn.request("POST", "/sql", "CREATE m:1 SET v = 2; SELECT * FROM m;", hdrs)
        r = conn.getresponse()
        r.read()
        assert r.status == 200
        # unknown RPC method -> per-method rpc error counter
        conn.request(
            "POST", "/rpc", json.dumps({"method": "nosuch", "params": []}),
            {**hdrs, "Content-Type": "application/json"},
        )
        conn.getresponse().read()

        conn.request("GET", "/metrics")
        r = conn.getresponse()
        text = r.read().decode()
        assert r.status == 200
        assert_valid_exposition(text)
        # the acceptance families, all from one scrape
        assert "surreal_dispatch_batch_size_bucket" in text
        assert 'surreal_dispatch_retries_total{cause="DEADLINE_EXCEEDED"} 1' in text
        assert re.search(r'surreal_rpc_errors_total\{.*method="_unknown".*\} 1', text)
        assert re.search(
            r'surreal_statement_duration_seconds_bucket\{kind="CreateStatement",le="\+Inf"\} \d+',
            text,
        )
        assert re.search(
            r'surreal_statement_duration_seconds_bucket\{kind="SelectStatement",le="\+Inf"\} \d+',
            text,
        )

        conn.request("GET", "/slow")
        r = conn.getresponse()
        slow = json.loads(r.read())
        assert r.status == 200
        assert isinstance(slow, list) and slow
        assert any(e["kind"] == "CreateStatement" and e["error"] is None for e in slow)
        conn.close()
    finally:
        srv.shutdown()
