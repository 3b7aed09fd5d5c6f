"""`tpch_lineitem`, scan-and-aggregate as a deployment (ISSUE 40): the
manifest's new entries, the generator held to the source's ranges, the
reference against a brute-force loop, the float32 control failing its
limit, the kernel's need on hand-worked shapes, the readers on hand-written
docs, and a CPU rehearsal of the cell whose every statement is served by the
device route (with the route forced to the host underneath, the loader's
probe refuses the run)."""

import json
import os

import numpy as np
import pytest

import run as bench_run  # noqa: F401  (puts benchmarks/ on the path as the command does)
from harness import manifest as mf
from test_bench_rehearsal import CPU, TUNING, fresh_program_state, well_formed  # noqa: F401
from test_bench_served_spans import ctx_of

CELL = "tpch_lineitem.q1_c8"
READERS = ["column_agg_roofline", "col.device_share", "col.prepare_ms"]


def config():
    with open(os.path.join(mf.BENCH_DIR, "configs", "tpch_lineitem.json")) as f:
        return json.load(f)


def sizes():
    from surrealdb_tpu.ops import pipeline

    return {"orders": max(3000, pipeline.DEVICE_MIN_ROWS // 3), "pool": 48}  # ~4 lines an order: past the route's floor


@pytest.fixture(scope="module")
def kind():
    return mf.load_modules(mf.BENCH_DIR, "deployments", "KIND")["scan_aggregate"]


# ------------------------------------------------------------------ manifest
def test_the_manifest_has_the_deployment_its_cell_and_its_readers_and_no_problems():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    cfg = config()
    (entry,) = [c for c in manifest["configs"] if c["name"] == "tpch_lineitem"]
    assert entry["reduced"] == ["rows"] == cfg["reduced"] and entry["file"] == "benchmarks/configs/tpch_lineitem.json"
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert "TPC-H" in entry["source"] and "6,001,215 rows" in entry["source"]
    order = [c["name"] for c in manifest["configs"]]
    assert order.index("tpch_lineitem") > order.index("msmarco_bm25")  # after those that were there
    cell = mf.cell(manifest, CELL)
    assert cell == {**cell, "config": "tpch_lineitem", "traffic": "ws_closed_c8", "chips": 1} and len(cell["why"]) <= 200
    assert "host-bound" in cell["why"]
    assert {m["name"] for m in mf.metrics_of(manifest, "end_to_end", CELL)} == {"setup_s", "stmt_per_s", "p50_ms", "p95_ms"}
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(READERS[0])
    assert names[at : at + 3] == READERS and at > names.index("ft.slot_fill")  # together, in order, after
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in READERS:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "p50_ms"
    assert [by_name[n]["layer"] for n in READERS] == ["kernels", "kernels", "mirrors"]
    assert [by_name[n]["source"] for n in READERS] == ["device_trace", "program_span", "program_span"]
    mine = {m["name"] for m in mf.metrics_of(manifest, "per_layer", CELL)}
    assert mine == {m["name"] for m in manifest["per_layer"] if "workloads" not in m} | set(READERS)


def test_the_configuration_states_the_deployment_and_changes_no_width():
    cfg = config()
    assert cfg["kind"] == "scan_aggregate" and cfg["kernel"] == "column_agg" and cfg["expected_strategies"] == []
    assert cfg["ddl"] == ["DEFINE TABLE lineitem SCHEMALESS"]
    assert len(cfg["columns"]) == 16 and all(k.startswith("l_") for k in cfg["columns"])
    q1, q6 = cfg["statements"]["primary"], cfg["statements"]["q6"]
    assert (q1["bind"], q1["dispatches"], q6["bind"], q6["dispatches"]) == ("q", 1, "q", 1)
    for piece in ("math::sum(l_extendedprice * (100 - l_discount) * (100 + l_tax)) AS sum_charge", "math::mean(l_discount) AS avg_disc",
                  "count() AS count_order", "WHERE l_shipdate <= <datetime> $q.d", "GROUP BY l_returnflag, l_linestatus ORDER BY l_returnflag, l_linestatus"):
        assert piece in q1["sql"], piece
    for piece in ("math::sum(l_extendedprice * l_discount) AS revenue", "l_shipdate >= <datetime> $q.lo AND l_shipdate < <datetime> $q.hi",
                  "l_discount >= $q.dlo AND l_discount <= $q.dhi AND l_quantity < $q.qty GROUP ALL"):
        assert piece in q6["sql"], piece
    assert cfg["load"]["probe"] == ["q6", "primary"]
    assert cfg["sizes"]["orders"] in (750_000, 375_000) and cfg["sizes"]["pool"] == 1024
    g = cfg["generator"]
    assert (g["corpus_seed"], g["orders_at_sf1"], g["parts_at_sf1"], g["q1_delta"], g["q6_year"], g["q6_discount"], g["q6_quantity"]) == (
        5, 1_500_000, 200_000, [60, 120], [1993, 1997], [2, 9], [24, 25])
    assert (g["orderdate_min"], g["orderdate_max"], g["currentdate"], g["q1_enddate"]) == ("1992-01-01", "1998-08-02", "1995-06-17", "1998-12-01")
    assert cfg["correct"]["group_mismatch_max"] == 0 and cfg["correct"]["value_mismatch_max"] == 0 and cfg["correct"]["why"]
    assert len(cfg["guarantees"]) >= 5 and len(cfg["assumed"]) >= 6 and cfg["reduced_why"]
    for needle in ("recalled", "scaled by 100", "2 of the source's 22", "l_comment", "datetimes"):
        assert any(needle in a for a in cfg["assumed"]), needle


# ------------------------------------------------------------------ generator and reference
@pytest.fixture(scope="module")
def small(kind):
    cfg = config()
    data = kind.generate(cfg, {"orders": 125, "pool": 40}, 7)
    return cfg, data, kind.reference(cfg, data)


def test_the_generator_holds_the_sources_ranges(kind):
    cfg = config()
    a = kind.generate(cfg, {"orders": 20_000, "pool": 300}, 2**31 + 5)
    b = kind.generate(cfg, {"orders": 20_000, "pool": 300}, 11)
    c = a["columns"]
    assert all((c[k] == b["columns"][k]).all() for k in c) and a["pool"] != b["pool"]  # one table; the seed draws the pool
    n = a["rows"]
    assert 3.9 < n / 20_000 < 4.1 and c["l_linenumber"].min() == 1 and c["l_linenumber"].max() == 7
    assert (c["l_quantity"].min(), c["l_quantity"].max()) == (1, 50)
    assert (c["l_discount"].min(), c["l_discount"].max()) == (0, 10) and (c["l_tax"].min(), c["l_tax"].max()) == (0, 8)
    assert 90_000 <= c["l_extendedprice"].min() and c["l_extendedprice"].max() <= 10_495_000
    retail = 90000 + (c["l_partkey"] // 10) % 20001 + 100 * (c["l_partkey"] % 1000)
    assert (c["l_extendedprice"] == c["l_quantity"] * retail).all()
    ship, receipt, cur = c["l_shipdate"], c["l_receiptdate"], kind.day("1995-06-17")
    assert kind.day("1992-01-02") <= ship.min() and ship.max() <= kind.day("1998-12-01")
    assert ((receipt - ship >= 1) & (receipt - ship <= 30)).all()
    assert ((c["l_returnflag"] == "N") == (receipt > cur)).all() and ((c["l_linestatus"] == "O") == (ship > cur)).all()
    assert 0.45 < (c["l_returnflag"][receipt <= cur] == "R").mean() < 0.55
    labels, _ = kind.flag_groups(c)
    assert labels == ["AF", "NF", "NO", "RF"]  # four groups exist
    row = kind.rows_of(a, 5, 6, lambda d: d)[0]
    assert len(row) == 17 and row["id"] == 5 and 10 <= len(row["l_comment"]) <= 43 and row["l_shipmode"] in kind.MODES
    # the pool: Q1's DELTA 60..120 days before 1998-12-01; Q6 one year of 1993..1997, a band of 2, 24 or 25
    end = kind.day("1998-12-01")
    deltas = {end - kind.day(e["d"][:10]) for e in a["pool"]}
    assert min(deltas) >= 60 and max(deltas) <= 120 and len(deltas) > 40
    assert {e["lo"][:4] for e in a["pool"]} == {"1993", "1994", "1995", "1996", "1997"}
    assert all(int(e["hi"][:4]) == int(e["lo"][:4]) + 1 and e["dhi"] - e["dlo"] == 2 and 1 <= e["dlo"] <= 8 and e["qty"] in (24, 25)
               for e in a["pool"])
    passes = [sum(r["count_order"] for r in kind.q1(c, kind.day(e["d"][:10]))) / n for e in a["pool"][:5]]
    assert all(0.97 < p < 0.995 for p in passes)  # Q1 passes ~98% of the rows
    e = a["pool"][0]
    mask = ((ship >= kind.day(e["lo"][:10])) & (ship < kind.day(e["hi"][:10])) & (c["l_discount"] >= e["dlo"])
            & (c["l_discount"] <= e["dhi"]) & (c["l_quantity"] < e["qty"]))
    assert 0.01 < mask.mean() < 0.03  # Q6 ~2%


def brute_force_q1(rows, cutoff):
    groups = {}
    for r in rows:
        if r["l_shipdate"] <= cutoff:
            g = groups.setdefault((r["l_returnflag"], r["l_linestatus"]), [0, 0, 0, 0, 0, 0])
            disc_price = r["l_extendedprice"] * (100 - r["l_discount"])
            for i, v in enumerate((r["l_quantity"], r["l_extendedprice"], disc_price, disc_price * (100 + r["l_tax"]), r["l_discount"], 1)):
                g[i] += v
    return [
        {"l_returnflag": k[0], "l_linestatus": k[1], "sum_qty": g[0], "sum_base_price": g[1], "sum_disc_price": g[2], "sum_charge": g[3],
         "avg_qty": g[0] / g[5], "avg_price": g[1] / g[5], "avg_disc": g[4] / g[5], "count_order": g[5]}
        for k, g in sorted(groups.items())
    ]


def test_the_reference_is_a_brute_force_loop(kind, small):
    cfg, data, ref = small
    rows = kind.rows_of(data, 0, data["rows"], lambda d: d)
    assert 450 < len(rows) < 550
    for e, q1, q6 in zip(data["pool"], ref["primary"], ref["q6"]):
        assert q1 == brute_force_q1(rows, kind.day(e["d"][:10])) == kind.q1(data["columns"], kind.day(e["d"][:10]))
        lo, hi = kind.day(e["lo"][:10]), kind.day(e["hi"][:10])
        mine = [r["l_extendedprice"] * r["l_discount"] for r in rows
                if lo <= r["l_shipdate"] < hi and e["dlo"] <= r["l_discount"] <= e["dhi"] and r["l_quantity"] < e["qty"]]
        assert q6 == (sum(mine) if mine else None)
    assert ref["groups"] == 4 and ref["rows"] == len(rows)
    # a cut-off that a group's rows all lie after: the group is absent, not zero
    early = kind.q1(data["columns"], kind.day("1995-06-10"))
    assert [r["l_linestatus"] for r in early] == ["F"] * len(early) and early == brute_force_q1(rows, kind.day("1995-06-10"))
    assert kind.q1_many(data["columns"], [kind.day("1995-06-10")])[kind.day("1995-06-10")] == early


def records_of(ref, name="primary", rows=None):
    out = []
    for q, ans in enumerate(rows or ref[name]):
        ans = ([] if ans is None else [{"revenue": ans}]) if name == "q6" else ans
        values = {}
        for r in ans:
            for k, v in r.items():
                if not isinstance(v, str):
                    values.setdefault(k, []).append(v)
        out.append({"status": "OK", "s": name, "q": q, "ids": [], "values": values})
    return out


def numbers(out):
    return {n: (v, lim) for n, v, _, lim in out["numbers"]}


def test_the_check_passes_the_reference_itself_and_fails_the_float32_control(kind, small):
    cfg, data, ref = small
    sound = kind.check(cfg, ref, records_of(ref) + records_of(ref, "q6"))
    assert numbers(sound) == {"group_mismatch": (0, 0), "value_mismatch": (0, 0)}
    assert sound["compared"]["answers"] == 80
    # the same sums carried in float32: every sum and mean of the charge is off
    control = [kind.q1(data["columns"], kind.day(e["d"][:10]), accumulate=kind.float32_sum) for e in data["pool"]]
    got = numbers(kind.check(cfg, ref, records_of(ref, rows=control)))
    assert got["group_mismatch"][0] == 0 and got["value_mismatch"][0] > 40 * 4
    assert sound["control"]["value_mismatch_float32"] > 4 and kind.float32_sum(np.asarray([2**24, 1, 1])) == 2**24


def test_the_check_counts_what_breaks_the_guarantee(kind, small):
    cfg, _, ref = small
    recs = records_of(ref)
    recs[3]["values"] = {k: v[:-1] for k, v in recs[3]["values"].items()}  # a group missing
    recs[5]["values"]["sum_charge"][0] += 1  # one integer off by one
    recs[6]["values"]["count_order"][1] = float(recs[6]["values"]["count_order"][1])  # the right number, the wrong type
    recs[7]["values"] = {k: v[::-1] for k, v in recs[7]["values"].items()}  # groups out of their order
    got = numbers(kind.check(cfg, ref, recs))
    assert got["group_mismatch"][0] == 1 and got["value_mismatch"][0] >= 2 + 8 * 2
    assert numbers(kind.check(cfg, ref, []))["group_mismatch"][0] == 1  # nothing compared is not correct


# ------------------------------------------------------------------ the kernel's need
def test_the_need_on_two_hand_worked_shapes():
    need = mf.load_modules(mf.BENCH_DIR, "kernels", None)["column_agg"].need
    # one statement alone over 1,000 rows, 4 groups of 8 aggregates
    one = need({"rows": 1000, "groups": 4, "aggregates": 8}, 1, 1)
    assert one["bytes"] == 1000 * 9 + 4 * 8 * 8 == 9256 and one["flops"] == 1000 * 12
    # eight riders of one dispatch over 3,000,000 rows read the table once and each compare every row
    shapes = {"rows": 3_000_000, "groups": 4, "aggregates": 8}
    eight = need(shapes, 8, 1)
    assert eight["bytes"] == 27_000_000 + 8 * 256 and eight["flops"] == 8 * 36_000_000
    assert need(shapes, 8, 8)["bytes"] == 8 * 27_000_000 + 8 * 256  # alone, each sweeps for itself


# ------------------------------------------------------------------ the readers
def doc(spans):
    return {"ts": 0.0, "spans": [{"name": n, "start_ms": 0.0, "dur_ms": d, "labels": l} for n, d, l in spans]}


def test_the_readers_on_hand_written_docs():
    readers = mf.load_modules(mf.BENCH_DIR, "layer_metrics", "NAME")
    docs = [
        doc([("column_prepare", 0.20, {"route": "device", "reason": "", "rows": "3000000"}), ("dispatch_launch", 0.5, {"batch": "8", "rows": "3000000"})]),
        doc([("column_prepare", 0.40, {"route": "device", "reason": ""})]),
        doc([("column_prepare", 0.90, {"route": "host", "reason": "float_cell:l_tax"})]),
        doc([("column_prepare", 0.10, {"route": "row", "reason": "decline_mirror"})]),
        doc([("ft_prepare", 0.3, {"route": "device"})]),
    ]
    ctx = ctx_of(*docs)
    assert readers["col.device_share"].read(ctx) == pytest.approx(2 / 4)
    assert readers["col.prepare_ms"].read(ctx) == pytest.approx(0.30)  # the median of 0.1, 0.2, 0.4, 0.9
    bare = ctx_of(docs[4])  # the parent commit's doc: no such span
    assert readers["col.device_share"].read(bare) is None and readers["col.prepare_ms"].read(bare) is None
    assert readers["column_agg_roofline"].read({**ctx, "kernel": None, "slice": None}) is None
    for name in READERS:
        assert (readers[name].NAME, readers[name].MOVES) == (name, "p50_ms")


# ------------------------------------------------------------------ rehearsal
def rehearse(trace, capsys, seconds=3.0):
    manifest = mf.load()
    line = bench_run.run(manifest, CELL, 2**31 + 9, seconds, trace, CPU, sizes=sizes(), tuning=TUNING)
    phases = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    return manifest, line, {p["phase"]: p for p in phases}


def test_the_cell_rehearsed_is_correct_and_every_statement_rides_the_device(capsys):
    manifest, line, phases = rehearse(True, capsys)
    well_formed(line, manifest, CELL, True)
    assert line["correct"] is True, phases["check"]
    assert line["failed"] == 0 and line["attempted"] > 0
    by_name = {n["name"]: n for n in phases["check"]["numbers"]}
    assert by_name["group_mismatch"]["value"] == 0 and by_name["value_mismatch"]["value"] == 0
    assert phases["check"]["control"]["value_mismatch_float32"] > 0
    assert by_name["statements_not_dispatched"]["value"] == 0 and by_name["compiles_in_window"]["value"] == 0
    got = {n: m["value"] for n, m in line["metrics"].items()}
    assert got["col.device_share"] == 1.0 and got["col.prepare_ms"] > 0
    assert "column_agg_roofline" not in got and "kernel.ms_per_dispatch" not in got  # no device plane on the CPU
    listless = {m["name"] for m in manifest["per_layer"] if "workloads" not in m} - {"kernel.ms_per_dispatch"}
    assert listless <= set(got), listless - set(got)
    ingest = phases["ingest"]
    assert ingest["read_back"] == ingest["acknowledged"] > 8000 and ingest["probe_s"] > 0
    assert ingest["rss_bytes"] >= ingest["rss_before_bytes"] > 0
    assert phases["traced"]["kernel_shapes"] == {"rows": ingest["acknowledged"], "groups": 4, "aggregates": 8}
    assert phases["window"]["strategies"] == {}


def test_the_untraced_rehearsal_reports_the_end_to_end_metrics(capsys):
    manifest, line, phases = rehearse(False, capsys)
    well_formed(line, manifest, CELL, False)
    assert line["correct"] is True, phases["check"]
    assert set(line["metrics"]) == {"setup_s", "stmt_per_s", "p50_ms", "p95_ms"}


def test_with_the_route_forced_to_the_host_the_loader_refuses_at_q6(monkeypatch, capsys):
    """The host route's answers equal the reference's too: only the dispatch
    counter shows it, and the loader's probe reads it before any client starts."""
    from surrealdb_tpu.ops import pipeline

    real = pipeline.grouped_route
    monkeypatch.setattr(pipeline, "grouped_route", lambda *a: ("host", "forced", None) if real(*a)[0] == "device" else real(*a))
    with pytest.raises(RuntimeError, match="'q6' of the loader's probe made 0 device dispatches"):
        rehearse(False, capsys)
