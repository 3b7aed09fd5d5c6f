"""The grouped aggregate's device route (ISSUE 40): a `SELECT ... GROUP BY` /
`GROUP ALL` of counts and integer sums over one table is ONE dispatch of
ops/column_agg.py::grouped_aggregate over the column mirror's planes on the
device, held here to the benchmark's plain reference
(benchmarks/deployments/scan_aggregate.py: NumPy, no JAX, nothing of
surrealdb_tpu) on seeded `lineitem` tables, route by route."""

import importlib.util
import json
import os
import threading
import time
from decimal import Decimal

import numpy as np
import pytest

from surrealdb_tpu import cnf, compile_log, telemetry, tracing
from surrealdb_tpu.dbs.session import Session
from surrealdb_tpu.kvs.ds import Datastore
from surrealdb_tpu.ops import column_agg, pipeline
from surrealdb_tpu.sql.value import Datetime

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_reference():
    path = os.path.join(ROOT, "benchmarks", "deployments", "scan_aggregate.py")
    spec = importlib.util.spec_from_file_location("scan_aggregate_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load_reference()
with open(os.path.join(ROOT, "benchmarks", "configs", "tpch_lineitem.json")) as _f:
    CFG = json.load(_f)
Q1, Q6 = CFG["statements"]["primary"]["sql"], CFG["statements"]["q6"]["sql"]


def wrap(d):
    return Datetime(d * REF.DAY_NS)


class Table:
    """A datastore with a seeded `lineitem` and the generator's arrays
    beside it (changed as the table is), so the reference answers any
    statement of the pool over what the table holds."""

    def __init__(self, orders: int, seed: int = 7, step: int = 1):
        self.ds = Datastore("memory")
        self.s = Session.owner("t", "t")
        self.run("DEFINE TABLE lineitem SCHEMALESS")
        self.data = REF.generate(CFG, {"orders": orders, "pool": 24}, seed)
        self.c = self.data["columns"]
        rows = REF.rows_of(self.data, 0, self.data["rows"], wrap)
        for r in rows:
            r["id"] *= step
        self.run("INSERT INTO lineitem $rows RETURN NONE", rows=rows)

    def run(self, sql, **vars):
        out = self.ds.execute(sql, self.s, vars=vars or None)
        assert all(r["status"] == "OK" for r in out), out
        return out[-1]["result"]

    def ask(self, sql, q=None, tid=None):
        """(rows, labels of the statement's `column_prepare` span, dispatches made)."""
        before = self.ds.dispatch.stats()["submitted"]
        tid = tid or f"t{time.perf_counter_ns()}"
        with tracing.request("col", trace_id=tid):
            rows = self.run(sql, q=q) if q is not None else self.run(sql)
        labels = [sp["labels"] for sp in tracing.get_trace(tid)["spans"] if sp["name"] == "column_prepare"]
        return rows, (labels[0] if labels else None), self.ds.dispatch.stats()["submitted"] - before

    def q1(self, e):
        return REF.q1(self.c, REF.day(e["d"][:10]))

    def q6(self, e):
        v = REF.q6(self.c, REF.day(e["lo"][:10]), REF.day(e["hi"][:10]), e["dlo"], e["dhi"], e["qty"])
        return [] if v is None else [{"revenue": v}]

    def close(self):
        self.ds.close()


@pytest.fixture(scope="module")
def table():
    t = Table(3000)
    assert t.data["rows"] >= pipeline.DEVICE_MIN_ROWS
    yield t
    t.close()


@pytest.fixture()
def small(monkeypatch):
    """A table of a few hundred rows that a test may write to, the route's
    floor of rows and the mirror's rebuild debounce out of the way."""
    monkeypatch.setattr(pipeline, "DEVICE_MIN_ROWS", 0)
    monkeypatch.setattr(cnf, "COLUMN_REBUILD_DEBOUNCE_SECS", 0.0)
    t = Table(150, step=2)
    yield t
    t.close()


def typed(rows):
    """Rows with every value's type beside it: `identical` means this."""
    return [{k: (type(v).__name__, repr(v)) for k, v in r.items()} for r in rows]


def by_route(t: Table, sql: str, q, monkeypatch):
    """The statement's rows as each route serves them: device, host, row."""
    out = {}
    out["device"] = t.ask(sql, q)
    with monkeypatch.context() as m:
        m.setattr(pipeline, "DEVICE_MIN_ROWS", 1 << 40)
        out["host"] = t.ask(sql, q)
    with monkeypatch.context() as m:
        m.setattr(pipeline, "mirror_for", lambda ctx, tb: None)
        out["row"] = t.ask(sql, q)
    return out


# ------------------------------------------------------------------ Q1 and Q6 against the reference
@pytest.mark.parametrize("i", range(6))
def test_q1_as_stated_is_one_dispatch_and_the_reference(table, i):
    e = table.data["pool"][i]
    rows, labels, made = table.ask(Q1, e)
    assert labels["route"] == "device" and made == 1, labels
    assert labels["rows"] == str(table.data["rows"]) and labels["aggregates"] == "8"
    want = table.q1(e)
    assert len(rows) == len(want) == 4 and REF.value_mismatches(rows, want) == 0
    assert [(r["l_returnflag"], r["l_linestatus"]) for r in rows] == [(w["l_returnflag"], w["l_linestatus"]) for w in want]
    assert typed(rows) == typed(want)


@pytest.mark.parametrize("i", range(6))
def test_q6_as_stated_is_one_dispatch_and_the_reference(table, i):
    e = table.data["pool"][i]
    rows, labels, made = table.ask(Q6, e)
    assert labels["route"] == "device" and made == 1, labels
    assert typed(rows) == typed(table.q6(e))


@pytest.mark.parametrize("sql", [Q1, Q6], ids=["q1", "q6"])
def test_device_host_and_row_return_identical_rows(table, sql, monkeypatch):
    got = by_route(table, sql, table.data["pool"][7], monkeypatch)
    assert [got[r][1]["route"] for r in ("device", "host", "row")] == ["device", "host", "row"]
    assert [got[r][2] for r in ("device", "host", "row")] == [1, 0, 0]
    assert typed(got["device"][0]) == typed(got["host"][0]) == typed(got["row"][0])


def test_float32_accumulated_control_differs(table):
    e = table.data["pool"][0]
    rows, _, _ = table.ask(Q1, e)
    control = REF.q1(table.c, REF.day(e["d"][:10]), accumulate=REF.float32_sum)
    assert REF.value_mismatches(rows, table.q1(e)) == 0
    assert REF.value_mismatches(control, table.q1(e)) > 0


def test_device_counter_and_dispatch_grow_by_one_a_statement(table):
    def device():
        return telemetry.snapshot()["counters"].get('column_pipeline{outcome="device"}', 0)

    c0, d0 = device(), table.ds.dispatch.stats()["submitted"]
    for e in table.data["pool"][:5]:
        table.ask(Q1, e)
        table.ask(Q6, e)
    assert device() - c0 == 10 and table.ds.dispatch.stats()["submitted"] - d0 == 10


# ------------------------------------------------------------------ the cast folds on every route
def test_cast_of_a_bound_date_lowers_on_the_host_mask_too(table, monkeypatch):
    monkeypatch.setattr(pipeline, "DEVICE_MIN_ROWS", 1 << 40)
    before = dict(telemetry.snapshot()["counters"])
    e = table.data["pool"][3]
    rows, labels, made = table.ask(Q1, e)
    assert labels["route"] == "host" and labels["reason"] == "rows" and made == 0
    assert typed(rows) == typed(table.q1(e))
    after = telemetry.snapshot()["counters"]
    grew = {k for k, v in after.items() if k.startswith("column_pipeline{") and v != before.get(k, 0)}
    assert grew == {'column_pipeline{outcome="grouped"}'}, grew
    # a plain SELECT with the same WHERE takes the columnar scan, not the row walk
    n = table.run("SELECT count() AS c FROM lineitem WHERE l_shipdate <= <datetime> $q.d GROUP ALL", q=e)
    assert n == [{"c": int((table.c["l_shipdate"] <= REF.day(e["d"][:10])).sum())}]


def test_uncast_text_matches_no_date_and_a_bad_cast_is_the_row_paths_error(table):
    e = table.data["pool"][0]
    assert table.run("SELECT count() AS c FROM lineitem WHERE l_shipdate <= $q.d GROUP ALL", q=e) == []
    out = table.ds.execute("SELECT count() AS c FROM lineitem WHERE l_shipdate <= <datetime> $q.d GROUP ALL",
                           table.s, vars={"q": {"d": "not a date"}})
    assert out[-1]["status"] == "ERR"


# ------------------------------------------------------------------ riders of one shape share a launch
def together(table, jobs, monkeypatch):
    """The (sql, q) `jobs` submitted from a thread each into one coalescing
    window: a launch waits until every rider has been submitted. Returns
    their rows in order and the launch widths the window added."""
    ds, gate, real = table.ds, threading.Event(), pipeline._device_runner
    s0 = ds.dispatch.stats()["submitted"]

    def held(plan):
        run = real(plan)

        def first_waits(payloads):
            if not gate.is_set():
                t_end = time.time() + 30
                while ds.dispatch.stats()["submitted"] - s0 < len(jobs) and time.time() < t_end:
                    time.sleep(0.001)
                gate.set()
            return run(payloads)

        return first_waits

    w0, out = ds.dispatch.width_distribution(), {}

    def one(i):
        out[i] = table.run(jobs[i][0], q=jobs[i][1])

    with monkeypatch.context() as m:
        m.setattr(pipeline, "_device_runner", held)
        threads = [threading.Thread(target=one, args=(i,)) for i in range(len(jobs))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
    w1 = ds.dispatch.width_distribution()
    return [out[i] for i in range(len(jobs))], {w: n - w0.get(w, 0) for w, n in w1.items() if n != w0.get(w, 0)}


def test_riders_of_different_constants_share_one_launch(table, monkeypatch):
    pool, riders = table.data["pool"], 7
    table.ask(Q1, pool[0])  # the shape's compile and the planes' upload are behind us
    out, widths = together(table, [(Q1, pool[i]) for i in range(riders + 1)], monkeypatch)
    assert widths == {1: 1, riders: 1}
    assert len({e["d"] for e in pool[: riders + 1]}) > 3
    for i in range(riders + 1):
        assert typed(out[i]) == typed(table.q1(pool[i])), i


def test_riders_of_different_expression_constants_share_one_launch(table, monkeypatch):
    """A summed expression's constants are a rider's own too: the launch
    sums the expression's monomials and each rider folds its own
    coefficients in, so eight values of `$q.k` are one sweep."""
    sql = ("SELECT l_linestatus, math::sum(l_extendedprice * ($q.k - l_discount)) AS s, math::mean(l_quantity * $q.k - 7) AS m "
           "FROM lineitem WHERE l_quantity < $q.qty GROUP BY l_linestatus")
    qs = [{"k": k, "qty": 20 + i} for i, k in enumerate((100, -3, 0, 1 << 20, 17, -(1 << 24), 5, 100))]
    rows, labels, made = table.ask(sql, qs[0])
    assert labels["route"] == "device" and made == 1
    out, widths = together(table, [(sql, q) for q in qs], monkeypatch)
    assert widths == {1: 1, len(qs) - 1: 1}
    c = table.c
    for q, got in zip(qs, out):
        want = []
        for status in dict.fromkeys(c["l_linestatus"][c["l_quantity"] < q["qty"]].tolist()):
            sel = (c["l_quantity"] < q["qty"]) & (c["l_linestatus"] == status)
            price, disc, qty = (c[k][sel].astype(object) for k in ("l_extendedprice", "l_discount", "l_quantity"))
            n = int(sel.sum())
            want.append({"l_linestatus": status, "s": int((price * (q["k"] - disc)).sum()),
                         "m": int((qty * q["k"] - 7).sum()) / n})
        assert typed(got) == typed(want), q
    with monkeypatch.context() as m:
        m.setattr(pipeline, "DEVICE_MIN_ROWS", 1 << 40)
        assert typed(table.ask(sql, qs[3])[0]) == typed(out[3])


def test_statements_of_one_shape_over_different_columns_never_share_a_launch(table, monkeypatch):
    """Two panels of one dashboard: the same statement shape over another
    column. The planes' indices are equal, the columns are not, and the
    dispatch key says the columns: each is swept over its own planes."""
    sqls = [f"SELECT l_returnflag, math::sum({col}) AS s, count() AS n FROM lineitem WHERE {where} <= $q.v GROUP BY l_returnflag"
            for col, where in (("l_quantity", "l_tax"), ("l_extendedprice", "l_tax"), ("l_quantity", "l_discount"))]
    for sql in sqls:
        assert table.ask(sql, {"v": 4})[1]["route"] == "device"
    jobs = [(sqls[i % 3], {"v": 2 + i % 5}) for i in range(9)]
    out, widths = together(table, jobs, monkeypatch)
    assert sum(w * n for w, n in widths.items()) == len(jobs) and max(widths) > 1, widths
    c = table.c
    for (sql, q), got in zip(jobs, out):
        col, where = [(k, w) for k in ("l_quantity", "l_extendedprice") for w in ("l_tax", "l_discount") if f"({k})" in sql and f"{w} <=" in sql][0]
        want = []
        for flag in dict.fromkeys(c["l_returnflag"][c[where] <= q["v"]].tolist()):
            sel = (c[where] <= q["v"]) & (c["l_returnflag"] == flag)
            want.append({"l_returnflag": flag, "s": sum(c[col][sel].tolist()), "n": int(sel.sum())})
        assert typed(got) == typed(want), (sql, q)


def test_any_and_value_of_one_column_are_one_plane(table):
    e = table.data["pool"][0]
    table.ask(Q6, e)  # l_quantity under a comparison alone: `any`
    table.ask(Q1, e)  # and summed: `value`
    mirror = table.ds.column_mirrors.get(("t", "t", "lineitem"))
    q1_q6 = {"l_quantity", "l_extendedprice", "l_discount", "l_tax", "l_shipdate", "l_returnflag", "l_linestatus"}
    held = [k for k, d in mirror._device.items() if k[0] in q1_q6 and not isinstance(d, str)]
    assert sorted(p for p, _ in held) == sorted(q1_q6), held
    assert {form for _, form in held} == {"value", "code"}


@pytest.mark.parametrize("tree,want", [
    (("col", "a"), {("a",): 1}),
    (("*", ("col", "a"), ("-", ("const", 100), ("col", "b"))), {("a",): 100, ("a", "b"): -1}),
    (("*", ("+", ("col", "a"), ("const", 3)), ("-", ("col", "a"), ("const", 3))), {("a", "a"): 1, ("a",): 0, (): -9}),
    (("*", ("col", "b"), ("*", ("col", "a"), ("const", 0))), {("a", "b"): 0}),
    (("-", ("const", 5), ("const", 7)), {(): -2}),
], ids=["column", "q1_disc_price", "zero_coefficient_keeps_its_monomial", "times_zero", "constants"])
def test_polynomial_of_an_expression(tree, want):
    assert pipeline._polynomial(tree) == want


def test_an_expression_of_too_many_monomials_is_host(table, monkeypatch):
    cols = ["l_quantity", "l_discount", "l_tax", "l_linenumber", "l_suppkey"]
    wide = " * ".join(f"({a} + {b} + 1)" for a, b in zip(cols, cols[1:]))  # 3**4 = 81 products
    sql = f"SELECT math::sum({wide}) AS s FROM lineitem GROUP ALL"
    got = by_route(table, sql, None, monkeypatch)
    assert got["device"][1]["route"] == "host" and got["device"][1]["reason"] == "expression" and got["device"][2] == 0
    assert typed(got["device"][0]) == typed(got["host"][0]) == typed(got["row"][0])


# ------------------------------------------------------------------ groups
def test_a_group_no_row_reaches_is_absent(table, monkeypatch):
    # nothing shipped on or before 1995-06-17 has line status O
    e = {"d": "1995-06-10T00:00:00Z"}
    rows, labels, _ = table.ask(Q1, e)
    assert labels["route"] == "device"
    assert [(r["l_returnflag"], r["l_linestatus"]) for r in rows] == [("A", "F"), ("N", "F"), ("R", "F")]
    assert typed(rows) == typed(table.q1(e))
    # and a WHERE that no row passes yields no row at all, GROUP BY or GROUP ALL
    assert table.ask(Q1, {"d": "1990-01-01T00:00:00Z"})[0] == []
    none = dict(table.data["pool"][0], lo="1980-01-01T00:00:00Z", hi="1981-01-01T00:00:00Z")
    got = by_route(table, Q6, none, monkeypatch)
    assert got["device"][0] == got["host"][0] == got["row"][0] == []


def test_groups_come_in_first_appearance_order_without_order_by(table, monkeypatch):
    sql = ("SELECT l_shipmode, l_linestatus, count() AS n, math::sum(l_quantity * 2 - l_tax) AS s FROM lineitem "
           "WHERE l_discount > $q.dlo GROUP BY l_shipmode, l_linestatus")
    got = by_route(table, sql, table.data["pool"][1], monkeypatch)
    assert got["device"][1]["route"] == "device" and got["device"][1]["groups"] == "16"
    assert len(got["device"][0]) == 14
    assert typed(got["device"][0]) == typed(got["host"][0]) == typed(got["row"][0])


def test_keys_of_every_kind_and_limit_start(table, monkeypatch):
    sql = ("SELECT l_linenumber, l_shipdate, math::mean(l_extendedprice - 90000 * l_quantity) AS m, count() AS n "
           "FROM lineitem WHERE l_shipdate >= <datetime> $q.lo AND l_shipdate < <datetime> $q.hi AND l_linenumber = 3 "
           "GROUP BY l_linenumber, l_shipdate ORDER BY n DESC, l_shipdate LIMIT 5 START 2")
    q = {"lo": "1994-01-01T00:00:00Z", "hi": "1995-01-01T00:00:00Z"}
    got = by_route(table, sql, q, monkeypatch)
    assert got["device"][1]["route"] == "host" and got["device"][1]["reason"] == "groups"  # 7 x ~2,500 ship dates
    sql = sql.replace("l_linenumber, l_shipdate,", "l_linenumber, l_shipmode,").replace(
        "GROUP BY l_linenumber, l_shipdate ORDER BY n DESC, l_shipdate", "GROUP BY l_linenumber, l_shipmode ORDER BY n DESC, l_shipmode")
    got = by_route(table, sql, q, monkeypatch)
    assert got["device"][1]["route"] == "device", got["device"][1]
    assert len(got["device"][0]) == 5
    assert typed(got["device"][0]) == typed(got["host"][0]) == typed(got["row"][0])


# ------------------------------------------------------------------ exactness
def test_sums_past_2_to_53_are_exact(small, monkeypatch):
    top = (1 << 31) - 1
    small.run("DEFINE TABLE big SCHEMALESS")
    small.run("INSERT INTO big $rows RETURN NONE", rows=[{"id": i, "g": i % 3, "v": top - (i % 5), "w": -(i % 7)} for i in range(2048)])
    sql = "SELECT g, math::sum(v * 10800) AS s, math::sum(w * v - v) AS t, math::mean(v * 10800) AS m FROM big GROUP BY g"
    got = by_route(small, sql, None, monkeypatch)
    assert got["device"][1]["route"] == "device" and got["device"][2] == 1
    want = []
    for g in range(3):
        vs = [(top - (i % 5), -(i % 7)) for i in range(2048) if i % 3 == g]
        s = sum(v * 10800 for v, _ in vs)
        want.append({"g": g, "s": s, "t": sum(w * v - v for v, w in vs), "m": s / len(vs)})
    assert want[0]["s"] > 1 << 53
    assert typed(got["device"][0]) == typed(want) == typed(got["host"][0]) == typed(got["row"][0])


def test_a_bound_that_could_pass_2_to_63_is_host(small, monkeypatch):
    small.run("DEFINE TABLE big SCHEMALESS")
    small.run("INSERT INTO big $rows RETURN NONE", rows=[{"id": i, "v": (1 << 31) - 1 - i} for i in range(1024)])
    sql = "SELECT math::sum(v * 2147483648) AS s FROM big GROUP ALL"  # 2**62 a row, 2**72 over the table
    got = by_route(small, sql, None, monkeypatch)
    assert got["device"][1]["route"] == "host" and got["device"][1]["reason"] == "bound" and got["device"][2] == 0
    assert got["device"][0] == [{"s": sum(((1 << 31) - 1 - i) << 31 for i in range(1024))}]
    assert typed(got["device"][0]) == typed(got["host"][0]) == typed(got["row"][0])


# ------------------------------------------------------------------ columns with no device form
@pytest.mark.parametrize("cell,reason", [(2.5, "float_cell:l_tax"), (None, "null_cell:l_tax"), (Decimal("0.04"), "other_cell:l_tax")],
                         ids=["float", "null", "decimal"])
def test_a_cell_that_is_no_int_sends_the_statement_to_host(small, cell, reason, monkeypatch):
    small.run("UPDATE lineitem:4 SET l_tax = $v", v=cell)
    got = by_route(small, Q1, small.data["pool"][0], monkeypatch)
    assert got["device"][1]["route"] == "host" and got["device"][1]["reason"] == reason, got["device"][1]
    assert got["device"][2] == 0
    assert typed(got["device"][0]) == typed(got["host"][0]) == typed(got["row"][0])
    # Q6 names no such column and stays on the device
    assert small.ask(Q6, small.data["pool"][0])[1]["route"] == "device"


def test_a_none_cell_sends_the_statement_to_host(small, monkeypatch):
    small.run("UPDATE lineitem:4 SET l_quantity = NONE")
    got = by_route(small, Q6, small.data["pool"][0], monkeypatch)
    assert got["device"][1]["route"] == "host" and got["device"][1]["reason"] == "none_cell:l_quantity"
    assert typed(got["device"][0]) == typed(got["host"][0]) == typed(got["row"][0])


@pytest.mark.parametrize("sql,reason", [
    ("SELECT math::max(l_quantity) AS m FROM lineitem GROUP ALL", "aggregate"),
    ("SELECT count(l_tax) AS m FROM lineitem GROUP ALL", "aggregate"),
    ("SELECT l_partkey, count() AS n FROM lineitem GROUP BY l_partkey", "groups"),
    ("SELECT count() AS n FROM lineitem WHERE l_quantity < 10 OR l_tax = 3 GROUP BY l_linestatus", "where"),
    ("SELECT count() AS n FROM lineitem WHERE l_quantity != 10 GROUP BY l_linestatus", "where"),
    ("SELECT count() AS n FROM lineitem WHERE l_quantity < 10.5 GROUP BY l_linestatus", "constant"),
    ("SELECT count() AS n FROM lineitem WHERE l_shipmode < 5 GROUP BY l_linestatus", "constant"),
    ("SELECT math::sum(l_quantity * 1.5) AS s FROM lineitem GROUP BY l_linestatus", "constant"),
    ("SELECT math::sum(l_shipmode) AS s FROM lineitem GROUP BY l_linestatus", "not_int:l_shipmode"),
    ("SELECT l_shipmode, count() AS n FROM lineitem GROUP BY l_linestatus", "projection"),
], ids=["max", "count_arg", "wide_key", "or", "ne", "float_const", "kind", "float_factor", "string_sum", "first_member"])
def test_what_the_rule_leaves_to_the_host(small, sql, reason, monkeypatch):
    got = by_route(small, sql, None, monkeypatch)
    assert got["device"][1]["route"] == "host" and got["device"][1]["reason"] == reason, got["device"][1]
    assert got["device"][2] == 0
    assert typed(got["device"][0]) == typed(got["host"][0]) == typed(got["row"][0])


def test_floor_of_rows_and_tpu_disable_are_host(small, monkeypatch):
    e = small.data["pool"][0]
    assert small.ask(Q6, e)[1]["route"] == "device"
    monkeypatch.setattr(cnf, "TPU_DISABLE", True)
    assert small.ask(Q6, e)[1]["reason"] == "tpu_disable"
    monkeypatch.setattr(cnf, "TPU_DISABLE", False)
    monkeypatch.undo()  # the route's own floor again: a few hundred rows are the host's
    rows, labels, made = small.ask(Q6, e)
    assert labels["route"] == "host" and labels["reason"] == "rows" and made == 0
    assert not hasattr(cnf, "COLUMN_DEVICE") and "SURREAL_COLUMN_DEVICE" not in open(cnf.__file__).read()


# ------------------------------------------------------------------ writes
def test_the_next_statement_after_a_write_sees_it(small):
    e, c = small.data["pool"][0], small.c
    assert typed(small.ask(Q1, e)[0]) == typed(small.q1(e))
    serial = small.ds.column_mirrors.get(("t", "t", "lineitem")).serial

    small.run("UPDATE lineitem:10 SET l_quantity = 49, l_extendedprice = 123456")
    c["l_quantity"][5], c["l_extendedprice"][5] = 49, 123456
    rows, labels, made = small.ask(Q1, e)
    assert labels["route"] == "device" and made == 1 and typed(rows) == typed(small.q1(e))

    small.run("DELETE lineitem:14")
    for k in list(c):
        c[k] = np.delete(c[k], 7)
    rows, labels, _ = small.ask(Q1, e)
    assert labels["route"] == "device" and typed(rows) == typed(small.q1(e))
    assert typed(small.ask(Q6, e)[0]) == typed(small.q6(e))

    # a second bulk batch, its ids between the first's: the mirror takes it as a delta, out of key order
    more = REF.generate(CFG, {"orders": 40, "pool": 1}, 9)
    extra = REF.rows_of(more, 0, more["rows"], wrap)
    for r in extra:
        r["id"] = 2 * r["id"] + 1
    small.run("INSERT INTO lineitem $rows RETURN NONE", rows=extra)
    for k in list(c):
        c[k] = np.concatenate([c[k], more["columns"][k]])
    rows, labels, made = small.ask(Q1, e)
    assert labels["route"] == "device" and made == 1 and labels["rows"] == str(c["l_quantity"].size)
    assert typed(rows) == typed(small.q1(e))
    mirror = small.ds.column_mirrors.get(("t", "t", "lineitem"))
    assert mirror.serial != serial and mirror.delta_fed
    # first appearance follows the record keys, not the order the rows arrived in
    sql = "SELECT l_shipmode, count() AS n FROM lineitem GROUP BY l_shipmode"
    dev = small.ask(sql)[0]
    with pytest.MonkeyPatch.context() as m:
        m.setattr(cnf, "COLUMN_MIRROR", False)
        assert typed(small.ask(sql)[0]) == typed(dev)


def test_a_shape_is_placed_once_a_mirror_build_and_its_sweeps_are_one_deep(small, monkeypatch):
    """What of the route rule a statement's predicate constants cannot move
    is worked out once a (mirror build, shape) and kept with the build; a
    write installs another build and with it another placement. The
    bucket of a sweep is one deep (a second sweep in flight would split
    the riders of one)."""
    calls, real = [], pipeline._place
    monkeypatch.setattr(pipeline, "_place", lambda *a: calls.append(1) or real(*a))
    pool = small.data["pool"]
    for e in pool[:6]:
        rows, labels, made = small.ask(Q1, e)
        assert labels["route"] == "device" and made == 1 and typed(rows) == typed(small.q1(e))
    assert len(calls) == 1
    mirror = small.ds.column_mirrors.get(("t", "t", "lineitem"))
    assert [type(p) for p in mirror.placements.values()] == [pipeline.DevicePlan]
    for e in pool[:3]:
        assert typed(small.ask(Q6, e)[0]) == typed(small.q6(e))
    assert len(calls) == 2 and len(mirror.placements) == 2
    small.run("UPDATE lineitem:10 SET l_quantity = 49")
    small.c["l_quantity"][5] = 49
    rows, labels, _ = small.ask(Q1, pool[0])
    assert labels["route"] == "device" and typed(rows) == typed(small.q1(pool[0])) and len(calls) == 3
    later = small.ds.column_mirrors.get(("t", "t", "lineitem"))
    assert later is not mirror and len(later.placements) == 1
    depths = {k[0]: b.depth for k, b in small.ds.dispatch._buckets.items()}
    assert depths == {"colagg": pipeline.SWEEP_DEPTH} and pipeline.SWEEP_DEPTH == 1


def test_a_reason_is_placed_too_and_expression_constants_cannot_fill_the_mirror(small, monkeypatch):
    """The host's reason is kept like a plan; a client's constants inside
    summed expressions are each a placement, and a build keeps at most
    PLACEMENTS_MAX of them."""
    monkeypatch.setattr(pipeline, "PLACEMENTS_MAX", 4)
    sql = "SELECT l_linestatus, math::sum(l_quantity * $q.k) AS s FROM lineitem GROUP BY l_linestatus"
    c = small.c
    for k in range(1, 12):
        rows, labels, _ = small.ask(sql, {"k": k})
        assert labels["route"] == "device"
        want = [{"l_linestatus": st, "s": k * int(c["l_quantity"][c["l_linestatus"] == st].sum())}
                for st in dict.fromkeys(c["l_linestatus"].tolist())]
        assert typed(rows) == typed(want), k
    mirror = small.ds.column_mirrors.get(("t", "t", "lineitem"))
    assert 1 <= len(mirror.placements) <= 4
    wide = "SELECT l_orderkey, l_linenumber, count() AS n FROM lineitem GROUP BY l_orderkey, l_linenumber"
    for _ in range(2):
        assert small.ask(wide)[1]["reason"] == "groups"
    assert "groups" in mirror.placements.values()


def test_a_transactions_own_writes_take_the_row_path(small):
    e, c = small.data["pool"][0], small.c
    tid = "own-writes"
    with tracing.request("col", trace_id=tid):
        out = small.ds.execute(f"BEGIN; UPDATE lineitem:0 SET l_quantity = 50; {Q1}; COMMIT;", small.s, vars={"q": e})
    labels = [sp["labels"] for sp in tracing.get_trace(tid)["spans"] if sp["name"] == "column_prepare"]
    assert labels and labels[0]["route"] == "row" and labels[0]["reason"] == "decline_mirror", labels
    c["l_quantity"][0] = 50
    rows = [r for r in out if isinstance(r.get("result"), list) and r["result"] and "sum_qty" in r["result"][0]][0]["result"]
    assert typed(rows) == typed(small.q1(e))
    rows, labels, made = small.ask(Q1, e)  # committed: the next statement is the device's again
    assert labels["route"] == "device" and made == 1 and typed(rows) == typed(small.q1(e))


# ------------------------------------------------------------------ compiles and caching
def test_no_compile_after_the_warm(table):
    pool = table.data["pool"]
    table.ask(Q1, pool[0])
    table.ask(Q6, pool[0])
    since = time.time()
    rng = np.random.default_rng(3)
    for i in rng.integers(0, len(pool), 200).tolist():
        sql = Q1 if i % 2 else Q6
        rows = table.run(sql, q=pool[i])
        assert REF.value_mismatches(rows, table.q1(pool[i]) if i % 2 else table.q6(pool[i])) == 0
    assert compile_log.events(since=since) == []


def test_no_result_is_remembered_between_statements(table):
    e = table.data["pool"][2]
    d0 = table.ds.dispatch.stats()["dispatches"]
    a, b = table.ask(Q1, e), table.ask(Q1, e)
    assert a[2] == b[2] == 1 and table.ds.dispatch.stats()["dispatches"] - d0 == 2
    assert typed(a[0]) == typed(b[0])


# ------------------------------------------------------------------ the kernel alone
def test_kernel_blocking_and_x64_off():
    import jax

    assert not jax.config.jax_enable_x64
    assert column_agg.blocking(1024) == (1, 1, 1024)
    assert column_agg.blocking(3_145_728) == (1, 48, 65_536)
    assert column_agg.blocking(3_145_728, 8 * 8) == (1, 48, 65_536)
    assert column_agg.blocking(12_288) == (1, 1, 12_288)
    supers, per, block = column_agg.blocking(1 << 27)
    assert block == 65_536 and per <= column_agg.SUPER_BLOCKS and supers * per * block == 1 << 27


@pytest.mark.parametrize("lanes,groups", [(8, 8), (8, 64), (16, 256), (64, 256)])
def test_one_scan_steps_one_hot_stays_under_its_bound(lanes, groups):
    """However many lanes and group slots ride, a step's one-hot is at most
    CHUNK_ELEMS elements and a superblock at most 127 x 65,536 rows."""
    for slots in (3_145_728, 1 << 24, 1 << 27, 12_288, 1024):
        supers, per, block = column_agg.blocking(slots, lanes * groups)
        chunk = column_agg.chunking(per, block, lanes * groups)
        assert supers * per * block == slots and per % chunk == 0
        assert per * block <= column_agg.SUPER_BLOCKS * column_agg.BLOCK_ROWS
        assert lanes * groups * chunk * block <= column_agg.CHUNK_ELEMS


def test_kernel_wraps_modulo_2_to_64_and_reads_signed():
    import jax.numpy as jnp

    n = 2048
    rng = np.random.default_rng(5)
    a = rng.integers(-(1 << 31) + 1, (1 << 31) - 1, n).astype(np.int32)
    b = rng.integers(-(1 << 31) + 1, (1 << 31) - 1, n).astype(np.int32)
    g = rng.integers(0, 3, n).astype(np.int32)
    monos = ((0, 1), (0,), (0, 0, 1))
    consts = np.zeros((8, 1), np.int32)
    consts[:3, 0] = [0, -5, 1 << 30]
    out = column_agg.grouped_aggregate(
        tuple(jnp.asarray(x) for x in (a, b, g)), np.int32(n - 48), consts, np.asarray([1], np.int32),
        pred=((0, ">"),), keys=(2,), exprs=monos, groups=4)
    res = column_agg.unpack_results(np.asarray(out), 3, 4, 3)
    A, B = a[: n - 48].astype(object), b[: n - 48].astype(object)
    want = [A * B, A, A * A * B]
    for r in range(3):
        counts, first, sums = res[r]
        for k in range(3):
            sel = (a[: n - 48] > consts[r, 0]) & (g[: n - 48] == k)
            assert counts[k] == sel.sum() and first[k] == np.flatnonzero(sel)[0]
            for j in range(3):
                true = sum(want[j][sel].tolist()) & ((1 << 64) - 1)  # past int64 a sum wraps, which the route rule never lets happen
                assert sums[j][k] == (true - (1 << 64) if true >> 63 else true)
        assert counts[3] == 0


def test_a_launch_of_many_group_slots_cuts_its_blocks_and_stays_exact():
    """16 lanes x 256 group slots over planes of 65,536-row blocks: the
    blocks are cut (8,192 rows) and the sums are what they were."""
    import jax.numpy as jnp

    n = 1 << 17
    rng = np.random.default_rng(11)
    v = rng.integers(-(1 << 31) + 1, (1 << 31) - 1, n).astype(np.int32)
    g = rng.integers(0, 200, n).astype(np.int32)
    assert column_agg.blocking(n, 16 * 256) == (1, 16, 8192)
    consts = np.zeros((16, 1), np.int32)
    consts[:, 0] = np.arange(16) * 10
    out = column_agg.grouped_aggregate((jnp.asarray(v), jnp.asarray(g)), np.int32(n - 5), consts, np.asarray([1], np.int32),
                                       pred=((1, ">="),), keys=(1,), exprs=((0, 0),), groups=256)
    res = column_agg.unpack_results(np.asarray(out), 16, 256, 1)
    V = v[: n - 5].astype(object)
    for r in (0, 7, 15):
        counts, _, sums = res[r]
        for k in (0, 69, 70, 71, 150, 199, 200, 255):
            sel = (g[: n - 5] >= consts[r, 0]) & (g[: n - 5] == k)
            assert counts[k] == sel.sum()
            true = sum((V[sel] * V[sel]).tolist()) & ((1 << 64) - 1)
            assert sums[0][k] == (true - (1 << 64) if true >> 63 else true)


# ------------------------------------------------------------------ the mirror's build by whole blocks
def _docs(kind: str, n: int = 1300):
    rng = np.random.default_rng(len(kind))
    docs = [{"a": int(rng.integers(-5, 5)), "s": f"w{i % 7}", "f": float(i) / 3, "b": bool(i % 2),
             "t": Datetime(i * 10**9), "big": (1 << 40) + i} for i in range(n)]
    at = n // 2  # inside the second block of a scan's 500
    if kind == "float_in_ints":
        docs[at]["a"] = 2.5
    elif kind == "missing_field":
        del docs[at]["s"]
    elif kind == "extra_field":
        docs[at]["z"] = 1
    elif kind == "none_and_null":
        docs[10]["a"], docs[11]["s"] = None, None
    elif kind == "nested":
        for i, d in enumerate(docs):
            d["o"] = {"x": i, "y": [i]}
    elif kind == "lists_and_things":
        from surrealdb_tpu.sql.value import Thing

        docs[5]["a"], docs[6]["s"] = [1, 2], Thing("t", 1)
    elif kind == "past_f64":
        docs[at]["big"] = (1 << 60) + 1
    elif kind == "past_int64":
        docs[at]["big"] = 1 << 70
    elif kind == "decimal":
        docs[3]["f"] = Decimal("1.5")
    elif kind == "field_order":
        docs[at] = dict(reversed(list(docs[at].items())))
    return docs


@pytest.mark.parametrize("kind", ["homogeneous", "float_in_ints", "missing_field", "extra_field", "none_and_null", "nested",
                                  "lists_and_things", "past_f64", "past_int64", "decimal", "field_order"])
def test_the_block_build_makes_the_per_cell_builds_columns(kind, monkeypatch):
    from surrealdb_tpu.idx import column_mirror as cm

    def build(block: bool):
        ds, s = Datastore("memory"), Session.owner("t", "t")
        try:
            with monkeypatch.context() as m:
                if not block:
                    m.setattr(cm, "_put_block", lambda *a: False)
                else:
                    real, calls = cm._put_block, []
                    m.setattr(cm, "_put_block", lambda *a: calls.append(real(*a)) or calls[-1])
                delta, _ = cm._build_block(_docs(kind, 90))
                if kind == "past_int64":
                    return (delta,)  # the KV holds no such int: the bulk delta's block alone
                rows = [dict(d, id=i) for i, d in enumerate(_docs(kind))]
                out = ds.execute("DEFINE TABLE x SCHEMALESS; INSERT INTO x $rows RETURN NONE;", s, vars={"rows": rows})
                assert all(r["status"] == "OK" for r in out), out
                mirror = ds.column_mirrors.build(ds, "t", "t", "x")
                if block and kind == "homogeneous":
                    assert calls and all(calls)  # every block took the whole-array path, the records' `id` and all
                return mirror, delta
        finally:
            ds.close()

    for fast, slow in zip(build(True), build(False)):
        assert fast.n == slow.n and list(fast.columns) == list(slow.columns)
        assert fast.nested_unsafe == slow.nested_unsafe and fast.overflow == slow.overflow
        for p, a in fast.columns.items():
            b = slow.columns[p]
            assert (a.tags == b.tags).all() and np.array_equal(a.nums, b.nums, equal_nan=True), p
            assert (a.str_array() == b.str_array()).all() and (a.i64() == b.i64()).all(), p
