"""Deployment kind `scan_aggregate`: one wide table loaded in bulk and read
whole by every statement: a WHERE over every row and a grouped or a single
aggregate of integer expressions (TPC-H's LINEITEM under Q1 and Q6).

Everything that decides `correct` is here and reads nothing the program
made: the seeded generator (dbgen's distributions, recalled), the plain
reference (the generated arrays, a boolean mask, the groups of the two
flags, exact integer sums), the float32-accumulated control and the
comparison. No JAX, nothing of `surrealdb_tpu` (the loader gets the
datastore handed in and wraps the dates as the program's datetimes).

Money and rates are integers scaled by 100 (cents, hundredths), dates are
days since 1970-01-01 here and datetimes at midnight UTC in the table.
"""

from __future__ import annotations

import datetime as _dt
import time

import numpy as np

KIND = "scan_aggregate"
INGEST_BATCH = 50_000
_EPOCH = _dt.date(1970, 1, 1)
DAY_NS = 86_400 * 10**9
INSTRUCT = ("DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN")
MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
WORDS = ("furiously", "quickly", "carefully", "blithely", "slyly", "regular", "express", "special", "final",
         "pending", "ironic", "even", "bold", "silent", "deposits", "requests", "accounts", "packages",
         "instructions", "foxes", "pinto", "beans", "theodolites", "platelets", "sleep", "nag", "haggle",
         "cajole", "wake", "above", "according", "to", "the", "across", "along")
Q1_SUMS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge")
Q1_MEANS = (("avg_qty", "sum_qty"), ("avg_price", "sum_base_price"), ("avg_disc", "sum_disc"))


def day(text: str) -> int:
    return (_dt.date.fromisoformat(text) - _EPOCH).days


def iso(days: int) -> str:
    return (_EPOCH + _dt.timedelta(days=int(days))).isoformat() + "T00:00:00Z"


# ------------------------------------------------------------------ data
def generate(cfg: dict, sizes: dict, seed: int) -> dict:
    """One fixed LINEITEM from the configuration's `corpus_seed` (the source
    has one data set a scale factor), and from `seed` the pool of
    substitution parameters. Orders are drawn first (date, 1 to 7 lines),
    then every line's columns, by dbgen's rules as the configuration's
    `generator` recalls them."""
    g = cfg["generator"]
    rng = np.random.default_rng(np.random.SeedSequence([int(g["corpus_seed"]), 47]))
    orders = int(sizes["orders"])
    scale = orders / float(g["orders_at_sf1"])
    parts = max(int(round(g["parts_at_sf1"] * scale)), 1000)
    suppliers = max(int(round(g["suppliers_at_sf1"] * scale)), 4)
    odate = rng.integers(day(g["orderdate_min"]), day(g["orderdate_max"]) + 1, orders)
    lines = rng.integers(1, 8, orders)
    order_of = np.repeat(np.arange(orders), lines)
    n = int(order_of.size)
    starts = np.cumsum(lines) - lines
    cur = day(g["currentdate"])
    c = {}
    c["l_orderkey"] = order_of + 1
    c["l_linenumber"] = np.arange(n) - starts[order_of] + 1
    c["l_partkey"] = rng.integers(1, parts + 1, n)
    c["l_suppkey"] = (c["l_partkey"] + rng.integers(0, 4, n) * (suppliers // 4 + (c["l_partkey"] - 1) // suppliers)) % suppliers + 1
    c["l_quantity"] = rng.integers(1, 51, n)
    retail = 90000 + (c["l_partkey"] // 10) % 20001 + 100 * (c["l_partkey"] % 1000)  # cents
    c["l_extendedprice"] = c["l_quantity"] * retail
    c["l_discount"] = rng.integers(0, 11, n)
    c["l_tax"] = rng.integers(0, 9, n)
    c["l_shipdate"] = odate[order_of] + rng.integers(1, 122, n)
    c["l_commitdate"] = odate[order_of] + rng.integers(30, 91, n)
    c["l_receiptdate"] = c["l_shipdate"] + rng.integers(1, 31, n)
    returned = np.where(rng.integers(0, 2, n) == 0, "R", "A")
    c["l_returnflag"] = np.where(c["l_receiptdate"] <= cur, returned, "N")
    c["l_linestatus"] = np.where(c["l_shipdate"] > cur, "O", "F")
    c["l_shipinstruct"] = rng.integers(0, len(INSTRUCT), n)
    c["l_shipmode"] = rng.integers(0, len(MODES), n)
    text = " ".join(WORDS[i] for i in rng.integers(0, len(WORDS), 40_000))
    c["comment_at"] = rng.integers(0, len(text) - 44, n)
    c["comment_len"] = rng.integers(10, 44, n)
    return {"columns": c, "text": text, "rows": n, "pool": draw_pool(cfg, int(sizes["pool"]), seed)}


def draw_pool(cfg: dict, size: int, seed: int) -> list:
    """Q1's DELTA and Q6's DATE, DISCOUNT and QUANTITY, uniform over the
    source's substitution ranges; dates as ISO text, as a JSON client sends
    them."""
    g = cfg["generator"]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed) & 0xFFFFFFFFFFFFFFFF, 53]))
    end = day(g["q1_enddate"])
    out = []
    for _ in range(size):
        delta = int(rng.integers(g["q1_delta"][0], g["q1_delta"][1] + 1))
        year = int(rng.integers(g["q6_year"][0], g["q6_year"][1] + 1))
        disc = int(rng.integers(g["q6_discount"][0], g["q6_discount"][1] + 1))
        out.append({
            "d": iso(end - delta), "lo": f"{year}-01-01T00:00:00Z", "hi": f"{year + 1}-01-01T00:00:00Z",
            "dlo": disc - 1, "dhi": disc + 1, "qty": int(rng.choice(g["q6_quantity"])),
        })
    return out


def pool(cfg: dict, data: dict) -> list:
    return data["pool"]


def rows_of(data: dict, lo: int, hi: int, wrap) -> list:
    """Rows lo..hi as the documents the loader inserts; `wrap` makes the
    table's datetime of a day number."""
    c, text = data["columns"], data["text"]
    cols = {k: v[lo:hi].tolist() for k, v in c.items()}
    dates = {k: [wrap(d) for d in cols[k]] for k in ("l_shipdate", "l_commitdate", "l_receiptdate")}
    return [
        {
            "id": lo + i,
            "l_orderkey": cols["l_orderkey"][i], "l_partkey": cols["l_partkey"][i],
            "l_suppkey": cols["l_suppkey"][i], "l_linenumber": cols["l_linenumber"][i],
            "l_quantity": cols["l_quantity"][i], "l_extendedprice": cols["l_extendedprice"][i],
            "l_discount": cols["l_discount"][i], "l_tax": cols["l_tax"][i],
            "l_returnflag": cols["l_returnflag"][i], "l_linestatus": cols["l_linestatus"][i],
            "l_shipdate": dates["l_shipdate"][i], "l_commitdate": dates["l_commitdate"][i],
            "l_receiptdate": dates["l_receiptdate"][i],
            "l_shipinstruct": INSTRUCT[cols["l_shipinstruct"][i]], "l_shipmode": MODES[cols["l_shipmode"][i]],
            "l_comment": text[cols["comment_at"][i] : cols["comment_at"][i] + cols["comment_len"][i]],
        }
        for i in range(hi - lo)
    ]


# ------------------------------------------------------------------ reference
def q1_terms(c: dict) -> dict:
    """Q1's summed expressions a row, as int64 (the largest, the charge, is
    under 1.2e11, and 2**63 / 1.2e11 is 7.6e7 rows: asserted where they are summed)."""
    price, disc, tax = (c[k].astype(np.int64) for k in ("l_extendedprice", "l_discount", "l_tax"))
    disc_price = price * (100 - disc)
    return {"sum_qty": c["l_quantity"].astype(np.int64), "sum_base_price": price, "sum_disc_price": disc_price,
            "sum_charge": disc_price * (100 + tax), "sum_disc": disc}


def flag_groups(c: dict) -> tuple:
    """(labels, group of a row): the distinct (returnflag, linestatus) pairs
    in their sorted order, as two-character labels, and each row's index
    into them. Both flags are one ASCII character, so a pair sorts as its
    two code points."""
    code = c["l_returnflag"].astype("<U1").view(np.uint32) * 256 + c["l_linestatus"].astype("<U1").view(np.uint32)
    codes, inv = np.unique(code, return_inverse=True)
    return [chr(int(k) >> 8) + chr(int(k) & 255) for k in codes], inv.reshape(-1)


def q1_row(label: str, sums: dict, count: int) -> dict:
    row = {"l_returnflag": label[0], "l_linestatus": label[1]}
    row.update({k: sums[k] for k in Q1_SUMS})
    row.update({mean: sums[of] / count for mean, of in Q1_MEANS})
    row["count_order"] = count
    return row


def q1(c: dict, cutoff: int, accumulate=None) -> list:
    """Q1 for one cut-off day: the rows shipped on or before it, grouped by
    the two flags in their sorted order; a group's four sums, three means
    (sum / count in float64) and count; a group no row reaches is absent.
    `accumulate` replaces the exact integer sum (the control's float32
    sum)."""
    labels, group = flag_groups(c)
    terms, mask = q1_terms(c), c["l_shipdate"] <= cutoff
    out = []
    for g, label in enumerate(labels):
        mine = np.flatnonzero(mask & (group == g))
        if not mine.size:
            continue
        sums = {}
        for name, vals in terms.items():
            assert int(np.abs(vals).max(initial=0)) * mine.size < 2**63
            sums[name] = accumulate(vals[mine]) if accumulate else int(vals[mine].sum(dtype=np.int64))
        out.append(q1_row(label, sums, int(mine.size)))
    return out


def q1_many(c: dict, cutoffs: list) -> dict:
    """{cut-off: q1(c, cut-off)} for many cut-offs in one pass: the rows in
    ship-date order, a running int64 sum a (group, expression), read at
    each cut-off's last row."""
    labels, group = flag_groups(c)
    order = np.argsort(c["l_shipdate"], kind="stable")
    ends = np.searchsorted(c["l_shipdate"][order], np.asarray(cutoffs), side="right")
    terms = {k: v[order] for k, v in q1_terms(c).items()}
    group = group[order]
    per = {cut: [] for cut in cutoffs}
    for g, label in enumerate(labels):
        mine = group == g
        counts = np.concatenate([[0], np.cumsum(mine)])[ends]
        sums = {}
        for name, vals in terms.items():
            assert int(np.abs(vals).max(initial=0)) * max(int(mine.sum()), 1) < 2**63
            sums[name] = np.concatenate([[0], np.cumsum(np.where(mine, vals, 0), dtype=np.int64)])[ends]
        for i, cut in enumerate(cutoffs):
            if counts[i]:
                per[cut].append(q1_row(label, {k: int(v[i]) for k, v in sums.items()}, int(counts[i])))
    return per


def q6(c: dict, lo: int, hi: int, dlo: int, dhi: int, qty: int):
    """Q6: the revenue of one year's lines in a discount band under a
    quantity; None where no line passes (GROUP ALL over no row is no row)."""
    ship, disc = c["l_shipdate"], c["l_discount"]
    mask = (ship >= lo) & (ship < hi) & (disc >= dlo) & (disc <= dhi) & (c["l_quantity"] < qty)
    if not mask.any():
        return None
    return int((c["l_extendedprice"][mask].astype(np.int64) * disc[mask]).sum(dtype=np.int64))


def float32_sum(vals: np.ndarray) -> int:
    """The control: the same sum carried in float32."""
    return int(np.add.reduce(vals.astype(np.float32), dtype=np.float32))


def reference(cfg: dict, data: dict) -> dict:
    """Both queries' answers for every pool entry (computed once a distinct
    parameter set: Q1 has 61 cut-offs, Q6 80 combinations), and the
    float32 control of the pool's first Q1."""
    c = data["columns"]
    by_cut = q1_many(c, sorted({day(e["d"][:10]) for e in data["pool"]}))
    by_q6, primary, second = {}, [], []
    for e in data["pool"]:
        primary.append(by_cut[day(e["d"][:10])])
        key = (day(e["lo"][:10]), day(e["hi"][:10]), e["dlo"], e["dhi"], e["qty"])
        if key not in by_q6:
            by_q6[key] = q6(c, *key)
        second.append(by_q6[key])
    control = q1(c, day(data["pool"][0]["d"][:10]), accumulate=float32_sum)
    passing = primary[0] and sum(r["count_order"] for r in primary[0])
    return {"primary": primary, "q6": second, "control": control, "rows": data["rows"],
            "groups": max(len(a) for a in primary), "q1_pass_share": passing / max(data["rows"], 1)}


def value_mismatches(served: list, truth: list) -> int:
    """Differing numbers between two lists of Q1 rows of equal length: an
    integer that differs, a mean that is not the reference's sum / count in
    float64."""
    bad = 0
    for got, want in zip(served, truth):
        for k, v in want.items():
            if not isinstance(v, str):
                bad += type(got.get(k)) is not type(v) or got[k] != v
    return bad


# ------------------------------------------------------------------ load
def load(ds, cfg: dict, data: dict, execute_ok) -> dict:
    """DEFINE the table, INSERT the rows through the embedded entry point in
    batches, then the probe (`load.probe`); the harness counts the rows back
    right after. Also the process's resident bytes before the first INSERT
    and after the probe."""
    from surrealdb_tpu.sql.value import Datetime

    tb, n = cfg["table"], data["rows"]
    for ddl in cfg["ddl"]:
        execute_ok(ds, ddl)
    before = resident_bytes()
    secs = 0.0
    for i in range(0, n, INGEST_BATCH):
        rows = rows_of(data, i, min(i + INGEST_BATCH, n), lambda d: Datetime(d * DAY_NS))
        t0 = time.perf_counter()
        execute_ok(ds, f"INSERT INTO {tb} $rows RETURN NONE", {"rows": rows})
        secs += time.perf_counter() - t0
    return {"acknowledged": n, "insert_s": secs, "unit": "rows", **probe(ds, cfg, data, execute_ok),
            "rss_before_bytes": before, "rss_bytes": resident_bytes()}


def resident_bytes() -> int:
    with open("/proc/self/status") as f:
        return next((int(ln.split()[1]) * 1024 for ln in f if ln.startswith("VmRSS:")), 0)


def probe(ds, cfg: dict, data: dict, execute_ok) -> dict:
    """The pool's first entry asked both statements of `load.probe`, in that
    order, through `ds.execute()`: each answer equal to the reference's
    (keys, values and types) and each exactly its `dispatches` device
    dispatches. A program that serves the first by walking rows is refused
    there and never starts the second's walk. The seconds of the first (it
    is the first statement over the table: the mirror's build, the columns'
    encode and upload, the first compile) come back for the `ingest` line."""
    c, entry, out = data["columns"], data["pool"][0], {}
    want = {
        "primary": q1(c, day(entry["d"][:10])),
        "q6": [{"revenue": v} for v in [q6(c, day(entry["lo"][:10]), day(entry["hi"][:10]), entry["dlo"], entry["dhi"], entry["qty"])] if v is not None],
    }
    for name in cfg["load"]["probe"]:
        st = cfg["statements"][name]
        before = ds.dispatch.stats()["submitted"]
        t0 = time.perf_counter()
        rows = execute_ok(ds, st["sql"], {st["bind"]: entry})[-1]["result"]
        out.setdefault("probe_s", time.perf_counter() - t0)
        made = ds.dispatch.stats()["submitted"] - before
        if made != st["dispatches"]:
            raise RuntimeError(f"statement {name!r} of the loader's probe made {made} device dispatches "
                               f"where it makes {st['dispatches']}: the scan was served on the host")
        if len(rows) != len(want[name]) or value_mismatches(rows, want[name]) or any(
                got.get(k) != v for got, truth in zip(rows, want[name]) for k, v in truth.items() if isinstance(v, str)):
            raise RuntimeError(f"statement {name!r} of the loader's probe differs from the reference: {str(rows)[:300]}")
    return out


def count_sql(cfg: dict) -> list:
    return [(f"SELECT count() AS c FROM {cfg['table']} GROUP ALL", None)]


def release(data: dict) -> None:
    """The table has been loaded and referred to: give its bytes back."""
    data.pop("columns", None)
    data.pop("text", None)


def wait_background(ds, cfg: dict, timeout: float) -> dict:
    from surrealdb_tpu import bg

    t0 = time.perf_counter()
    if not bg.wait_idle(timeout, owner=id(ds)):
        raise RuntimeError(f"background tasks still running after {timeout:.0f}s")
    return {"state": {}, "line": {"wait_s": time.perf_counter() - t0}}


def kernel_shapes(cfg: dict, data: dict, state: dict) -> dict:
    """What one Q1 reads and writes: the table's rows, its groups, its aggregates."""
    return {"rows": int(data["rows"]), "groups": int(cfg["groups"]), "aggregates": int(cfg["aggregates"])}


# ------------------------------------------------------------------ check
def check(cfg: dict, ref: dict, records: list) -> dict:
    """Every answer of the window against the reference. The load generator
    keeps a reply's numbers by field in row order (not its strings), so a
    group missing, extra or out of its order shows as `group_mismatch` (the
    row counts differ) or as every number of the displaced rows differing."""
    lim = cfg["correct"]
    groups = values = answers = 0
    for r in records:
        if r["status"] != "OK":
            continue
        answers += 1
        truth = ref[r["s"]][r["q"]]
        if r["s"] == "q6":
            truth = [] if truth is None else [{"revenue": truth}]
        n = max((len(v) for v in r["values"].values()), default=0)
        if n != len(truth):
            groups += 1
            continue
        served = [{k: v[i] for k, v in r["values"].items()} for i in range(n)]
        values += value_mismatches(served, truth)
    first = ref["primary"][0]
    return {
        "numbers": [
            ["group_mismatch", groups if answers else 1, "<=", lim["group_mismatch_max"]],
            ["value_mismatch", values, "<=", lim["value_mismatch_max"]],
        ],
        "control": {"value_mismatch_float32": value_mismatches(ref["control"], first)},
        "metrics": {},
        "compared": {"answers": answers, "rows": ref["rows"]},
    }
