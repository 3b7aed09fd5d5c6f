#!/usr/bin/env python3
"""Chip smoke: the served kNN and graph path, once, on the TPU.

One process owns the chip from start to finish. It starts the server in
process, bulk-loads through the embedded entry point on that server's
Datastore, asks every query over the wire (POST /sql and the WebSocket
`query` RPC through `surrealdb_tpu.Surreal`), and checks what comes back
against plain NumPy references over the same data:

- `item`: HNSW index (served as IVF) over --rows x 768 clustered vectors
  (BASELINE.json config 2); `item_exact`: an MTREE index (always the exact
  fused kernel) over the first 262,144 of them;
- `person`/`knows`: 10,000 nodes / 1,000,000 edges (config 1), 3-hop and
  1-hop `count(->knows->person...)` chains.

Every check is fatal. There is no branch that carries on without a chip:
when `jax.default_backend()` is not `tpu` the script says what it found
and exits non-zero before it loads anything. `tests/test_chip_smoke.py`
drives the same load/query/check functions at 8,192 rows on the CPU
backend (it hands `run()` a small `Sizes`) — the device check is the one
thing that rehearsal cannot pass.

    python chip_smoke.py --seed 0 [--rows N]

`--rows` may only cut `item` as far as the time limit forces and never
below 262,144; every other size is fixed. It prints one JSON line per
phase (each names platform, device_kind, device count and the JAX/libtpu
versions; the `passed` line states the sizes that ran and any row cut) and,
last, one JSON object with exactly these keys, the device as JAX reports it:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
The speeds it prints are observations named by device, not claims.
"""

from __future__ import annotations

import argparse
import dataclasses
import http.client
import json
import resource
import sys
import threading
import time

import numpy as np

NS = DB = "smoke"
DIM = 768  # BASELINE.json config 2 width; never cut
K, EF = 10, 64
N_CLUSTERS, CLUSTER_SIGMA = 4000, 0.35
ROWS, MIN_ROWS = 1_000_000, 262_144
INGEST_BATCH = 20_000
WAIT_S = 600.0  # longest wait for one background phase or one HTTP reply
KNN_SQL = f"SELECT id FROM {{tb}} WHERE emb <|{K},{EF}|> {{q}}"
HOP = "->knows->person"
COUNT_SQL = "SELECT count({chain}) AS c FROM person:{seed}"

# served-path strategies that ran a device kernel (idx/knn.py)
DEVICE_STRATEGIES = ("ivf", "ivf-sharded", "exact-device", "exact-sharded")


class SmokeFailure(Exception):
    """A fatal check: the smoke exits non-zero and prints no ok line."""


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What one run loads and asks. The defaults are the smoke; only `rows`
    is reachable from the command line (tests/test_chip_smoke.py builds a
    small one for the CPU rehearsal)."""

    rows: int = ROWS  # item (HNSW -> IVF)
    exact_rows: int = 262_144  # item_exact (MTREE): the first rows of item
    nodes: int = 10_000
    edges: int = 1_000_000
    warm: int = 100  # warm single-stream statements per shape and transport
    clients: int = 32  # concurrent WebSocket sessions in the burst

    def stated(self) -> dict:
        return {
            "rows": self.rows, "dim": DIM,
            "exact_rows": min(self.exact_rows, self.rows),
            "nodes": self.nodes, "edges": self.edges,
            "rows_cut": None if self.rows >= ROWS else f"{self.rows} of {ROWS}",
        }


# ------------------------------------------------------------------ device
def require_tpu(backend: str, rows: int) -> None:
    if backend != "tpu":
        raise SmokeFailure(
            f"jax.default_backend() is {backend!r}, not 'tpu': no accelerator, "
            "nothing was run"
        )
    if rows < MIN_ROWS:
        raise SmokeFailure(
            f"--rows {rows} is below {MIN_ROWS}, the smallest corpus the smoke "
            "may be cut to on a chip: nothing was run"
        )


# ------------------------------------------------------------------ data
def gen_corpus(n: int, seed: int) -> np.ndarray:
    """Clustered corpus (mixture of gaussians: 4,000 centres, sigma 0.35),
    rows of `BASELINE.json` config 2's width, from --seed."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((N_CLUSTERS, DIM)).astype(np.float32)
    out = np.empty((n, DIM), dtype=np.float32)
    step = 65_536
    for i in range(0, n, step):
        m = min(step, n - i)
        cid = rng.integers(0, N_CLUSTERS, size=m)
        out[i : i + m] = centers[cid] + CLUSTER_SIGMA * rng.standard_normal(
            (m, DIM), dtype=np.float32
        )
    return out


def gen_queries(corpus: np.ndarray, n: int, rng) -> np.ndarray:
    idx = rng.integers(0, corpus.shape[0], size=n)
    return corpus[idx] + 0.05 * rng.standard_normal((n, DIM)).astype(np.float32)


# ------------------------------------------------------------------ references
def exact_topk(corpus: np.ndarray, qs: np.ndarray, k: int) -> np.ndarray:
    """Exact float32 euclidean top-k ids, chunked BLAS. [nq, k]."""
    q2 = (qs**2).sum(axis=1)[:, None]
    best_d = np.full((qs.shape[0], k), np.inf, dtype=np.float32)
    best_i = np.zeros((qs.shape[0], k), dtype=np.int64)
    step = 131_072
    for i in range(0, corpus.shape[0], step):
        blk = corpus[i : i + step]
        d = q2 + (blk**2).sum(axis=1)[None, :] - 2.0 * (qs @ blk.T)
        md = np.concatenate([best_d, d], axis=1)
        mi = np.concatenate(
            [best_i, np.broadcast_to(np.arange(i, i + blk.shape[0]), d.shape)],
            axis=1,
        )
        sel = np.argpartition(md, k - 1, axis=1)[:, :k]
        best_d = np.take_along_axis(md, sel, axis=1)
        best_i = np.take_along_axis(mi, sel, axis=1)
    order = np.argsort(best_d, axis=1)
    return np.take_along_axis(best_i, order, axis=1)


def recall_at_k(got, truth: np.ndarray) -> float:
    """Mean |got ∩ truth| / k over queries; `got` is a list of id lists."""
    hits = sum(len(set(g) & set(t.tolist())) for g, t in zip(got, truth))
    return hits / float(truth.shape[0] * truth.shape[1])


def path_counts(pairs: np.ndarray, nodes: int, seeds) -> dict:
    """{seed: {1: 1-hop count, 3: 3-hop count}} over the edge arrays. Every edge
    record is its own path step (duplicate pairs count twice), which is
    the flatten-without-dedup multiplicity the engine keeps."""
    src, dst = pairs[:, 0], pairs[:, 1]
    outdeg = np.bincount(src, minlength=nodes).astype(np.int64)
    out = {}
    for s in seeds:
        x1 = np.bincount(dst[src == s], minlength=nodes).astype(np.int64)
        x2 = np.bincount(dst, weights=x1[src], minlength=nodes).astype(np.int64)
        out[int(s)] = {1: int(outdeg[s]), 3: int((x2 * outdeg).sum())}
    return out


# ------------------------------------------------------------------ load
def _session():
    from surrealdb_tpu.dbs.session import Session

    return Session.owner(NS, DB)


def execute_ok(ds, sql: str, vars=None) -> list:
    out = ds.execute(sql, _session(), vars=vars)
    check_rows_ok(out, sql)
    return out


def load_items(ds, table: str, index: str, corpus: np.ndarray) -> float:
    """DEFINE the table + vector index, then bulk-load `corpus` through the
    embedded entry point; returns the INSERT seconds."""
    execute_ok(
        ds,
        f"DEFINE TABLE {table} SCHEMALESS; DEFINE INDEX {table}_emb ON {table} "
        f"FIELDS emb {index}",
    )
    secs = 0.0
    for i in range(0, corpus.shape[0], INGEST_BATCH):
        blk = corpus[i : i + INGEST_BATCH]
        rows = [{"id": i + j, "emb": blk[j]} for j in range(blk.shape[0])]
        t0 = time.perf_counter()
        execute_ok(ds, f"INSERT INTO {table} $rows RETURN NONE", {"rows": rows})
        secs += time.perf_counter() - t0
    return secs


def load_graph(ds, nodes: int, pairs: np.ndarray) -> tuple:
    """(person INSERT seconds, knows INSERT RELATION seconds)."""
    from surrealdb_tpu.sql.value import Thing

    execute_ok(ds, "DEFINE TABLE person SCHEMALESS; DEFINE TABLE knows SCHEMALESS")
    t0 = time.perf_counter()
    for i in range(0, nodes, INGEST_BATCH):
        rows = [{"id": j} for j in range(i, min(i + INGEST_BATCH, nodes))]
        execute_ok(ds, "INSERT INTO person $rows RETURN NONE", {"rows": rows})
    t_nodes = time.perf_counter() - t0
    t_edges = 0.0
    for i in range(0, pairs.shape[0], INGEST_BATCH):
        rows = [
            {"in": Thing("person", int(a)), "out": Thing("person", int(b))}
            for a, b in pairs[i : i + INGEST_BATCH]
        ]
        t0 = time.perf_counter()
        execute_ok(ds, "INSERT RELATION INTO knows $rows RETURN NONE", {"rows": rows})
        t_edges += time.perf_counter() - t0
    return t_nodes, t_edges


# ------------------------------------------------------------------ wire
class Wire:
    """One client: a WebSocket RPC session and an HTTP /sql connection."""

    def __init__(self, srv):
        from surrealdb_tpu import Surreal

        self.host, self.port = srv.host, srv.port
        self.ws = Surreal(f"ws://{self.host}:{self.port}/rpc")
        self.ws.use(NS, DB)

    def rpc(self, sql: str, vars=None) -> list:
        """WebSocket `query` RPC."""
        return self.ws.query(sql, vars)

    def _http(self, method: str, path: str, body=None) -> bytes:
        conn = http.client.HTTPConnection(self.host, self.port, timeout=WAIT_S)
        try:
            conn.request(
                method, path, body,
                {"NS": NS, "DB": DB, "Accept": "application/json"},
            )
            r = conn.getresponse()
            data = r.read()
        finally:
            conn.close()
        if r.status != 200:
            raise SmokeFailure(f"{method} {path} -> HTTP {r.status}: {data[:200]!r}")
        return data

    def sql(self, text: str) -> list:
        """POST /sql (raw SurrealQL text, JSON response)."""
        return json.loads(self._http("POST", "/sql", text.encode()))

    def counters(self) -> dict:
        """{(family, labels-string): value} of GET /metrics' counter lines."""
        out = {}
        for line in self._http("GET", "/metrics").decode().splitlines():
            if line.startswith("#") or "_total" not in line:
                continue
            name, _, val = line.rpartition(" ")
            fam, brace, labels = name.partition("{")
            out[(fam, brace + labels)] = float(val)
        return out

    def close(self) -> None:
        self.ws.close()


def vec_literal(v: np.ndarray) -> str:
    # 9 significant digits round-trip a float32 exactly
    return "[" + ",".join(format(float(x), ".9g") for x in v) + "]"


def knn_ids(rows: list) -> list:
    """Record ids of the last statement's result, from either transport
    (/sql renders `item:12`, the msgpack RPC carries a Thing)."""
    out = []
    for r in rows[-1]["result"]:
        rid = r["id"]
        out.append(int(str(rid).rsplit(":", 1)[1]) if isinstance(rid, str) else int(rid.id))
    return out


def ask_knn(wire: Wire, table: str, q: np.ndarray, transport: str) -> tuple:
    """(ids, seconds) of one kNN statement over the named transport."""
    t0 = time.perf_counter()
    if transport == "sql":
        rows = wire.sql(KNN_SQL.format(tb=table, q=vec_literal(q)))
    else:
        rows = wire.rpc(KNN_SQL.format(tb=table, q="$q"), {"q": q.tolist()})
    dt = time.perf_counter() - t0
    check_rows_ok(rows, f"kNN on {table} via {transport}")
    return knn_ids(rows), dt


def ask_count(wire: Wire, seed: int, hops: int, transport: str) -> tuple:
    sql = COUNT_SQL.format(chain=HOP * hops, seed=seed)
    t0 = time.perf_counter()
    rows = wire.sql(sql) if transport == "sql" else wire.rpc(sql)
    dt = time.perf_counter() - t0
    check_rows_ok(rows, sql)
    return int(rows[-1]["result"][0]["c"]), dt


def quartiles(xs) -> dict:
    """Median and quartiles of a list of seconds, in ms, unrounded."""
    q1, q2, q3 = np.percentile(np.asarray(xs, dtype=np.float64) * 1e3, [25, 50, 75])
    return {"n": len(xs), "p25_ms": float(q1), "p50_ms": float(q2), "p75_ms": float(q3)}


def warm_knn(wire: Wire, table: str, qs: np.ndarray) -> tuple:
    """Every query over both transports, single stream: ({transport:
    [id lists]}, {transport: quartiles})."""
    got, lat = {}, {}
    for transport in ("rpc", "sql"):
        res = [ask_knn(wire, table, q, transport) for q in qs]
        got[transport] = [ids for ids, _ in res]
        lat[transport] = quartiles([dt for _, dt in res])
    return got, lat


def burst_knn(srv, table: str, qs: np.ndarray, clients: int) -> dict:
    """One burst: `clients` WebSocket sessions, each its own thread, fire
    their share of `qs` at once. Returns ids per query + latencies."""
    wires = [Wire(srv) for _ in range(clients)]
    per = qs.shape[0] // clients
    got = [None] * (per * clients)
    lat, errors = [], []
    barrier = threading.Barrier(clients + 1)

    def client(i: int) -> None:
        barrier.wait()
        for r in range(per):
            j = i * per + r
            try:
                got[j], dt = ask_knn(wires[i], table, qs[j], "rpc")
                lat.append(dt)
            except Exception as e:  # noqa: BLE001 — reported, then fatal
                errors.append(repr(e)[:300])

    threads = [threading.Thread(target=client, args=(i,)) for i in range(clients)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    for w in wires:
        w.close()
    if errors:
        raise SmokeFailure(f"{len(errors)} burst queries failed; first: {errors[0]}")
    return {"ids": got, "wall_s": wall, "latency": quartiles(lat)}


# ------------------------------------------------------------------ observe
def counter_delta(after: dict, before: dict, family: str) -> dict:
    """{labels-string: increase} of one counter family between two
    `Wire.counters()` readings."""
    fam = f"surreal_{family}_total"
    return {
        labels: v - before.get((f, labels), 0.0)
        for (f, labels), v in after.items()
        if f == fam and v != before.get((f, labels), 0.0)
    }


def knn_strategies(after: dict, before: dict) -> dict:
    """{strategy: statements it served} between two readings."""
    return {
        labels.split('strategy="', 1)[1].split('"', 1)[0]: int(v)
        for labels, v in counter_delta(after, before, "knn_strategy").items()
    }


def observe(wire: Wire, ds, metrics0: dict, t_start: float) -> dict:
    """What the engine says about the run: the strategies, dispatch and
    error counters that served it, background tasks, compile events and
    device memory. `metrics0` is `wire.counters()` from before the run."""
    import jax

    from surrealdb_tpu import bg, cnf, compile_log

    m1 = wire.counters()
    tasks = bg.snapshot()
    memory = [(d.id, d.memory_stats() or {}) for d in jax.local_devices()]
    return {
        "strategies": knn_strategies(m1, metrics0),
        "dispatch": ds.dispatch.stats(),
        "widths": {str(w): n for w, n in sorted(ds.dispatch.width_distribution().items())},
        "prewarm_errors": sum(counter_delta(m1, metrics0, "prewarm_errors").values()),
        "statement_errors": sum(counter_delta(m1, metrics0, "statement_errors").values()),
        "bg_bad": [
            {k: t[k] for k in ("kind", "target", "state", "stalled", "error")}
            for t in tasks["live"] + tasks["recent"]
            if t["state"] in ("failed", "stalled") or t["stalled"]
        ],
        "bg_durations": {
            f"{t['kind']}:{t['target']}": t["duration_s"]
            for t in tasks["recent"]
            if t["kind"] in ("ivf_train", "graph_prewarm", "shape_warm")
        },
        "compile_events": compile_log.events(since=t_start),
        "tpu_disable": bool(cnf.TPU_DISABLE),
        "device_memory": [
            {"id": i, "bytes_in_use": ms.get("bytes_in_use"),
             "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
             "bytes_limit": ms.get("bytes_limit")}
            for i, ms in memory
        ],
    }


# ------------------------------------------------------------------ checks
def check_rows_ok(rows: list, what: str) -> None:
    for r in rows:
        if r.get("status") != "OK":
            raise SmokeFailure(f"status {r.get('status')!r} for {what[:120]}: {str(r.get('result'))[:300]}")


def check_engine(obs: dict) -> None:
    """The served path ran on the device and nothing on it was absorbed."""
    bad = []
    served = obs["strategies"]
    if not served:
        bad.append("no kNN strategy was counted")
    for s in served:
        if s.split("(", 1)[0] not in DEVICE_STRATEGIES:
            bad.append(f"kNN strategy {s!r} served {served[s]} statement(s): not a device path")
    if not any(s.startswith("ivf") for s in served):
        bad.append(f"no IVF strategy served (served: {sorted(served)})")
    if not any(s.startswith("exact-") for s in served):
        bad.append(f"no exact device strategy served (served: {sorted(served)})")
    d = obs["dispatch"]
    if not d["dispatches"] > 0:
        bad.append("dispatch.dispatches is 0")
    for k in ("retries", "splits", "failures"):
        if d[k]:
            bad.append(f"dispatch.{k} = {d[k]}")
    for k in ("prewarm_errors", "statement_errors"):
        if obs[k]:
            bad.append(f"{k} = {obs[k]}")
    for t in obs["bg_bad"]:
        bad.append(f"bg task {t['kind']}:{t['target']} {t['state']} ({t['error']})")
    subsystems = {e["subsystem"] for e in obs["compile_events"]}
    for want in (("knn_exact", "knn_sharded"), ("ivf", "ivf_sharded")):
        if not subsystems & set(want):
            bad.append(f"no compile event for {' / '.join(want)}")
    if not any(s.startswith("graph_") for s in subsystems):
        bad.append("no compile event for a graph_* kernel")
    for e in obs["compile_events"]:
        if e["error"]:
            bad.append(f"compile of {e['subsystem']} {e['shape']} failed: {e['error']}")
    if obs["tpu_disable"]:
        bad.append("cnf.TPU_DISABLE is set")
    if bad:
        raise SmokeFailure("; ".join(bad))


def check_device_memory(obs: dict, corpus_bf16_bytes: int) -> None:
    """Each device holds at least its share of the bf16 corpus."""
    mem = obs["device_memory"]
    share = corpus_bf16_bytes // len(mem)
    for m in mem:
        if not m["bytes_in_use"] or m["bytes_in_use"] < share:
            raise SmokeFailure(
                f"device {m['id']} bytes_in_use={m['bytes_in_use']} is below its "
                f"{share}-byte share of the bf16 corpus"
            )


def host_twin_recall(mirror, qs: np.ndarray, truth: np.ndarray) -> float:
    """recall@k of `IvfState.search_host` — the same quantizer, probed and
    reranked in NumPy over the float32 host rows."""
    from surrealdb_tpu.idx.ivf import default_nprobe

    ivf = mirror.ivf
    data, _, _ = mirror.host_view()
    _, slots = ivf.search_host(qs, data, "euclidean", K, default_nprobe(ivf.nlists, EF))
    return recall_at_k([r.tolist() for r in slots], truth)


def check_ivf_recall(name: str, value: float, host_twin: float) -> None:
    if abs(value - host_twin) > 0.02 + 1e-9:
        raise SmokeFailure(
            f"{name}: recall@{K} {value:.4f} is not within 0.02 of "
            f"IvfState.search_host's {host_twin:.4f}"
        )


def check_counts_dispatched(asked: int, dispatched: int) -> None:
    """Every 3-hop count was one device dispatch. A count the engine served
    from its host walk (`_host_hop`) equals NumPy as well, so the answer
    alone cannot tell which side served it."""
    if dispatched != asked:
        raise SmokeFailure(
            f"{asked} 3-hop count statements grew dispatch.submitted by "
            f"{dispatched}: the rest were served on the host"
        )


def check_recall(name: str, value: float, floor: float) -> None:
    if not value >= floor:
        raise SmokeFailure(f"{name} recall@{K} {value:.4f} < {floor}")


# ------------------------------------------------------------------ phases
def measure_rtt(n: int = 50) -> dict:
    """A bare jitted dispatch + fetch round trip."""
    import jax
    import jax.numpy as jnp

    x = jax.device_put(jnp.ones((8, 8)))
    f = jax.jit(lambda a: (a @ a).sum())
    float(f(x))
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        float(f(x))
        ts.append(time.perf_counter() - t0)
    return quartiles(ts)


def host_rss_bytes() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * resource.getpagesize()


def _durations(names) -> dict:
    from surrealdb_tpu import telemetry

    d = telemetry.snapshot()["durations"]
    return {n: d[n]["total_s"] for n in names if n in d}


def _compile_seconds(events: list) -> dict:
    return {
        f"{e['subsystem']}[{e['shape']}]({e['mode']})": e["duration_ms"] / 1e3
        for e in events
    }


def phase_items(srv, wire, emit, corpus, rng, warm: int, clients: int) -> None:
    """`item` (HNSW -> IVF): ingest, the exact window while the quantizer
    trains, the IVF path, the burst."""
    from surrealdb_tpu import bg, compile_log
    from surrealdb_tpu.idx.ivf import default_nprobe

    ds, n = srv.ds, corpus.shape[0]
    t_phase = time.time()
    secs = load_items(
        ds, "item", f"HNSW DIMENSION {DIM} DIST EUCLIDEAN EFC {EF}", corpus
    )
    emit("ingest_item", rows=n, seconds=secs, rows_per_s=n / secs)

    # straight after ingest: the first statement builds + uploads the
    # mirror (minutes at 1M rows), compiles the exact kernel and kicks IVF
    # training; until the quantizer is ready the statements are served
    # exact over ALL rows
    window_q = gen_queries(corpus, 9, rng)
    c0 = wire.counters()
    ids, first_s = ask_knn(wire, "item", window_q[0], "rpc")
    got, truth_rows = [ids], [0]
    before = wire.counters()
    for i in range(1, window_q.shape[0]):
        ids, _ = ask_knn(wire, "item", window_q[i], "rpc")
        after = wire.counters()
        if not all(s.startswith("exact-") for s in knn_strategies(after, before)):
            break  # the quantizer is ready: the window has closed
        got.append(ids)
        truth_rows.append(i)
        before = after
    window_recall = recall_at_k(got, exact_topk(corpus, window_q[truth_rows], K))
    emit(
        "exact_window_item",
        first_query_s=first_s,
        exact_served=len(got),
        strategies=knn_strategies(wire.counters(), c0),
        recall_at_10=window_recall,
        **_durations(("vector_mirror_build", "vector_mirror_cast", "vector_mirror_upload")),
        compile_s=_compile_seconds(compile_log.events(since=t_phase)),
    )
    check_recall("exact window (bf16 corpus, all rows)", window_recall, 0.95)

    mirror = ds.index_stores.get(NS, DB, "item", "item_emb")
    t0 = time.perf_counter()
    if not mirror.wait_ivf(WAIT_S):
        raise SmokeFailure(f"IVF training did not finish in {WAIT_S:.0f}s")
    waited = time.perf_counter() - t0
    ivf = mirror.ivf
    train = [
        t for t in bg.snapshot()["recent"] if t["kind"] == "ivf_train"
    ]
    emit(
        "ivf_training",
        seconds=train[0]["duration_s"] if train else None,
        waited_s=waited,
        nlists=ivf.nlists,
        longest_list=max(len(l) for l in ivf.lists),
        nprobe=default_nprobe(ivf.nlists, EF),
    )

    t_ivf = time.time()
    qs = gen_queries(corpus, warm, rng)
    _, first_s = ask_knn(wire, "item", qs[0], "rpc")
    # the other tile widths compile in the background (ivf._warm_tiles):
    # join them so the burst below starts on compiled shapes
    t0 = time.perf_counter()
    if not bg.wait_idle(WAIT_S, owner=id(ds)):
        raise SmokeFailure(f"shape warmers still running after {WAIT_S:.0f}s")
    warm_wait = time.perf_counter() - t0
    got, lat = warm_knn(wire, "item", qs)
    truth = exact_topk(corpus, qs, K)
    host_recall = host_twin_recall(mirror, qs, truth)
    recalls = {t: recall_at_k(got[t], truth) for t in got}
    emit(
        "ivf_item",
        first_query_s=first_s,
        shape_warm_wait_s=warm_wait,
        warm=lat,
        recall_at_10=recalls,
        host_twin_recall_at_10=host_recall,
        compile_s=_compile_seconds(compile_log.events(since=t_ivf)),
    )
    for t, r in recalls.items():
        check_ivf_recall(f"IVF path via {t}", r, host_recall)

    w0 = ds.dispatch.width_distribution()
    d0 = ds.dispatch.stats()
    bqs = gen_queries(corpus, clients * 4, rng)
    burst = burst_knn(srv, "item", bqs, clients)
    w1, d1 = ds.dispatch.width_distribution(), ds.dispatch.stats()
    widths = {w: c - w0.get(w, 0) for w, c in sorted(w1.items()) if c - w0.get(w, 0)}
    truth = exact_topk(corpus, bqs, K)
    burst_recall = recall_at_k(burst["ids"], truth)
    host_recall = host_twin_recall(mirror, bqs, truth)
    emit(
        "burst_item",
        clients=clients,
        queries=len(burst["ids"]),
        wall_s=burst["wall_s"],
        qps=len(burst["ids"]) / burst["wall_s"],
        latency=burst["latency"],
        widths={str(w): c for w, c in widths.items()},
        dispatch={k: d1[k] - d0[k] for k in ("submitted", "dispatches", "retries", "splits", "failures")},
        recall_at_10=burst_recall,
        host_twin_recall_at_10=host_recall,
    )
    if max(widths, default=0) <= 1:
        raise SmokeFailure(f"the burst never coalesced: widths {widths}")
    check_ivf_recall("IVF path in the burst", burst_recall, host_recall)


def phase_exact(srv, wire, emit, corpus, rng, warm: int) -> None:
    """`item_exact` (MTREE: always the exact fused kernel)."""
    from surrealdb_tpu import compile_log

    n = corpus.shape[0]
    t_phase = time.time()
    secs = load_items(srv.ds, "item_exact", f"MTREE DIMENSION {DIM} DIST EUCLIDEAN", corpus)
    emit("ingest_item_exact", rows=n, seconds=secs, rows_per_s=n / secs)
    qs = gen_queries(corpus, warm, rng)
    _, first_s = ask_knn(wire, "item_exact", qs[0], "rpc")
    got, lat = warm_knn(wire, "item_exact", qs)
    truth = exact_topk(corpus, qs, K)
    recalls = {t: recall_at_k(got[t], truth) for t in got}
    emit(
        "exact_item_exact",
        first_query_s=first_s,
        warm=lat,
        recall_at_10=recalls,
        compile_s=_compile_seconds(compile_log.events(since=t_phase)),
    )
    for t, r in recalls.items():
        check_recall(f"exact path via {t}", r, 0.95)


def phase_graph(srv, wire, emit, nodes: int, pairs: np.ndarray, rng, warm: int) -> None:
    from surrealdb_tpu import compile_log

    ds = srv.ds
    t_phase = time.time()
    t_nodes, t_edges = load_graph(ds, nodes, pairs)
    emit(
        "ingest_graph",
        nodes=nodes, node_seconds=t_nodes, nodes_per_s=nodes / t_nodes,
        edges=pairs.shape[0], edge_seconds=t_edges,
        edges_per_s=pairs.shape[0] / t_edges,
    )
    # the ingest-armed mirror build + count-kernel prewarm (idx/graph_csr.py)
    t0 = time.perf_counter()
    if not ds.graph_mirrors.wait_prewarm(WAIT_S):
        raise SmokeFailure(f"graph prewarm still running after {WAIT_S:.0f}s")
    prewarm_s = time.perf_counter() - t0
    seeds = rng.integers(0, nodes, size=8).tolist()
    want = path_counts(pairs, nodes, seeds)

    # single stream: what `submitted` grows by across one statement is that
    # statement's own device work
    asked, dispatched = {3: 0, 1: 0}, {3: 0, 1: 0}

    def timed_count(seed: int, hops: int, transport: str) -> float:
        s0 = ds.dispatch.stats()["submitted"]
        c, dt = ask_count(wire, seed, hops, transport)
        asked[hops] += 1
        dispatched[hops] += ds.dispatch.stats()["submitted"] - s0
        if c != want[seed][hops]:
            raise SmokeFailure(
                f"{hops}-hop count from person:{seed} via {transport} is {c}, "
                f"NumPy says {want[seed][hops]}"
            )
        return dt

    first = {hops: timed_count(seeds[0], hops, "sql") for hops in (3, 1)}
    lat = {
        f"{hops}hop_{transport}": quartiles(
            [timed_count(seeds[i % len(seeds)], hops, transport) for i in range(warm)]
        )
        for hops in (3, 1)
        for transport in ("rpc", "sql")
    }
    emit(
        "graph",
        prewarm_wait_s=prewarm_s,
        first_query_s={f"{h}hop": s for h, s in first.items()},
        warm=lat,
        statements={f"{h}hop": n for h, n in asked.items()},
        dispatched={f"{h}hop": n for h, n in dispatched.items()},
        counts={str(s): {"1hop": want[s][1], "3hop": want[s][3]} for s in seeds},
        compile_s=_compile_seconds(compile_log.events(since=t_phase)),
    )
    check_counts_dispatched(asked[3], dispatched[3])


def run(seed: int, sizes: Sizes, info: dict) -> dict:
    """Every phase, in order, then the engine's own account of the run
    (`observe`), which is returned. Raises SmokeFailure on the first
    failed check; the checks only a chip can pass are main()'s."""
    from surrealdb_tpu.net.server import serve

    def emit(phase: str, **fields) -> None:
        fields["host_rss_bytes"] = host_rss_bytes()
        print(json.dumps({"phase": phase, **info, **fields}), flush=True)

    t_start = time.time()
    rng = np.random.default_rng(seed)
    srv = serve("memory", port=0, auth_enabled=False).start_background()
    wire = Wire(srv)
    try:
        emit("rtt", **measure_rtt())
        metrics0 = wire.counters()
        t0 = time.perf_counter()
        corpus = gen_corpus(sizes.rows, seed)
        pairs = rng.integers(0, sizes.nodes, size=(sizes.edges, 2))
        emit("generate", seed=seed, **sizes.stated(), seconds=time.perf_counter() - t0)
        phase_items(srv, wire, emit, corpus, rng, sizes.warm, sizes.clients)
        # the full corpus has done its work as a reference: give its 3 GB
        # back before the next tables load
        exact_corpus = corpus[: sizes.exact_rows].copy()
        del corpus
        phase_exact(srv, wire, emit, exact_corpus, rng, sizes.warm)
        phase_graph(srv, wire, emit, sizes.nodes, pairs, rng, sizes.warm)
        obs = observe(wire, srv.ds, metrics0, t_start)
        emit(
            "engine",
            **obs,
            peak_host_rss_bytes=resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024,
        )
        check_engine(obs)
    finally:
        wire.close()
        srv.shutdown()
        srv.ds.close()
    return obs


def ok_line(d: dict) -> str:
    """The last line of stdout: `ok` and `device` and nothing else, the
    device as `device.describe()` read it off `jax.devices()`."""
    return json.dumps({
        "ok": True,
        "device": {
            "platform": d["platform"], "kind": d["device_kind"],
            "count": d["device_count"],
        },
    })


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--rows", type=int, default=ROWS,
        help=f"item rows: a cut the time limit forces, never below {MIN_ROWS} on the chip",
    )
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax

    from surrealdb_tpu import device

    sizes = Sizes(rows=args.rows)
    try:
        require_tpu(jax.default_backend(), sizes.rows)
        d = device.describe()
        t0 = time.perf_counter()
        obs = run(args.seed, sizes, d)
        check_device_memory(obs, sizes.rows * DIM * 2)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    seconds = time.perf_counter() - t0
    print(f"chip_smoke: all phases passed in {seconds:.1f}s", file=sys.stderr)
    print(json.dumps({"phase": "passed", **d, "size": sizes.stated(), "seconds": seconds}))
    print(ok_line(d), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
