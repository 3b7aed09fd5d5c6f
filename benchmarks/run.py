#!/usr/bin/env python3
"""One cell of the benchmark, once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts the server in this process (which holds the chip), makes the data from
`--seed`, loads it through `ds.execute()`, asks the first statement, waits
for the background work, warms up with the cell's own traffic until nothing
has compiled for a while, measures for `--seconds`, checks every answer of
the window against the plain reference, and prints one JSON object as the
last line of stdout. Phase lines go before it, one JSON object each.

The clients are child processes (`harness/loadgen.py`) that never import JAX.
Nothing here branches on the name of a workload, a configuration or a mix:
`BENCHMARK.json` names them, and the files under `configs/`, `traffic/`,
`deployments/`, `layer_metrics/` and `kernels/` are found by those names.
On any backend but a TPU with the chips the cell asks for, `main()` exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up counts from here: imports and libtpu's start are part of it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import device, manifest as mf, stats, trace_reduce  # noqa: E402

WAIT_S = 900.0  # longest wait for one background phase
DEFAULT_TUNING = {
    "quiet_s": 5.0,  # the window opens when nothing has compiled for this long
    "warm_min_s": 5.0,
    "warm_max_s": 240.0,
    "slice_delay_s": 3.0,  # a traced run profiles [delay, delay + slice_s) of the window
    "slice_s": 4.0,
    "tags": 300,  # tagged requests a traced run aims for (the program's trace store keeps 512)
}


class Refused(Exception):
    """The run cannot produce a result (bad manifest, failed set-up)."""


def execute_ok(ds, sql: str, vars, ns: str, db: str) -> list:
    from surrealdb_tpu.dbs.session import Session

    out = ds.execute(sql, Session.owner(ns, db), vars=vars)
    for r in out:
        if r.get("status") != "OK":
            raise Refused(f"status {r.get('status')!r} for {sql[:80]}: {str(r.get('result'))[:300]}")
    return out


def bare_round_trip_ms(n: int = 50) -> float:
    """Median of `n` bare jitted dispatch + fetch round trips: the floor
    under every statement that reaches the device."""
    import jax
    import jax.numpy as jnp

    x = jax.device_put(jnp.ones((8, 8)))
    f = jax.jit(lambda a: (a @ a).sum())
    float(f(x))
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        float(f(x))
        ts.append((time.perf_counter() - t0) * 1e3)
    return stats.median(ts)


def strategy_counters(srv) -> dict:
    """{strategy: statements served} of GET /metrics' `knn_strategy` counter."""
    import http.client

    conn = http.client.HTTPConnection(srv.host, srv.port, timeout=60)
    try:
        conn.request("GET", "/metrics", headers={"Accept": "text/plain"})
        text = conn.getresponse().read().decode()
    finally:
        conn.close()
    out = {}
    for line in text.splitlines():
        if line.startswith("surreal_knn_strategy_total{"):
            name, _, val = line.rpartition(" ")
            out[name.split('strategy="', 1)[1].split('"', 1)[0]] = float(val)
    return out


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after if after[k] != before.get(k, 0)}


class Clients:
    """The mix's client processes: started, told to tag or to stop, read back."""

    def __init__(self, srv, cfg: dict, mix: dict, pool_file: str, seed: int, workdir: str):
        self.procs, self.outs = [], []
        per = -(-mix["clients"] // mix["processes"])
        ids = list(range(mix["clients"]))
        statements = []
        for st in mix["statements"]:
            s = cfg["statements"][st["name"]]
            statements.append(
                {"name": st["name"], "weight": st["weight"], "sql": s["sql"], "bind": s["bind"],
                 "pool": pool_file}
            )
        for p in range(mix["processes"]):
            mine = ids[p * per : (p + 1) * per]
            if not mine:
                continue
            out = os.path.join(workdir, f"records_{p}.json")
            spec = os.path.join(workdir, f"spec_{p}.json")
            with open(spec, "w") as f:
                json.dump(
                    {"url": f"ws://{srv.host}:{srv.port}/rpc", "ns": cfg["ns"], "db": cfg["db"],
                     "seed": seed, "client_ids": mine, "think_time_s": mix["think_time_s"],
                     "statements": statements, "out": out}, f,
                )
            self.outs.append(out)
            self.procs.append(
                subprocess.Popen(
                    [sys.executable, os.path.join(BENCH_DIR, "harness", "loadgen.py"), spec],
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
                )
            )
        for p in self.procs:
            if p.stdout.readline().strip() != "started":
                self.kill()
                raise Refused("a load generator did not start")

    def tell(self, line: str) -> None:
        for p in self.procs:
            p.stdin.write(line + "\n")
            p.stdin.flush()

    def stop(self) -> list:
        """Stop every client, wait for its process, return all records."""
        self.tell("stop")
        records = []
        for p, out in zip(self.procs, self.outs):
            try:
                rc = p.wait(timeout=120)
            except subprocess.TimeoutExpired:
                self.kill()
                raise Refused("a load generator did not stop")
            if rc != 0:
                raise Refused(f"a load generator exited with code {rc}")
            with open(out) as f:
                got = json.load(f)
            if got["errors"] or got["jax_imported"]:
                raise Refused(f"load generator: errors {got['errors']}, jax imported: {got['jax_imported']}")
            records += got["records"]
        self.procs = []
        return records

    def kill(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
            p.wait()
        self.procs = []


def profile_slice(workdir: str, ds, slice_s: float) -> dict:
    """Trace `slice_s` seconds with the JAX profiler. The two annotations put
    the slice's ends on the trace's clock; the wall clock and the dispatch
    counters are read at the same instants."""
    import jax

    trace_dir = os.path.join(workdir, "trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.BEGIN):
            wall0, d0 = time.time(), ds.dispatch.stats()
        time.sleep(slice_s)
        with jax.profiler.TraceAnnotation(trace_reduce.END):
            d1 = ds.dispatch.stats()
    finally:
        jax.profiler.stop_trace()
    return {"dir": trace_dir, "wall0": wall0, "dispatch": {k: d1[k] - d0[k] for k in d1}}


def tagged_spans(tagged: list, wall0: float) -> list:
    """(name, start_s, end_s) of every span of the tagged requests, in
    seconds from `wall0` (a trace doc's `ts` is the wall clock at its start)."""
    out = []
    for t in tagged:
        base = t["doc"]["ts"] - wall0
        for s in t["doc"]["spans"]:
            start = base + s["start_ms"] / 1e3
            out.append((s["name"], start, start + s["dur_ms"] / 1e3))
    return out


def run(manifest: dict, workload: str, seed: int, seconds: float, trace: bool, dev: dict,
        sizes: dict = None, tuning: dict = None, bench_dir: str = BENCH_DIR,
        keep: str = None) -> dict:
    """Everything but the look for a chip; returns the last line's object.
    `sizes` replaces the configuration's own (the CPU rehearsal's tiny ones,
    handed in by a test), `tuning` the warm-up and slice timings; a traced run
    leaves the plain form of its profiler trace in the directory `keep` (how
    `fixtures/` was made; no flag of the command sets it)."""
    tune = {**DEFAULT_TUNING, **(tuning or {})}
    cell = mf.cell(manifest, workload)
    cfg = mf.load_json(bench_dir, "configs", cell["config"])
    mix = mf.load_json(bench_dir, "traffic", cell["traffic"])
    dep = mf.load_modules(bench_dir, "deployments", "KIND")[cfg["kind"]]
    kernel = mf.load_modules(bench_dir, "kernels", None)[cfg["kernel"]]
    readers = mf.load_modules(bench_dir, "layer_metrics", "NAME")
    sizes = sizes or cfg["sizes"]
    info = {"platform": dev["platform"], "device_kind": dev["kind"], "device_count": dev["count"]}

    def emit(phase: str, **fields) -> None:
        print(json.dumps({"phase": phase, **info, **fields}), flush=True)

    workdir = tempfile.mkdtemp(prefix="bench_run_")
    try:
        return _run(manifest, workload, seed, seconds, trace, dev, sizes, tune, workdir,
                    cfg, mix, dep, kernel, readers, emit, keep)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _run(manifest, workload, seed, seconds, trace, dev, sizes, tune, workdir,
         cfg, mix, dep, kernel, readers, emit, keep) -> dict:
    from surrealdb_tpu import Surreal, bg, compile_log, tracing
    from surrealdb_tpu.net.server import serve

    srv = clients = client = None
    phases = {}
    try:
        emit("rtt", p50_ms=bare_round_trip_ms(), cpu_count=os.cpu_count(),
             client_processes=mix["processes"], clients=mix["clients"])
        t0 = time.perf_counter()
        data = dep.generate(cfg, sizes, seed)
        emit("generate", workload=workload, seed=seed, sizes=sizes, seconds=time.perf_counter() - t0)
        t0 = time.perf_counter()
        ref = dep.reference(cfg, data)
        reference_s = time.perf_counter() - t0
        emit("reference", seconds=reference_s)
        pool = dep.pool(cfg, data)
        pool_file = os.path.join(workdir, "pool.json")
        with open(pool_file, "w") as f:
            json.dump(pool, f)

        srv = serve("memory", port=0, auth_enabled=False).start_background()
        ds = srv.ds
        loaded = dep.load(ds, cfg, data, lambda d, sql, vars=None: execute_ok(d, sql, vars, cfg["ns"], cfg["db"]))
        t0 = time.perf_counter()
        read_back = sum(
            int(execute_ok(ds, sql, vars, cfg["ns"], cfg["db"])[-1]["result"][0]["c"])
            for sql, vars in dep.count_sql(cfg)
        )
        phases.update(loaded, read_back=read_back)
        emit("ingest", **loaded, per_s=loaded["acknowledged"] / loaded["insert_s"],
             read_back=read_back, read_back_s=time.perf_counter() - t0)
        dep.release(data)

        # the first statement over the wire: it builds the mirror / composes the operator
        client = Surreal(f"ws://{srv.host}:{srv.port}/rpc")
        client.use(cfg["ns"], cfg["db"])
        first = cfg["statements"][mix["statements"][0]["name"]]
        t0 = time.perf_counter()
        if first["bind"] == "inline":
            rows = client.query(first["sql"].replace("{arg}", str(pool[0])))
        else:
            rows = client.query(first["sql"], {first["bind"]: pool[0]})
        phases["first_stmt_s"] = time.perf_counter() - t0
        if any(r.get("status") != "OK" for r in rows):
            raise Refused(f"the first statement failed: {str(rows)[:300]}")
        emit("first_stmt", seconds=phases["first_stmt_s"])

        t0 = time.perf_counter()
        background = dep.wait_background(ds, cfg, WAIT_S)
        emit("background", seconds=time.perf_counter() - t0, **background["line"])
        shapes = dep.kernel_shapes(cfg, data, background["state"])

        # warm up with the cell's own traffic until nothing has compiled for quiet_s
        total0 = ds.dispatch.stats()
        clients = Clients(srv, cfg, mix, pool_file, seed, workdir)
        t_warm = time.perf_counter()
        while True:
            time.sleep(0.25)
            now, events = time.perf_counter(), compile_log.events()
            last = max((e["ts"] for e in events), default=0.0)
            quiet = time.time() - last
            if now - t_warm >= tune["warm_min_s"] and quiet >= tune["quiet_s"] and bg.wait_idle(0.01, owner=id(ds)):
                break
            if now - t_warm > tune["warm_max_s"]:
                raise Refused(f"still compiling after {tune['warm_max_s']:.0f}s of warm-up")
        emit("warm", seconds=time.perf_counter() - t_warm, compile_events=len(compile_log.events()))

        # ------------------------------------------------------------ window
        strat0, d0, w0 = strategy_counters(srv), ds.dispatch.stats(), ds.dispatch.width_distribution()
        t_open, wall_open = time.perf_counter(), time.time()
        sliced = None
        if trace:
            clients.tell(f"tag {seconds * mix['clients'] / tune['tags']:.6f}")
            time.sleep(min(tune["slice_delay_s"], max(seconds - tune["slice_s"], 0.0) / 2))
            sliced = profile_slice(workdir, ds, min(tune["slice_s"], seconds / 2))
        time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
        t_close, wall_close = time.perf_counter(), time.time()
        d1, w1, strat1 = ds.dispatch.stats(), ds.dispatch.width_distribution(), strategy_counters(srv)
        docs = {tid: tracing.get_trace(tid) for tid in tracing.trace_ids()} if trace else {}
        records = clients.stop()
        total1 = ds.dispatch.stats()
        clients = None
        compiles = [e for e in compile_log.events(since=wall_open) if e["ts"] <= wall_close]
        memory_peak = device.memory_peak_bytes()
        setup_s = (t_open - T_START) - reference_s
    finally:
        if clients is not None:
            clients.kill()
        if client is not None:
            client.close()
        if srv is not None:
            srv.shutdown()
            srv.ds.close()

    # ------------------------------------------------------------ reduce
    window = [r for r in records if t_open <= r["t1"] <= t_close]
    ok = [r for r in window if r["status"] == "OK"]
    lat = stats.latency_summary([r["t1"] - r["t0"] for r in ok]) if ok else None
    dwin = {k: d1[k] - d0[k] for k in d1}
    widths = {str(w): n - w0.get(w, 0) for w, n in sorted(w1.items()) if n != w0.get(w, 0)}
    served = delta(strat1, strat0)
    ends = [t_open] + sorted(r["t1"] for r in window) + [t_close]
    gap, gap_at = max((b - a, a - t_open) for a, b in zip(ends, ends[1:]))
    emit("window", seconds=t_close - t_open, completed=len(window), ok=len(ok),
         latency=lat, dispatch=dwin, widths=widths, strategies=served,
         all_requests=len(records), setup_s=setup_s,
         longest_gap_s=gap, longest_gap_at_s=gap_at)  # a stall shows here and in no percentile

    verdict = dep.check(cfg, ref, window)
    numbers = list(verdict["numbers"])
    numbers += [
        ["requests_in_window", len(window), ">=", 1],
        ["failed_requests", len(window) - len(ok), "<=", 0],
        ["unexpected_strategies",
         sum(n for s, n in served.items() if s not in cfg["expected_strategies"]), "<=", 0],
        ["dispatch_retries", dwin["retries"], "<=", 0],
        ["dispatch_splits", dwin["splits"], "<=", 0],
        ["dispatch_failures", dwin["failures"], "<=", 0],
        ["compiles_in_window", len(compiles), "<=", 0],
        ["rows_not_read_back", abs(phases["read_back"] - phases["acknowledged"]), "<=", 0],
        ["statements_not_dispatched",
         abs(sum(cfg["statements"][r["s"]]["dispatches"] for r in records)
             - (total1["submitted"] - total0["submitted"])), "<=", 0],
    ]
    checked = [
        {"name": n, "value": v, "relation": rel, "limit": lim,
         "ok": bool(v >= lim) if rel == ">=" else bool(v <= lim)}
        for n, v, rel, lim in numbers
    ]
    correct = all(c["ok"] for c in checked)
    emit("check", correct=correct, numbers=checked, control=verdict["control"],
         compared=verdict["compared"],
         compiles=[f"{e['subsystem']}[{e['shape']}]({e['mode']})" for e in compiles])

    e2e = {
        "setup_s": setup_s,
        "stmt_per_s": len(ok) / (t_close - t_open),
        "p50_ms": lat["p50_ms"] if lat else None,
        "p95_ms": lat["p95_ms"] if lat else None,
        **verdict["metrics"],
    }
    dev_line = {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
                "memory_peak_bytes": memory_peak}
    line = {"correct": correct, "attempted": len(window), "failed": len(window) - len(ok)}
    if not trace:
        wanted = mf.metrics_of(manifest, "end_to_end", workload)
        line["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in wanted if e2e.get(m["name"]) is not None
        }
        line["device"] = dev_line
        return line

    # ------------------------------------------------------------ traced run
    by_tid = {r["trace"]: r for r in window if r.get("trace")}
    tagged = [{"record": by_tid[tid], "doc": doc} for tid, doc in docs.items()
              if tid in by_tid and doc is not None]
    reduced = None
    path = trace_reduce.find_xplane(sliced["dir"])
    if path is not None:
        ir = trace_reduce.load_xplane(path)
        reduced = trace_reduce.reduce(ir, kernel.MODULE)
        if keep:
            os.makedirs(keep, exist_ok=True)
            with open(os.path.join(keep, f"trace_{workload}_{seed}.json"), "w") as f:
                json.dump(ir, f)
    ctx = {
        "cfg": cfg, "mix": mix, "phases": phases, "tagged": tagged,
        "window": {"records": window, "dispatch": dwin, "widths": widths},
        "slice": {"reduced": reduced, "dispatch": sliced["dispatch"]} if reduced else None,
        "kernel": {"name": cfg["kernel"], "need": kernel.need, "shapes": shapes},
        "peaks": device.peaks(dev["kind"]) if reduced else None,
    }
    values = {}
    for m in mf.metrics_of(manifest, "per_layer", workload):
        v = readers[m["name"]].read(ctx)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    line["metrics"] = values
    line["device"] = dev_line
    emit("traced", tagged=len(tagged), kernel_shapes=shapes,
         slice_dispatch=sliced["dispatch"],
         modules=reduced["modules"] if reduced else None, end_to_end_traced=e2e)
    if reduced:
        dev_line["busy_s"], dev_line["window_s"] = reduced["busy_s"], reduced["window_s"]
        line["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": trace_reduce.attribute_gaps(
                reduced["gaps"], tagged_spans(tagged, sliced["wall0"])),
        }
    return line


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        manifest = mf.load(ROOT)
        bad = mf.problems(manifest, BENCH_DIR)
        if bad:
            raise Refused("BENCHMARK.json: " + "; ".join(bad))
        if args.workload not in [w["name"] for w in manifest["workloads"]]:
            raise Refused(f"no workload named {args.workload!r} in BENCHMARK.json")
        cell = mf.cell(manifest, args.workload)
        dev = device.describe()
        device.require_chips(dev, cell["chips"])
        line = run(manifest, args.workload, args.seed, args.seconds, bool(args.trace), dev)
    except (Refused, device.NoChip) as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
