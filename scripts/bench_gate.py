#!/usr/bin/env python
"""Opt-in perf gate: smoke-scale concurrent-kNN and filtered-SELECT floors.

Runs bench.py with configs 2 and 6 (the north-star concurrent-kNN pass and
the columnar filtered-SELECT scan) at a smoke scale, then FAILS if:
  - config 2 shows any errors, concurrent qps below the committed floor,
    or recall@10 below its floor (the round-5 collapse signatures);
  - config 6 shows columnar output diverging from the row path, columnar
    qps below its floor, or a columnar/row speedup below the ratio floor
    (the columnar scan path regressing back to per-row work).
Post-ingest statements over 5s are surfaced as a WARNING only: on
accelerator-less CI containers jax-CPU compiles land mid-window and would
trip a hard gate without any engine defect (inspect slowest_trace).

Not part of tier-1 (it is a perf measurement, not a correctness suite):
run it next to scripts/tier1.sh when touching the dispatch/kNN/scan path:

    python scripts/bench_gate.py

Env knobs:
    SURREAL_BENCH_GATE_SCALE       corpus scale for the smoke run (default 0.02)
    SURREAL_BENCH_GATE_FLOOR       concurrent-kNN qps floor (default 3.0 — half
                                   the worst rate measured on the 2-core CI
                                   container; real hardware clears it by 10x+)
    SURREAL_BENCH_GATE_RECALL      recall@10 floor (default 0.6 at smoke scale;
                                   tiny corpora probe fewer clustered lists)
    SURREAL_BENCH_GATE_SCAN_FLOOR  filtered-SELECT columnar qps floor
                                   (default 20.0)
    SURREAL_BENCH_GATE_SCAN_RATIO  columnar vs row-path speedup floor
                                   (default 5.0 — the ISSUE 4 acceptance bar)
    SURREAL_BENCH_GATE_INGEST_FLOOR  bulk-load ingest_rate_rows_s floor
                                   (run-cumulative engine-path rate;
                                   default 5000.0 — half the ~11-13k rows/s
                                   the 2-core CI container sustains on the
                                   vector-indexed item corpus)
    SURREAL_BENCH_GATE_INGEST_RATIO  sustained mirrored-table delta-feed vs
                                   r10-rescan speedup floor (default 5.0 —
                                   the ISSUE 8 acceptance bar; measured
                                   ~20-30x at smoke scale)
    SURREAL_BENCH_GATE_CHAOS_ERRORS  config-8 chaos-window error ceiling
                                   (default 3; zero wrong answers is a
                                   hard rule regardless — the ISSUE 9 bar)
    SURREAL_BENCH_GATE_PROFILER_OVERHEAD  sampling-profiler overhead ceiling
                                   in percent on the config-2 engine path
                                   (default 3.0 — the always-on contract)
    SURREAL_BENCH_GATE_ADVISOR_OVERHEAD  advisor-sweep overhead ceiling in
                                   percent on the config-2 engine path
                                   (default 3.0 — same contract)
    SURREAL_BENCH_GATE_PLAN_CACHE_HIT  config-2 plan-cache warm hit-rate
                                   floor on the parity battery (default 0.9)
    SURREAL_BENCH_GATE_PLAN_CACHE_WARM_RATIO  warm/cold pre-kernel cost
                                   ceiling (default 0.7 — looser than the
                                   committed artifact's >=2x bar because
                                   the gate re-measures µs-scale parse
                                   timings on whatever container it runs
                                   on; tighten via the env knob)
    SURREAL_BENCH_GATE_NET_VICTIM_RATIO  config-13 victim-tenant contended
                                   p99 ceiling as a multiple of its solo
                                   p99 (default 3.0 — the C1M QoS
                                   isolation bar: an abusive tenant's
                                   flood may cost the victim at most 3x)
    SURREAL_BENCH_GATE_TIMEOUT     whole-run timeout seconds (default 1200)

Exit code 0 = gate passed; 1 = gate failed (reasons on stderr).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

SCALE = os.environ.get("SURREAL_BENCH_GATE_SCALE", "0.02")
FLOOR_QPS = float(os.environ.get("SURREAL_BENCH_GATE_FLOOR", "3.0"))
FLOOR_RECALL = float(os.environ.get("SURREAL_BENCH_GATE_RECALL", "0.6"))
FLOOR_SCAN_QPS = float(os.environ.get("SURREAL_BENCH_GATE_SCAN_FLOOR", "20.0"))
FLOOR_SCAN_RATIO = float(os.environ.get("SURREAL_BENCH_GATE_SCAN_RATIO", "5.0"))
FLOOR_INGEST = float(os.environ.get("SURREAL_BENCH_GATE_INGEST_FLOOR", "5000.0"))
FLOOR_INGEST_RATIO = float(os.environ.get("SURREAL_BENCH_GATE_INGEST_RATIO", "5.0"))
CHAOS_MAX_ERRORS = int(os.environ.get("SURREAL_BENCH_GATE_CHAOS_ERRORS", "3"))
# elastic window (config 10): error ceiling during the kill+join window and
# the repair-time ceiling — kill -> replacement-converged must stay bounded
# (zero wrong answers / zero lost acked writes are validator rules already)
ELASTIC_MAX_ERRORS = int(os.environ.get("SURREAL_BENCH_GATE_ELASTIC_ERRORS", "4"))
REPAIR_CEILING_S = float(os.environ.get("SURREAL_BENCH_GATE_REPAIR_CEILING", "60.0"))
# vectorized SELECT pipeline (config 9): ORDER BY+LIMIT and GROUP BY
# aggregate columnar/row speedup floor (the ISSUE 13 acceptance bar)
FLOOR_PIPE_RATIO = float(os.environ.get("SURREAL_BENCH_GATE_PIPE_RATIO", "5.0"))
# workload statistics plane (schema/12): the always-on sampling profiler's
# measured overhead on the config-2 engine path must stay under this
# ceiling (percent; the ISSUE 15 <=3% contract — bench.py reports the
# noise-cancelling paired minimum, see _profiler_overhead)
PROFILER_OVERHEAD_CEILING = float(
    os.environ.get("SURREAL_BENCH_GATE_PROFILER_OVERHEAD", "3.0")
)
# tenant cost-attribution plane (schema/13): the per-statement metering's
# measured overhead on the config-2 engine path must stay under this
# ceiling (percent; the ISSUE 16 <=3% contract — same paired-minimum
# estimator, see bench.py _accounting_overhead)
ACCOUNTING_OVERHEAD_CEILING = float(
    os.environ.get("SURREAL_BENCH_GATE_ACCOUNTING_OVERHEAD", "3.0")
)
# advisor plane (schema/14): the sweep service's measured overhead on the
# config-2 engine path must stay under this ceiling (percent; the ISSUE
# 17 <=3% contract — same paired-minimum estimator, measured at a
# deliberately hostile 0.25s sweep interval, see bench.py
# _advisor_overhead)
ADVISOR_OVERHEAD_CEILING = float(
    os.environ.get("SURREAL_BENCH_GATE_ADVISOR_OVERHEAD", "3.0")
)
# plan cache (schema/15): the config-2 warm window must actually serve —
# hit-rate floor on the parity battery — and a warm serve's pre-kernel
# (parse+plan) cost must stay under this fraction of the cold parse's
# (the >=2x speedup acceptance bar, expressed as a <=0.5x cost ratio)
PLAN_CACHE_HIT_FLOOR = float(
    os.environ.get("SURREAL_BENCH_GATE_PLAN_CACHE_HIT", "0.9")
)
PLAN_CACHE_WARM_COST_RATIO = float(
    os.environ.get("SURREAL_BENCH_GATE_PLAN_CACHE_WARM_RATIO", "0.7")
)
# C1M network plane (schema/16): the victim tenant's p99 under an abusive
# tenant's flood must stay within this multiple of its solo p99, the
# active burst must complete error-free, and the abuser's overflow must
# have been shed (pushed back on, not buffered)
NET_VICTIM_RATIO = float(
    os.environ.get("SURREAL_BENCH_GATE_NET_VICTIM_RATIO", "3.0")
)
TIMEOUT = int(os.environ.get("SURREAL_BENCH_GATE_TIMEOUT", "1200"))


def main() -> int:
    out = os.path.join(tempfile.mkdtemp(prefix="bench_gate_"), "bench_gate.json")
    env = dict(os.environ)
    env.update(
        {
            "SURREAL_BENCH_SCALE": SCALE,
            "SURREAL_BENCH_CONFIGS": "2,6,8,9,10,13",
            "SURREAL_BENCH_ROUND": "gate",
            "SURREAL_BENCH_OUT": out,
        }
    )
    print(
        f"bench_gate: scale={SCALE} floor={FLOOR_QPS}qps recall>={FLOOR_RECALL} "
        f"scan>={FLOOR_SCAN_QPS}qps scan_ratio>={FLOOR_SCAN_RATIO}x"
    )
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            env=env,
            timeout=TIMEOUT,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
    except subprocess.TimeoutExpired:
        print(f"bench_gate: FAIL — bench run exceeded {TIMEOUT}s", file=sys.stderr)
        return 1
    tail = proc.stdout.decode(errors="replace")[-4000:]
    if proc.returncode != 0:
        print(tail, file=sys.stderr)
        print(f"bench_gate: FAIL — bench exited rc={proc.returncode}", file=sys.stderr)
        return 1

    sys.path.insert(0, HERE)
    from check_bench_artifact import validate

    problems = validate(out)
    if problems:
        for p in problems:
            print(f"bench_gate: artifact invalid: {p}", file=sys.stderr)
        return 1

    with open(out) as f:
        art = json.load(f)
    line = next(
        (
            r
            for r in art["results"]
            if str(r.get("config")) == "2" and str(r.get("metric", "")).startswith("knn_qps")
        ),
        None,
    )
    if line is None:
        print("bench_gate: FAIL — no config-2 knn_qps line in artifact", file=sys.stderr)
        return 1

    failures = []
    errs = line.get("errors") or {}
    if any(errs.values()):
        failures.append(f"errors != 0: {errs}")
    qps = line.get("value") or 0.0
    if qps < FLOOR_QPS:
        failures.append(f"concurrent kNN qps {qps} < floor {FLOOR_QPS}")
    recall = line.get("recall_at_10")
    if recall is not None and recall < FLOOR_RECALL:
        failures.append(f"recall@10 {recall} < floor {FLOOR_RECALL}")
    po = line.get("profiler_overhead") or {}
    overhead = po.get("overhead_pct")
    if overhead is None:
        failures.append("config 2 carries no profiler_overhead measurement")
    elif overhead > PROFILER_OVERHEAD_CEILING:
        failures.append(
            f"sampling-profiler overhead {overhead}% > ceiling "
            f"{PROFILER_OVERHEAD_CEILING}% (the always-on contract)"
        )
    ao = line.get("accounting_overhead") or {}
    acct_overhead = ao.get("overhead_pct")
    if acct_overhead is None:
        failures.append("config 2 carries no accounting_overhead measurement")
    elif acct_overhead > ACCOUNTING_OVERHEAD_CEILING:
        failures.append(
            f"tenant-accounting overhead {acct_overhead}% > ceiling "
            f"{ACCOUNTING_OVERHEAD_CEILING}% (the always-on contract)"
        )
    vo = line.get("advisor_overhead") or {}
    adv_overhead = vo.get("overhead_pct")
    if adv_overhead is None:
        failures.append("config 2 carries no advisor_overhead measurement")
    elif adv_overhead > ADVISOR_OVERHEAD_CEILING:
        failures.append(
            f"advisor-sweep overhead {adv_overhead}% > ceiling "
            f"{ADVISOR_OVERHEAD_CEILING}% (the always-on contract)"
        )
    # plan cache (schema/15): the parity object is the validator's problem
    # structurally; the gate enforces the PERF floors — the warm window
    # must serve (hit rate) and serving must actually be cheaper than
    # parsing (warm pre-kernel <= ratio * cold pre-kernel)
    pp = line.get("plan_cache_parity") or {}
    pc_hit = pp.get("warm_hit_rate")
    if pc_hit is None:
        failures.append("config 2 carries no plan_cache_parity measurement")
    else:
        if pc_hit < PLAN_CACHE_HIT_FLOOR:
            failures.append(
                f"plan-cache warm hit rate {pc_hit} < floor {PLAN_CACHE_HIT_FLOOR}"
            )
        cold_us, warm_us = pp.get("prekernel_cold_us"), pp.get("prekernel_warm_us")
        if not cold_us or warm_us is None:
            failures.append(
                "plan-cache parity carries no cold/warm pre-kernel split"
            )
        elif warm_us > cold_us * PLAN_CACHE_WARM_COST_RATIO:
            failures.append(
                f"plan-cache warm pre-kernel {warm_us}us > "
                f"{PLAN_CACHE_WARM_COST_RATIO} * cold {cold_us}us — serving "
                "is not beating re-parsing"
            )
    # the statistics plane must have SEEN the window: a /12 artifact whose
    # config-2 line recorded no fingerprints means recording is broken
    st = line.get("statements") or {}
    if not st.get("top"):
        failures.append("config 2 statements.top is empty — stats plane blind")
    if line.get("slow_over_5s"):
        # warning only: on accelerator-less CI containers the jax-CPU
        # compiles land mid-window and trip this without any engine defect
        print(
            f"bench_gate: WARN — {line['slow_over_5s']} post-ingest "
            "statement(s) over 5s (see slowest_trace in the artifact)",
            file=sys.stderr,
        )

    # ---- config 6: columnar filtered-SELECT floor --------------------
    scan_line = next(
        (
            r
            for r in art["results"]
            if str(r.get("config")) == "6"
            and str(r.get("metric", "")).startswith("filtered_scan")
        ),
        None,
    )
    scan_summary = None
    if scan_line is None:
        failures.append("no config-6 filtered_scan line in artifact")
    else:
        if scan_line.get("same_results") is not True:
            failures.append("filtered_scan: columnar results diverged from row path")
        sqps = scan_line.get("value") or 0.0
        if sqps < FLOOR_SCAN_QPS:
            failures.append(f"filtered_scan qps {sqps} < floor {FLOOR_SCAN_QPS}")
        ratio = scan_line.get("vs_baseline")
        if ratio is not None and ratio < FLOOR_SCAN_RATIO:
            failures.append(
                f"filtered_scan columnar/row speedup {ratio}x < floor {FLOOR_SCAN_RATIO}x"
            )
        serrs = scan_line.get("errors") or {}
        if any(serrs.values()):
            failures.append(f"filtered_scan errors != 0: {serrs}")
        scan_summary = {
            "qps": sqps,
            "ratio": ratio,
            "rows_matched": scan_line.get("rows_matched"),
            "scan": scan_line.get("scan"),
        }

    # ---- ingest floors (schema/7): bulk-load rate on every config line,
    # plus the sustained mirrored-table delta-feed ratio on config 6 -----
    ingest_summary = None
    for r in art["results"]:
        rate = r.get("ingest_rate_rows_s")
        if str(r.get("config")) == "8":
            # the chaos window measures SURVIVAL, not ingest: its seed load
            # is deliberately tiny and RF-replicated over the HTTP channel,
            # so its informational rate sits in a different regime than the
            # embedded bulk path the floor protects
            continue
        if r.get("config") is not None and isinstance(rate, (int, float)):
            if rate < FLOOR_INGEST:
                failures.append(
                    f"config {r['config']} ingest_rate_rows_s {rate} < "
                    f"floor {FLOOR_INGEST}"
                )
    if scan_line is not None:
        ing = scan_line.get("ingest") or {}
        ingest_summary = ing
        iratio = ing.get("delta_vs_r10")
        if iratio is None or iratio < FLOOR_INGEST_RATIO:
            failures.append(
                f"sustained mirrored-table ingest delta_vs_r10 {iratio} < "
                f"floor {FLOOR_INGEST_RATIO}x"
            )
        if ing.get("parity_failures") != 0:
            failures.append(
                f"sustained ingest parity failures: {ing.get('parity_failures')}"
            )

    # ---- config 8: chaos-window floors (errors bounded, zero wrong
    # answers; the validator already enforced chaos structure + wrong==0,
    # the gate re-checks so a weakened validator can't sneak one through)
    chaos_summary = None
    chaos_line = next(
        (
            r
            for r in art["results"]
            if str(r.get("config")) == "8"
            and str(r.get("metric", "")).startswith("chaos_")
        ),
        None,
    )
    if chaos_line is None:
        failures.append("no config-8 chaos_reads line in artifact")
    else:
        ch = chaos_line.get("chaos") or {}
        chaos_summary = ch
        if ch.get("wrong_answers") != 0:
            failures.append(
                f"chaos window wrong_answers {ch.get('wrong_answers')} != 0"
            )
        if (ch.get("errors") or 0) > CHAOS_MAX_ERRORS:
            failures.append(
                f"chaos window errors {ch.get('errors')} > ceiling {CHAOS_MAX_ERRORS}"
            )
        if (ch.get("rf") or 1) >= 2 and not ch.get("degraded_responses"):
            failures.append("chaos window shows no degraded responses after the kill")
        # events floor (schema/9): the chaos window's structured timeline
        # must SHOW the failure handling — at least one breaker event, and
        # every degraded read attributed to a statement's trace (an
        # unattributed degraded read is a failover no one can explain)
        ev = chaos_line.get("events")
        if not isinstance(ev, dict):
            failures.append("chaos line carries no 'events' accounting")
        else:
            if (ev.get("breaker") or 0) < 1:
                failures.append(
                    "chaos window shows no breaker event — the kill never "
                    "tripped a circuit breaker"
                )
            if ev.get("unattributed_degraded_reads") != 0:
                failures.append(
                    f"{ev.get('unattributed_degraded_reads')} degraded "
                    "read(s) carry no trace_id — unattributable failovers"
                )

    # ---- config 10: elastic-chaos floors (schema/11) ------------------
    elastic_summary = None
    elastic_line = next(
        (
            r
            for r in art["results"]
            if str(r.get("config")) == "10"
            and str(r.get("metric", "")).startswith("elastic_")
        ),
        None,
    )
    if elastic_line is None:
        failures.append("no config-10 elastic_reads line in artifact")
    else:
        el = elastic_line.get("elastic") or {}
        elastic_summary = el
        # re-check the validator's hard rules (a weakened validator must
        # not sneak one through), then the gate-only ceilings
        if el.get("wrong_answers") != 0:
            failures.append(
                f"elastic window wrong_answers {el.get('wrong_answers')} != 0"
            )
        if el.get("lost_acked_writes") != 0:
            failures.append(
                f"elastic window lost {el.get('lost_acked_writes')} acked write(s)"
            )
        if (el.get("errors") or 0) > ELASTIC_MAX_ERRORS:
            failures.append(
                f"elastic window errors {el.get('errors')} > ceiling {ELASTIC_MAX_ERRORS}"
            )
        if not el.get("migration_rows"):
            failures.append("elastic window streamed no migration rows")
        rs = el.get("repair_s")
        if rs is None or rs > REPAIR_CEILING_S:
            failures.append(
                f"elastic repair time {rs}s exceeds ceiling {REPAIR_CEILING_S}s "
                "(kill -> replacement-converged must stay bounded)"
            )
        ev = elastic_line.get("events")
        if not isinstance(ev, dict) or not ev.get("member_join"):
            failures.append(
                "elastic window shows no cluster.member_join event — the "
                "replacement join left no timeline evidence"
            )

    # ---- config 9: vectorized-pipeline floors (schema/10) -------------
    pipe_summary = None
    pipe_line = next(
        (
            r
            for r in art["results"]
            if str(r.get("config")) == "9"
            and str(r.get("metric", "")).startswith("ordered_agg")
        ),
        None,
    )
    if pipe_line is None:
        failures.append("no config-9 ordered_agg line in artifact")
    else:
        pipe_summary = {
            "order": pipe_line.get("order"),
            "agg": pipe_line.get("agg"),
        }
        for part in ("order", "agg"):
            obj = pipe_line.get(part) or {}
            if obj.get("same_results") is not True:
                failures.append(
                    f"ordered_agg: {part} columnar results diverged from row path"
                )
            ratio = obj.get("ratio")
            if ratio is None or ratio < FLOOR_PIPE_RATIO:
                failures.append(
                    f"ordered_agg {part} columnar/row speedup {ratio}x < "
                    f"floor {FLOOR_PIPE_RATIO}x"
                )
        perrs = pipe_line.get("errors") or {}
        if any(perrs.values()):
            failures.append(f"ordered_agg errors != 0: {perrs}")

    # ---- config 13: C1M network-plane floors (schema/16) --------------
    net_summary = None
    net_line = next(
        (
            r
            for r in art["results"]
            if str(r.get("config")) == "13"
            and str(r.get("metric", "")).startswith("c1m_net")
        ),
        None,
    )
    if net_line is None:
        failures.append("no config-13 c1m_net line in artifact")
    else:
        net = net_line.get("net") or {}
        net_summary = {
            "idle_conns": net.get("idle_conns"),
            "active_conns": net.get("active_conns"),
            "per_conn_bytes": net.get("per_conn_bytes"),
            "accept_to_first_byte": net.get("accept_to_first_byte"),
            "victim": net.get("victim"),
            "abuser_shed": (net.get("abuser") or {}).get("shed"),
        }
        # re-check the validator's hard rules, then the gate-only ceiling
        if net.get("errors") != 0:
            failures.append(f"c1m_net active-burst errors {net.get('errors')} != 0")
        vic = net.get("victim") or {}
        ratio = vic.get("p99_ratio")
        if ratio is None:
            failures.append("c1m_net carries no victim p99_ratio measurement")
        elif ratio > NET_VICTIM_RATIO:
            failures.append(
                f"victim-tenant contended p99 is {ratio}x its solo p99 > "
                f"ceiling {NET_VICTIM_RATIO}x — the abusive tenant broke "
                "through the weighted-fair admission plane"
            )
        if vic.get("shed"):
            failures.append(
                f"victim tenant was shed {vic.get('shed')} time(s) under the flood"
            )
        if not (net.get("abuser") or {}).get("shed"):
            failures.append(
                "c1m_net abuser.shed == 0 — the flood was never pushed back on"
            )

    summary = {
        "qps": qps,
        "c1m_net": net_summary,
        "profiler_overhead_pct": overhead,
        "advisor_overhead_pct": adv_overhead,
        "recall_at_10": recall,
        "latency_ms": line.get("latency_ms"),
        "errors": errs,
        "retries": line.get("retries"),
        "splits": line.get("splits"),
        "width_dist": (line.get("batch") or {}).get("width_dist"),
        "plan_cache": pp,
        "filtered_scan": scan_summary,
        "ingest_rate_rows_s": line.get("ingest_rate_rows_s"),
        "ingest": ingest_summary,
        "chaos": chaos_summary,
        "elastic": elastic_summary,
        "ordered_agg": pipe_summary,
        "artifact": out,
    }
    print(f"bench_gate: {json.dumps(summary)}")
    if failures:
        for msg in failures:
            print(f"bench_gate: FAIL — {msg}", file=sys.stderr)
        return 1
    print("bench_gate: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
