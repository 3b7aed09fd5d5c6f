"""graftcheck — compiled-IR static analysis for the engine's kernels.

graftlint (scripts/graftlint) polices the Python SOURCE; this package
polices what the engine actually COMPILES. Every kernel call site
registered in `surrealdb_tpu/compile_log.py:KERNEL_SITES` declares an
audit contract (representative shape matrix, abstract-lowering builder,
allowed collectives, declared output dtypes) at the module that owns the
kernel; `python -m scripts.graftcheck` lowers each (site, shape) pair to
jaxpr + StableHLO — the warm-tile shapes single-device, a simulated
8-device mesh for the `shard_map` runners — and checks the IR contracts:

  GC001  purity: no host callbacks (pure_callback / io_callback /
         debug.callback) and no jaxpr effects in any serving kernel — a
         callback serializes the async dispatch pipeline and breaks
         multi-chip lowering.
  GC002  dtype stability: no f64 anywhere in the jaxpr (an implicit
         float64 promotion doubles bandwidth and falls off the MXU), and
         every lowered output dtype is one the site declared — the
         dispatch tile contract collect() relies on.
  GC003  collective discipline: the lowered StableHLO of a sharded
         kernel contains ONLY the declared collectives (the intentional
         O(k·devices) top-k merge all-gathers); any new collective kind,
         any collective in a single-device kernel, and any
         all-gather-whose-result-feeds-a-dynamic-slice (the SPMD
         partitioner's reshard signature — gathering the corpus to every
         chip just to re-slice it) fails.
  GC004  static shapes only: no dynamic dimensions (`?` dims /
         dynamic-shape ops) that would defeat warm-tile executable reuse.

Like graftlint it has inline suppressions (a `"suppress": ("GC00X",)`
entry on the site/shape declaration — visible in review, which is the
point), a committed baseline (scripts/graftcheck/baseline.json;
`--update-baseline` rewrites it), and a tier-1 gate in scripts/tier1.sh.
The per-kernel audit report (rule results, declared collectives,
lowered-shape matrix, HLO digest per shape key) is written as JSON and
embedded as the `kernel_audit` debug-bundle section
(`GET /debug/bundle`).

`--fixtures` audits the seeded-violation kernels in fixtures.py instead
(host callback, f64 promotion, undeclared collective, output-dtype
drift) — the self-test that proves the gate can actually fail.
"""
