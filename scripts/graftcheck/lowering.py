"""Abstract lowering of one (site, shape) pair to jaxpr + StableHLO.

Nothing here executes a kernel: `jax.make_jaxpr` traces the builder's
function over ShapeDtypeStructs and `jax.jit(...).lower(...)` emits the
StableHLO text XLA would compile — the audit sees exactly the IR the
serving path ships, without paying a compile. Tracing runs under
`jax.enable_x64` so an implicit float64 promotion is
VISIBLE in the jaxpr instead of being silently truncated to f32 by the
default x64-disabled mode (the truncation would hide the exact bug
GC002 exists to catch).
"""

from __future__ import annotations

import hashlib
import re
from dataclasses import dataclass, field
from typing import Dict, List, Set, Tuple

# StableHLO op name -> the report/allowlist spelling (the dashed names the
# XLA literature and the ISSUE/SNIPPETS HLO assertions use)
COLLECTIVE_OPS = {
    "all_gather": "all-gather",
    "all_reduce": "all-reduce",
    "all_to_all": "all-to-all",
    "collective_permute": "collective-permute",
    "collective_broadcast": "collective-broadcast",
    "reduce_scatter": "reduce-scatter",
}
CALLBACK_PRIMITIVES = frozenset(
    {"pure_callback", "io_callback", "debug_callback", "callback"}
)
# dynamic-SHAPE ops (output dims decided at run time). Plain dynamic_slice
# is NOT here: its output shape is static (only the start index is
# dynamic) — it matters to GC003's gather-then-slice pattern, not GC004.
DYNAMIC_SHAPE_OPS = (
    "dynamic_reshape",
    "dynamic_broadcast_in_dim",
    "dynamic_iota",
    "dynamic_pad",
    "real_dynamic_slice",
    "dynamic_conv",
)

_OP_RE = re.compile(r'"?stablehlo\.([a-z_0-9]+)"?')
_DEF_RE = re.compile(r"^\s*(%[\w#:]+)\s*=\s*(.+)$")
_SSA_RE = re.compile(r"%[\w#]+")


@dataclass
class Lowered:
    """Everything the rules need about one lowered (site, shape)."""

    subsystem: str
    label: str
    primitives: Set[str] = field(default_factory=set)
    effects: List[str] = field(default_factory=list)
    aval_dtypes: Set[str] = field(default_factory=set)
    out_dtypes: List[str] = field(default_factory=list)
    hlo_text: str = ""
    hlo_sha256: str = ""
    collectives: Dict[str, int] = field(default_factory=dict)
    gather_feeds_dynamic_slice: bool = False
    dynamic_shape_ops: List[str] = field(default_factory=list)
    has_dynamic_dims: bool = False


def _walk_jaxpr(jaxpr, prims: Set[str], dtypes: Set[str]) -> None:
    for eqn in jaxpr.eqns:
        prims.add(eqn.primitive.name)
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            # weak-typed scalars (Python-literal constants like jnp.inf)
            # are f64 under x64 only until they touch a real operand —
            # not a promotion; only strongly-typed f64 flags GC002
            if getattr(aval, "weak_type", False):
                continue
            dtypes.add(str(aval.dtype))
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    _walk_jaxpr(inner, prims, dtypes)
                elif hasattr(sub, "eqns"):  # a bare Jaxpr param
                    _walk_jaxpr(sub, prims, dtypes)


def _scan_hlo(low: Lowered) -> None:
    """Collective census + the gather-then-dynamic-slice signature over
    the StableHLO text (SSA-level: a dynamic_slice consuming an
    all_gather's result is the SPMD reshard smell, not every coincidental
    pair of ops)."""
    gather_ids: Set[str] = set()
    for raw in low.hlo_text.splitlines():
        # MLIR SSA names are FUNCTION-scoped (%12 in shmap_body and %12 in
        # a helper func are unrelated values) — reset the gather set at
        # every function boundary so a later function's local dynamic_slice
        # can't collide with another function's all_gather result
        if "func.func" in raw:
            gather_ids.clear()
        ops = _OP_RE.findall(raw)
        for op in ops:
            if op in COLLECTIVE_OPS:
                name = COLLECTIVE_OPS[op]
                low.collectives[name] = low.collectives.get(name, 0) + 1
            if op in DYNAMIC_SHAPE_OPS:
                low.dynamic_shape_ops.append(op)
        m = _DEF_RE.match(raw)
        if m and "all_gather" in ops:
            # result ids of an all_gather (`%12` or `%12:2` tuple parts)
            gather_ids.add(m.group(1).split(":")[0])
        if "dynamic_slice" in raw and gather_ids:
            rhs = m.group(2) if m else raw
            used = {s.split("#")[0] for s in _SSA_RE.findall(rhs)}
            if used & gather_ids:
                low.gather_feeds_dynamic_slice = True
    # a `?` dimension inside any tensor type = dynamic shape
    low.has_dynamic_dims = bool(re.search(r"tensor<[^>]*\?", low.hlo_text))


def lower_site(contract: dict, shape: dict) -> Lowered:
    """Trace + lower one declared shape of one site. Raises on a builder
    that itself fails — a broken contract is a finding-level event the
    caller converts (GC000), never a silent skip."""
    import jax

    fn, args = contract["build"](dict(shape))
    low = Lowered(subsystem=contract["subsystem"], label=shape["label"])
    # trace 1 — the REAL serving configuration (x64 off): this is the IR
    # XLA compiles, so HLO text/digest, collectives, output dtypes and
    # callback/effect detection all come from here
    closed = jax.make_jaxpr(fn)(*args)
    _walk_jaxpr(closed.jaxpr, low.primitives, set())
    low.effects = sorted(str(e) for e in closed.effects)
    low.out_dtypes = [
        str(v.aval.dtype)
        for v in closed.jaxpr.outvars
        if hasattr(v.aval, "dtype")
    ]
    low.hlo_text = jax.jit(fn).lower(*args).as_text()
    # trace 2 — x64 enabled, ONLY for the f64-promotion scan: the default
    # mode silently truncates a float64 promotion to f32, which would
    # hide exactly the bug GC002 exists to catch. Integer widening under
    # x64 (arange -> i64) is an audit artifact and is not collected.
    with jax.enable_x64(True):
        closed64 = jax.make_jaxpr(fn)(*args)
        _walk_jaxpr(closed64.jaxpr, set(), low.aval_dtypes)
    low.hlo_sha256 = hashlib.sha256(low.hlo_text.encode()).hexdigest()
    _scan_hlo(low)
    return low
