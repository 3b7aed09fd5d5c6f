"""A kernel's share of its roofline: the least time the chip could take for
the operations and bytes the algorithm needs (the larger of operations over
peak FLOP/s and bytes over peak bytes/s), over the kernel's device time in
the traced slice."""

from __future__ import annotations

from typing import Optional


def least_seconds(need: dict, peaks: dict) -> tuple:
    """(seconds, which bound it is)."""
    t_ops = need["flops"] / peaks["bf16_flops_per_s"]
    t_mem = need["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def share(ctx: dict, kernel: str) -> Optional[dict]:
    """{pct, bound, ...} for `kernel`, or None where this cell does not run
    it or the slice saw none of it."""
    k, s = ctx.get("kernel"), ctx.get("slice")
    if not k or k["name"] != kernel or not s or s["reduced"]["kernel_s"] <= 0:
        return None
    d = s["dispatch"]
    need = k["need"](k["shapes"], float(d["submitted"]), float(d["dispatches"]))
    least, bound = least_seconds(need, ctx["peaks"])
    return {
        "pct": 100.0 * least / s["reduced"]["kernel_s"],
        "bound": bound, "least_s": least, "kernel_s": s["reduced"]["kernel_s"], **need,
    }


def share_pct(ctx: dict, kernel: str) -> Optional[float]:
    out = share(ctx, kernel)
    return None if out is None else out["pct"]
