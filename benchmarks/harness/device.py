"""The device a run is on, its peaks, and the rule that no chip means no number."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def describe() -> dict:
    """{platform, kind, count} as JAX reports them (initialises the backend)."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def require_chips(dev: dict, chips: int) -> None:
    if dev["platform"] != "tpu":
        raise NoChip(
            f"jax reports platform {dev['platform']!r}, not 'tpu': no accelerator, nothing was run"
        )
    if dev["count"] < chips:
        raise NoChip(f"the cell asks for {chips} chip(s) and jax sees {dev['count']}")


def peaks(kind: str) -> dict:
    """The published peaks of `kind`; a device that is not in the table is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if kind not in table:
        raise KeyError(f"no peaks for device_kind {kind!r} in harness/peaks.json")
    return table[kind]


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device (0 where the backend
    reports no memory statistics, as the CPU backend does)."""
    import jax

    peak = 0
    for d in jax.local_devices():
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0) or 0))
    return peak
