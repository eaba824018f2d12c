"""Production mesh construction (multi-pod dry-run spec).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state.  Single pod: (16, 16) = 256 chips on
("data", "model"); multi-pod: (2, 16, 16) = 512 chips on
("pod", "data", "model") — the leading "pod" axis crosses the slower
inter-pod links, mirroring the paper's node/worker bandwidth hierarchy.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_axis: int = 1):
    """Degenerate mesh over whatever devices exist (tests / CPU runs)."""
    n = len(jax.devices())
    data = n // model_axis
    return jax.make_mesh((data, model_axis), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def device_inventory() -> list:
    """Enumerate the real ``jax.Device``s of the host mesh, one dict per
    device — the device-class record a ``CalibrationProfile`` carries so a
    profile fitted on one substrate is never silently applied to another.
    Sorted by device id for a deterministic listing."""
    out = []
    for d in sorted(jax.devices(), key=lambda d: d.id):
        out.append({
            "id": int(d.id),
            "platform": str(d.platform),
            "device_kind": str(getattr(d, "device_kind", d.platform)),
            "process_index": int(getattr(d, "process_index", 0)),
        })
    return out


def device_class(backend: str = "jax") -> str:
    """One-line device-class summary for profile metadata, e.g.
    ``"jax:cpu (TFRT CPU) x8"``.  Falls back to ``"<backend>:host"`` when
    jax device enumeration is unavailable (numpy/sim backends never need
    real devices)."""
    try:
        inv = device_inventory()
    except Exception:  # pragma: no cover - no jax runtime
        return f"{backend}:host"
    if not inv:
        return f"{backend}:host"
    d = inv[0]
    return f"{backend}:{d['platform']} ({d['device_kind']}) x{len(inv)}"
