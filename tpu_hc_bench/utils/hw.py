"""Per-chip peak-FLOPs table for MFU accounting.

The reference never computes MFU (its metric is raw images/sec); the
BASELINE.json north star for this repo is ">=60% MFU on v5e", so the driver
needs peak numbers.  Figures are the public per-chip peak dense-matmul
rates (bf16 / fp32-equivalent), keyed by the exact ``device_kind`` string
JAX reports.  A kind that is not in the table is an error, not a default:
an MFU against the wrong peak is worse than no MFU.
"""

from __future__ import annotations

import jax

# device_kind -> (bf16_peak_flops, fp32_peak_flops) per chip
_PEAKS: dict[str, tuple[float, float]] = {
    "TPU v5 lite": (197e12, 98e12),    # v5e
    "TPU v5": (459e12, 229e12),        # v5p
    "TPU v4": (275e12, 137e12),
    "TPU v3": (123e12, 61e12),
    "TPU v2": (45e12, 22e12),
    "TPU v6 lite": (918e12, 459e12),   # v6e (Trillium)
    "cpu": (1e11, 5e10),               # nominal: the virtual test mesh only
}


def peak_flops(device: jax.Device | None = None, dtype: str = "bfloat16") -> float:
    """Peak FLOPs/s for one chip of this device's kind; raises KeyError
    on a kind the table does not hold."""
    device = device or jax.devices()[0]
    try:
        bf16, f32 = _PEAKS[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no peak-FLOPs row for device_kind={device.device_kind!r} "
            f"(known: {sorted(_PEAKS)}); add the sourced figure to "
            f"tpu_hc_bench/utils/hw.py rather than guess") from None
    return bf16 if dtype == "bfloat16" else f32


def require_accelerator(virtual_devices: int = 0) -> None:
    """Refuse to carry on without the chip: raise unless the backend is
    ``tpu``, or the CPU was asked for (``JAX_PLATFORMS``/``jax_platforms``
    names it, or ``--virtual_devices``).  JAX itself only warns and falls
    back to the CPU when it finds no TPU; a benchmark that then runs to
    exit 0 reports CPU numbers under device names."""
    backend = jax.default_backend()
    if backend == "tpu":
        return
    if backend == "cpu" and (virtual_devices or "cpu" in _requested_platforms()):
        return
    raise RuntimeError(
        f"JAX initialised the {backend!r} backend, not 'tpu', and the CPU "
        f"was not asked for (JAX_PLATFORMS={_requested_platforms()!r}, no "
        f"--virtual_devices): refusing to benchmark a device nobody "
        f"chose.  Set JAX_PLATFORMS=cpu for a CPU run.")


def _requested_platforms() -> str:
    return jax.config.jax_platforms or ""


def device_kind() -> str:
    return jax.devices()[0].device_kind


def ici_topology_lines(devices=None) -> list[str]:
    """Live fabric introspection for the banner — the operator's ground
    truth before a run, playing the role of the reference's sysfs PKEY
    read + UCX_NET_DEVICES pin (run-tf-sing-ucx-openmpi.sh:85-95).

    Reports the slice shape (chip-coordinate bounding box), per-host chip
    counts, and each local chip's ICI coordinates.  Degrades gracefully on
    devices without coords (CPU test meshes): reports kinds only.
    """
    devices = list(devices if devices is not None else jax.devices())
    lines = []
    coords = [getattr(d, "coords", None) for d in devices]
    if any(c is not None for c in coords):
        known = [c for c in coords if c is not None]
        dims = range(len(known[0]))
        shape = "x".join(
            str(max(c[i] for c in known) - min(c[i] for c in known) + 1)
            for i in dims)
        lines.append(
            f"ici: slice_shape={shape} chips={len(known)} "
            f"kind={devices[0].device_kind}")
        per_host: dict[int, list] = {}
        for d, c in zip(devices, coords):
            per_host.setdefault(d.process_index, []).append(
                (d.id, c, getattr(d, "core_on_chip", 0)))
        for host in sorted(per_host):
            chips = " ".join(
                f"d{did}@{','.join(map(str, c))}" if c is not None
                else f"d{did}" for did, c, _ in per_host[host])
            lines.append(f"ici: host{host}: {chips}")
    else:
        lines.append(
            f"ici: no chip coordinates exposed ({devices[0].device_kind} "
            f"x{len(devices)}) — virtual/CPU mesh")
    return lines
