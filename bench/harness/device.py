"""The chip: pinning JAX to the TPU, the table of peaks, peak memory."""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def pin_tpu(chips: int):
    """Start JAX on the TPU alone (no quiet fallback to the CPU) and
    return its devices; raises NoChip when it finds none or too few.
    ``LIBTPU_INIT_ARGS`` is left as the machine sets it."""
    os.environ["JAX_PLATFORMS"] = "tpu"
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"no TPU visible to JAX ({e})") from e
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX runs on {devices[0].platform!r}, not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices[:chips]


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device missing from
    the table is an error, not a default."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}")
    return table[device_kind]


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None
