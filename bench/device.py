"""The chips a run uses: a TPU and nothing else, with its published peaks.

A run that finds another platform, fewer chips than its cell asks for,
or a device kind that `bench/peaks.json` does not list, stops here with
a message and prints no result.
"""
from __future__ import annotations

import gc
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class DeviceError(SystemExit):
    """The run cannot be measured on this machine (exit code 1)."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}; nothing was run")


def peaks_for(kind: str, path: str = os.path.join(HERE, "peaks.json")) -> dict:
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise DeviceError(f"device kind {kind!r} is not in bench/peaks.json "
                          f"({sorted(table)})")
    return table[kind]


def require_tpu(chips: int) -> tuple:
    """(the first `chips` devices, their peaks); raises DeviceError."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:  # no backend could start
        raise DeviceError(f"JAX found no device ({e})") from e
    if devs[0].platform != "tpu":
        raise DeviceError(f"no TPU found (JAX's default device is "
                          f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX sees "
                          f"{len(devs)}")
    return devs[:chips], peaks_for(devs[0].device_kind)


def describe(devs: list) -> dict:
    import jax

    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": jax.device_count()}


def peak_bytes(devs: list) -> int:
    """`peak_bytes_in_use` of the fullest of `devs`."""
    gc.collect()
    return max(int(d.memory_stats()["peak_bytes_in_use"]) for d in devs)
