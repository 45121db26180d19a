"""The device a run stands on: a TPU with as many chips as the cell
asks for, or no run at all."""
from __future__ import annotations

import sys


def require_tpu(chips: int) -> dict:
    """The device record of the result line; exits 1 (printing nothing
    on standard output) when JAX finds no TPU or too few chips."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        print(f"bench: no TPU found (JAX platform {d.platform!r}); the "
              f"benchmark runs only on a TPU", file=sys.stderr)
        raise SystemExit(1)
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} TPU chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        raise SystemExit(1)
    return describe()


def describe() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device, where it is reported."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))
