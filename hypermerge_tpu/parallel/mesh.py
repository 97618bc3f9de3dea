"""What the process can see of its devices."""

from __future__ import annotations


def device_topology() -> dict:
    """Visible-device summary: the Telemetry reply's `device` and what
    `tools/meta.py --devices` prints standalone."""
    import jax

    devs = jax.devices()
    return {
        "n_devices": len(devs),
        "platform": devs[0].platform if devs else None,
        "device_kind": devs[0].device_kind if devs else None,
        "default_backend": jax.default_backend(),
        "process_count": jax.process_count(),
    }
