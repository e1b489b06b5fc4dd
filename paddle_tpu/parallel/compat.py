"""Backend differences the parallel stack has to ask about."""

from __future__ import annotations

__all__ = ["memory_kind"]


def memory_kind(device, kind):
    """`kind` when `device` can address that memory space, else None
    (= the device's default space): a sharding built with a kind the
    backend lacks must degrade rather than fail at device_put."""
    try:
        kinds = {m.kind for m in device.addressable_memories()}
    except Exception:
        return None
    return kind if kind in kinds else None
