"""A field of the device's memory statistics from ``GET /v1/info`` of
the process that owns the chip, read after the window: the largest over
the chips. args: ``field``."""


def read(ctx, field="peak_bytes_in_use"):
    vals = [m.get(field) for m in ctx.info.get("device_memory", [])
            if m.get(field) is not None]
    return float(max(vals)) if vals else None
