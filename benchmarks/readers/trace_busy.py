"""Quantities of the device trace of the window (trace_reduce.py).

args: ``quantity``
  "busy_ms_per_stmt"     union of device-op intervals / statements
  "idle_share"           100 * (1 - busy union / traced window)
  "dispatches_per_stmt"  program executions on the device / statements
  "scan_roofline"        100 * (bytes the window's statements must read
                         / peak HBM bytes per second) / busy time —
                         memory-bound by construction (bytes_model.py)
Nothing where the run has no device trace."""


def read(ctx, quantity):
    tr = ctx.trace
    if tr is None or not tr["devices"] or tr["busy_s"] <= 0:
        return None
    n = len(ctx.statements)
    if quantity == "busy_ms_per_stmt":
        return tr["busy_s"] * 1e3 / n if n else None
    if quantity == "idle_share":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    if quantity == "dispatches_per_stmt":
        return tr["executions"] / tr["devices"] / n if n else None
    if quantity == "scan_roofline":
        import bytes_model

        peak = ctx.peaks[ctx.info["device_kind"]]["hbm_gbytes_per_s"] * 1e9
        need = bytes_model.window_bytes(ctx.config, ctx.mix, ctx.statements)
        return 100.0 * (need / peak) / tr["busy_s"]
    raise ValueError(f"unknown quantity {quantity!r}")
