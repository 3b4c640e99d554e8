"""A gauge of ``GET /v1/metrics`` as it reads after the window (a
level, where ``prometheus_delta`` reads a counter's difference).

args: ``series`` (a list is summed), ``of`` ("chip": the process that
owns the chip, "entry": the one the client talks to, "all": every
child), ``scale``. Nothing where no server exports the series."""


def read(ctx, series, of="chip", scale=1.0):
    names = [series] if isinstance(series, str) else list(series)
    roles = list(ctx.after)
    if of != "all":
        uri = ctx.servers.chip_uri if of == "chip" else ctx.servers.entry_uri
        roles = [r for r, u in ctx.servers.uris.items() if u == uri]
    vals = [ctx.after[role][name] for role in roles for name in names
            if name in ctx.after[role]]
    return sum(vals) * scale if vals else None
