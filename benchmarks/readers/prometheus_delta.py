"""A counter of ``GET /v1/metrics`` after the window minus before.

args: ``series`` (a list is summed), ``of`` ("chip": the process that
owns the chip, "entry": the one the client talks to, "all": every
child), ``per`` ("window" or "stmt"), ``scale``. Nothing where a
server never exported the series."""


def read(ctx, series, of="chip", per="window", scale=1.0):
    names = [series] if isinstance(series, str) else list(series)
    roles = list(ctx.after)
    if of != "all":
        uri = ctx.servers.chip_uri if of == "chip" else ctx.servers.entry_uri
        roles = [r for r, u in ctx.servers.uris.items() if u == uri]
    found, total = False, 0.0
    for role in roles:
        for name in names:
            if name in ctx.after[role]:
                found = True
                total += (ctx.after[role][name]
                          - ctx.before[role].get(name, 0.0))
    if not found:
        return None
    if per == "stmt":
        if not ctx.statements:
            return None
        total /= len(ctx.statements)
    return total * scale
