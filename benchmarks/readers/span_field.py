"""A flat field that the program's span tree puts on a statement's row
of ``GET /v1/query`` (``trino_tpu/server/coordinator.py``: the sealed
tree's totals by span name, ``<name>_ms``, and the counts
``host_syncs`` / ``dispatches``), averaged over the window's statements
by the rules of ``query_list.py``: matched by query id, and the run
fails where under ``min_share`` of the window's statements carry the
field.

Nothing where no statement of the list carries the field at all: the
program serves no such span (a checkout from before the spans), which
is not a fault of the run.

args: ``field``, ``cls``, ``min_share`` (``query_list``'s)."""

import importlib.util
import os


def read(ctx, field, cls=None, min_share=0.9):
    if not any(q.get(field) is not None for q in ctx.query_list):
        return None
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "query_list.py")
    spec = importlib.util.spec_from_file_location("_reader_query_list", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx, field, cls=cls, min_share=min_share)
