#!/usr/bin/env python
"""AOT rehearsal: do the programs the engine dispatches compile for a
TPU v5e, and how long does the chip's compiler take?

No chip is needed and none is used.  Two steps, both on this host:

``capture``  runs TPC-H statements through ``QueryRunner`` on the CPU
    backend with ``jax.jit`` wrapped, so that every top-level program
    the engine dispatches is seen with its real argument shapes (the
    canonical buckets of that scale factor).  Each distinct program is
    re-lowered **for the tpu platform** through ``jax.export`` (the tpu
    lowering rules, no backend needed) and written to ``--out`` as
    serialized StableHLO plus one line of ``manifest.jsonl``.

``compile``  compiles each exported program for a described ``v5e:2x2``
    topology (one device of it), one child process per program, so a
    compiler crash or a hang costs that program and not the run.  One
    line of ``results.jsonl`` per program: ``compiled`` | ``refused`` |
    ``crashed`` | ``timeout``, seconds, and ``memory_analysis()`` bytes.

A compile that passes is not a chip run: nothing executes, so this
says nothing about results or device times.

    JAX_PLATFORMS=cpu python tools/aot_probe.py capture --sf sf1 \
        --queries q01,q03,q06,q18 --out /root/scratch/aot
    JAX_PLATFORMS=cpu python tools/aot_probe.py compile --out /root/scratch/aot

``capture --mesh 4`` (under ``XLA_FLAGS=
--xla_force_host_platform_device_count=4``) records the mesh executor's
programs with their layouts; ``compile`` then builds a ``Mesh`` of the
described chips for them.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES = 16 * 1024**3  # one v5e chip (Google Cloud documentation, "TPU v5e")


# ---- capture ---------------------------------------------------------------


class _Recorder:
    """Stands in for ``jax.jit``: the wrapped callable behaves like the
    jitted function, and remembers the abstract signature of every
    top-level call (no tracers among its arguments)."""

    def __init__(self, orig_jit):
        self.orig_jit = orig_jit
        self.calls: dict[tuple, dict] = {}
        self.current = ""

    def jit(self, fun=None, **kw):
        if fun is None:  # partial(jax.jit, static_argnames=...) form
            return lambda f: self.jit(f, **kw)
        return _Wrapped(self, fun, kw)


class _Wrapped:
    def __init__(self, rec: _Recorder, fun, kw):
        self._rec, self._fun, self._kw = rec, fun, kw
        self._jitted = rec.orig_jit(fun, **kw)
        self.__wrapped__ = fun
        self.__name__ = getattr(fun, "__name__", "program")
        self.__doc__ = getattr(fun, "__doc__", None)

    def __getattr__(self, name):
        return getattr(self._jitted, name)

    def __call__(self, *args, **kwargs):
        self._record(args, kwargs)
        return self._jitted(*args, **kwargs)

    def _record(self, args, kwargs):
        import inspect

        import jax

        static = set(self._kw.get("static_argnames") or ())
        try:
            bound = inspect.signature(self._fun).bind(*args, **kwargs)
        except (TypeError, ValueError):
            return
        dyn = {k: v for k, v in bound.arguments.items() if k not in static}
        stat = {k: v for k, v in bound.arguments.items() if k in static}
        leaves, treedef = jax.tree_util.tree_flatten(dyn)
        if any(isinstance(x, jax.core.Tracer) for x in leaves):
            return  # nested inside another program: part of that one
        import numpy as np

        specs = tuple(
            (tuple(np.shape(x)), str(jax.numpy.result_type(x))) for x in leaves
        )
        shardings = tuple(_named_spec(x) for x in leaves)
        key = (id(self), repr(stat), str(treedef), specs, shardings)
        hit = self._rec.calls.get(key)
        if hit is not None:
            hit["calls"] += 1
            if self._rec.current not in hit["queries"]:
                hit["queries"].append(self._rec.current)
            return
        self._rec.calls[key] = {
            "fun": self._fun, "kw": self._kw, "static": stat,
            "treedef": treedef, "specs": specs, "shardings": shardings,
            "calls": 1,
            "queries": [self._rec.current],
            "name": getattr(self._fun, "__qualname__", "program"),
        }


def _named_spec(x):
    """(mesh axis names, mesh shape, partition spec) of an argument laid
    out over a multi-device mesh, else None."""
    from jax.sharding import NamedSharding

    sh = getattr(x, "sharding", None)
    if not isinstance(sh, NamedSharding) or sh.mesh.size == 1:
        return None
    return (
        tuple(sh.mesh.axis_names), tuple(sh.mesh.devices.shape),
        tuple(None if p is None else p for p in sh.spec),
    )


def _mesh_of(shardings):
    for sp in shardings:
        if sp is not None:
            return sp[0], sp[1]
    return None


def _export_one(rec: _Recorder, entry: dict):
    """StableHLO for the tpu platform, over flat leaves (no pytree
    registry needed to deserialize)."""
    import inspect

    import jax
    from jax import export

    treedef, static, fun = entry["treedef"], entry["static"], entry["fun"]

    sig = inspect.signature(fun)

    def flat(*leaves):
        named = {**jax.tree_util.tree_unflatten(treedef, leaves), **static}
        call = sig.bind_partial()
        # parameter order, so *args parameters expand positionally
        call.arguments = {
            k: named[k] for k in sig.parameters if k in named
        }
        return jax.tree_util.tree_leaves(fun(*call.args, **call.kwargs))

    mesh = _mesh_of(entry["shardings"])
    if mesh is None:
        avals = [jax.ShapeDtypeStruct(s, d) for s, d in entry["specs"]]
    else:
        # a mesh program: export over the host's (virtual) devices with
        # the recorded layouts; compile_one rebuilds them on the
        # described chips
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        m = Mesh(
            np.asarray(jax.devices()[: int(np.prod(mesh[1]))]).reshape(
                mesh[1]
            ), mesh[0],
        )
        avals = [
            jax.ShapeDtypeStruct(
                s, d, sharding=NamedSharding(
                    m, PartitionSpec(*(sp[2] if sp else ()))
                ),
            )
            for (s, d), sp in zip(entry["specs"], entry["shardings"])
        ]
    return export.export(rec.orig_jit(flat), platforms=["tpu"])(*avals)


def capture(args) -> int:
    import jax

    rec = _Recorder(jax.jit)
    jax.jit = rec.jit  # before trino_tpu binds its decorators
    from trino_tpu.connectors.tpch.queries import QUERIES
    from trino_tpu.engine import QueryRunner

    os.makedirs(args.out, exist_ok=True)
    t0 = time.time()
    mesh = None
    if args.mesh:
        from trino_tpu.parallel.core import make_mesh

        mesh = make_mesh(args.mesh)
    runner = QueryRunner.tpch(args.sf, mesh=mesh)
    for q in args.queries.split(","):
        rec.current = q
        t1 = time.time()
        res = runner.execute(QUERIES[q])
        print(f"{q}: {len(res.rows)} rows in {time.time() - t1:.1f}s on "
              f"{jax.default_backend()} (host time, not a device metric)",
              flush=True)
    print(f"captured {len(rec.calls)} programs in {time.time() - t0:.1f}s",
          flush=True)
    with open(os.path.join(args.out, "manifest.jsonl"), "w") as man:
        for entry in rec.calls.values():
            line = {
                "name": entry["name"], "queries": entry["queries"],
                "calls": entry["calls"], "static": repr(entry["static"])[:200],
                "specs": [list(map(str, s)) for s in entry["specs"]],
                "arg_bytes": sum(
                    _nbytes(s, d) for s, d in entry["specs"]
                ),
                "shardings": entry["shardings"],
            }
            try:
                exp = _export_one(rec, entry)
                blob = bytes(exp.serialize())
                pid = hashlib.sha1(blob).hexdigest()[:12]
                with open(os.path.join(args.out, f"{pid}.mlir.bin"), "wb") as fh:
                    fh.write(blob)
                line["id"] = pid
            except Exception as e:  # recorded, the probe goes on
                line["export_error"] = f"{type(e).__name__}: {e}"[:400]
            man.write(json.dumps(line) + "\n")
            man.flush()
    return 0


def _nbytes(shape, dtype) -> int:
    import numpy as np

    n = 1
    for d in shape:
        n *= d
    return n * np.dtype(dtype).itemsize


# ---- compile ---------------------------------------------------------------


def compile_one(path: str, topology: str, shardings=None) -> dict:
    """Runs in a child: deserialize one exported program and compile it
    for one device of the described topology — or, for a mesh program,
    for a ``Mesh`` of its devices with the recorded layouts."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_enable_compilation_cache", False)
    from jax import export
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    with open(path, "rb") as fh:
        exp = export.deserialize(bytearray(fh.read()))
    topo = topologies.get_topology_desc(platform="tpu", topology_name=topology)
    mesh = _mesh_of(shardings or [])
    if mesh is None:
        one = SingleDeviceSharding(topo.devices[0])
        layouts = [one] * len(exp.in_avals)
    else:
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        n = int(np.prod(mesh[1]))
        m = Mesh(np.asarray(topo.devices[:n]).reshape(mesh[1]), mesh[0])
        layouts = [
            NamedSharding(m, PartitionSpec(*(sp[2] if sp else ())))
            for sp in shardings
        ]
    avals = [
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=lay)
        for a, lay in zip(exp.in_avals, layouts)
    ]
    t0 = time.time()
    lowered = jax.jit(exp.call).lower(*avals)
    compiled = lowered.compile()
    secs = time.time() - t0
    ma = compiled.memory_analysis()
    return {
        "status": "compiled", "seconds": round(secs, 2),
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "code_bytes": int(ma.generated_code_size_in_bytes),
    }


def compile_all(args) -> int:
    with open(os.path.join(args.out, "manifest.jsonl")) as fh:
        manifest = [json.loads(x) for x in fh if x.strip()]
    only = set(args.only.split(",")) if args.only else None
    todo = [m for m in manifest if "id" in m and (not only or m["id"] in only)]
    results_path = os.path.join(args.out, "results.jsonl")
    running: list[tuple] = []
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if args.jobs > 1:
        # scratch-only: lets several compiler processes load libtpu at
        # once on a host that has no chip to fight over
        env["ALLOW_MULTIPLE_LIBTPU_LOAD"] = "1"

    def reap():
        for item in list(running):
            proc, m, t0 = item
            try:
                out, err = proc.communicate(timeout=0.2)
            except subprocess.TimeoutExpired:
                if time.time() - t0 < args.timeout:
                    continue
                proc.kill()
                proc.communicate()
                res = {"status": "timeout", "seconds": args.timeout}
            else:
                last = out.strip().splitlines()[-1] if out.strip() else ""
                if proc.returncode == 0 and last.startswith("{"):
                    res = json.loads(last)
                elif proc.returncode < 0 or proc.returncode >= 128:
                    res = {"status": "crashed", "rc": proc.returncode,
                           "seconds": round(time.time() - t0, 1),
                           "stderr": err[-600:]}
                else:
                    res = {"status": "refused", "rc": proc.returncode,
                           "seconds": round(time.time() - t0, 1),
                           "stderr": err[-1200:]}
            running.remove(item)
            res.update(id=m["id"], name=m["name"], queries=m["queries"],
                       arg_bytes=m["arg_bytes"])
            if res["status"] == "compiled":
                total = (res["argument_bytes"] + res["output_bytes"]
                         + res["temp_bytes"])
                res["hbm_share"] = round(total / HBM_BYTES, 4)
            with open(results_path, "a") as fh:
                fh.write(json.dumps(res) + "\n")
            print(json.dumps({k: v for k, v in res.items() if k != "stderr"}),
                  flush=True)

    for m in todo:
        while len(running) >= args.jobs:
            reap()
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "_one",
             os.path.join(args.out, f"{m['id']}.mlir.bin"), args.topology,
             json.dumps(m.get("shardings"))],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        )
        running.append((proc, m, time.time()))
    while running:
        reap()
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    c = sub.add_parser("capture")
    c.add_argument("--sf", default="sf1")
    c.add_argument("--queries", default="q01,q03,q06,q18")
    c.add_argument("--out", required=True)
    c.add_argument(
        "--mesh", type=int, default=0,
        help="run the mesh executor over this many (virtual) devices; "
             "needs XLA_FLAGS=--xla_force_host_platform_device_count=N",
    )
    k = sub.add_parser("compile")
    k.add_argument("--out", required=True)
    k.add_argument("--topology", default="v5e:2x2")
    k.add_argument("--timeout", type=float, default=600.0)
    k.add_argument("--jobs", type=int, default=1)
    k.add_argument("--only", default="")
    o = sub.add_parser("_one")
    o.add_argument("path")
    o.add_argument("topology")
    o.add_argument("shardings", nargs="?", default="null")
    args = ap.parse_args()
    if args.cmd == "capture":
        return capture(args)
    if args.cmd == "compile":
        return compile_all(args)
    print(json.dumps(compile_one(
        args.path, args.topology, json.loads(args.shardings)
    )))
    return 0


if __name__ == "__main__":
    sys.exit(main())
