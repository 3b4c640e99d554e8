"""The scope grammar of ``exec/kernels.py`` is complete: every
instruction of every program a statement dispatches lies under an
operator's scope, every sort, gather and scatter under a kernel's, and
no scope holds anything but a static name.

A device trace hands an instruction's ``op_name`` back as ``tf_op`` on
the event's metadata (``benchmarks/readers/trace_scopes.py``,
``kernel_profile.attribute``); here the same text is read off the
compiled program on the CPU, which costs no chip.
"""

import gzip
import importlib.util
import os
import re
import shutil
import subprocess
import sys

import jax
import pytest

from trino_tpu import kernel_profile
from trino_tpu.connectors.tpch.queries import QUERIES
from trino_tpu.engine import QueryRunner
from trino_tpu.exec import local, mesh
from trino_tpu.parallel.core import make_mesh

TEMPLATES = ("q01", "q03", "q06", "q18")

#: one instruction with its opcode and its op_name
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\S+\s+([\w\-]+)\("
    r'.*?metadata=\{[^}]*op_name="([^"]*)"',
    re.M,
)
#: the opcodes that are a sort, a gather or a scatter themselves
_MOVERS = ("sort", "gather", "scatter")


def _abstract(x):
    """An argument's shape, with its layout where it is laid over
    several devices (an array on one device is placed by the call)."""
    sh = getattr(x, "sharding", None)
    spread = sh is not None and len(sh.device_set) > 1
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sh if spread else None)


class _Recorder:
    """Stands where ``_named_jit`` stands: the programs it builds are
    remembered with the arguments of their first call."""

    def __init__(self, monkeypatch):
        self.calls: dict = {}
        orig = local._named_jit

        def named_jit(fn, name, **kw):
            jitted = orig(fn, name, **kw)

            def call(*args):
                if id(jitted) not in self.calls:
                    self.calls[id(jitted)] = (
                        name, jitted, jax.tree.map(_abstract, args))
                return jitted(*args)

            call.__name__ = jitted.__name__
            call.lower = jitted.lower
            return call

        monkeypatch.setattr(local, "_named_jit", named_jit)
        monkeypatch.setattr(mesh, "_named_jit", named_jit)

    def programs(self):
        """(name, compiled text) of every program built, compiled anew:
        the persistent cache's key leaves metadata out, so it may hold
        the same program under the scopes of an older tree."""
        from jax.experimental.compilation_cache import compilation_cache as cc

        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            for name, jitted, args in self.calls.values():
                yield name, jitted.lower(*args).compile().as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            cc.reset_cache()


def _faults(text: str) -> list:
    """Every instruction of a compiled program that breaks the grammar.
    Only an instruction whose ``op_name`` starts at the program
    (``jit(<program>)/…``) is one the device runs and the trace times;
    a comparator's or a reduction's body carries a bare primitive name
    and a parameter's copy the argument's name."""
    out = []
    for opcode, op_name in _INSTR.findall(text):
        if opcode == "parameter" or not op_name.startswith("jit("):
            continue
        scopes = kernel_profile.scopes_of(op_name)
        if scopes["operator"] is None:
            out.append(("no operator", opcode, op_name))
        if opcode in _MOVERS and scopes["kernel"] is None:
            out.append(("no kernel", opcode, op_name))
        for comp in op_name.split("/")[:-1]:
            ours = re.match(r"(op\d*|k|s):(.*)", comp)
            if ours and re.search(r"\d", ours.group(2)):
                out.append(("a digit in a scope", opcode, op_name))
    return out


def _check(runner, rec, template):
    runner.execute(QUERIES[template])
    programs = list(rec.programs())
    assert programs, "the statement dispatched no program"
    seen_kernel = False
    for name, text in programs:
        assert _INSTR.search(text), f"{name}: no op_name in the program"
        assert not _faults(text), (name, _faults(text)[:8])
        seen_kernel = seen_kernel or "/k:" in text
    # q06 is a filter and a global sum: no sort, gather, scatter or scan
    assert seen_kernel or template == "q06", (
        "no program of the statement holds a kernel scope")


@pytest.mark.parametrize("template", TEMPLATES)
def test_every_instruction_is_scoped_local(template, monkeypatch):
    rec = _Recorder(monkeypatch)
    _check(QueryRunner.tpch("tiny"), rec, template)


@pytest.mark.parametrize("template", TEMPLATES)
def test_every_instruction_is_scoped_mesh(template, monkeypatch):
    rec = _Recorder(monkeypatch)
    _check(QueryRunner.tpch("tiny", mesh=make_mesh(2)), rec, template)
    assert any(n.startswith("mesh_") for n, _, _ in rec.calls.values())


def test_a_program_without_an_operator_is_refused():
    with pytest.raises(KeyError):
        local._named_jit(lambda x: x, "mystery")


def test_scopes_of_reads_the_grammar():
    s = kernel_profile.scopes_of(
        "jit(join_count)/op:Join/jit(join_ranges)/k:join_ranges/"
        "k:searchsorted/k:merge_rank/k:packed_argsort/s:gather_high/gather:")
    assert s == {"operator": "Join", "scope": "op:Join",
                 "kernel": "packed_argsort", "site": "gather_high",
                 "primitive": "gather"}
    s = kernel_profile.scopes_of("jit(counted)/op12:Aggregate/cumsum")
    assert (s["operator"], s["scope"], s["kernel"], s["primitive"]) == (
        "Aggregate", "op12:Aggregate", None, "scan")
    s = kernel_profile.scopes_of("env['§2'][0]:")
    assert s["operator"] is None and s["primitive"] == "other"


# ---- the two decoders agree ---------------------------------------------------
#
# The benchmark imports nothing of the program and the program nothing
# of the benchmark: ``benchmarks/xplane_meta.py`` + ``readers/
# trace_scopes.py`` and ``kernel_profile.device_planes`` +
# ``attribute_device`` each decode the raw trace by themselves. This
# test holds them together, over the trace the benchmark keeps.

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


def test_the_operator_and_the_benchmark_read_a_chip_trace_alike(
        tmp_path, monkeypatch):
    path = tmp_path / "t.xplane.pb"
    with gzip.open(os.path.join(
            BENCH, "testdata", "fleet_short.xplane.pb.gz"), "rb") as fin, \
            open(path, "wb") as fout:
        shutil.copyfileobj(fin, fout)
    monkeypatch.syspath_prepend(BENCH)
    for name in ("trace_reduce", "xplane_meta"):
        monkeypatch.delitem(sys.modules, name, raising=False)
    spec = importlib.util.spec_from_file_location(
        "_trace_scopes", os.path.join(BENCH, "readers", "trace_scopes.py"))
    ts = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ts)
    theirs = ts.reduce(
        ts.trace_reduce.load(str(path)),
        ts.xplane_meta.device_metadata(str(path)), 0.0, 1e18, [])
    planes = kernel_profile.device_planes(str(path))
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    assert len(planes[0]["ops"]) == 9448
    ours = kernel_profile.attribute_device(planes)
    for axis, key in (("operator", "operators"), ("kernel", "kernels"),
                      ("primitive", "primitives")):
        want = {k: v / 1e3 for k, v in theirs["axes"][axis].items()}
        # ProfileData hands out whole nanoseconds, the file picoseconds:
        # up to a nanosecond an event
        assert ours[key] == pytest.approx(want, rel=1e-4, abs=0.5), axis
    assert ours["unscoped_us"] == pytest.approx(
        theirs["axes"]["operator"]["unscoped"] / 1e3, rel=1e-4)
    assert ours["scopes"]["op1:Aggregate"] > 0
    assert sum(ours["scopes"].values()) + ours["unscoped_us"] == pytest.approx(
        theirs["total_ns"] / 1e3, rel=1e-5)
    assert ours["devices"] == 1 and ours["events"] == 9448
    # a trace whose events carry no tf_op (a CPU's) is not this path's
    for md in planes[0]["metadata"].values():
        md["tf_op"] = None
    assert kernel_profile.attribute_device(planes) is None


_CACHE_PROBE = """
import re, sys
import jax, jax.numpy as jnp
from jax import monitoring
hits = []
monitoring.register_event_listener(
    lambda name, **kw: hits.append(name) if "cache_hit" in name else None)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
def prog(x):
    with jax.named_scope(sys.argv[1]):
        return jnp.flip(x * 2)
text = jax.jit(prog).lower(
    jax.ShapeDtypeStruct((4096,), jnp.int32)).compile().as_text()
print(len(hits), sorted(set(re.findall(r"op:[A-Za-z]+", text))))
"""


def test_the_compile_caches_key_leaves_the_scopes_out(tmp_path):
    """Why ``trino_tpu/__init__.py`` namespaces its default cache
    (``_CACHE_GEN``) and why a cache named by
    ``JAX_COMPILATION_CACHE_DIR`` must be fresh for a traced run of
    changed scopes: the same program under another scope hits the
    entry the first wrote and comes back under the first's scope."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    got = []
    for scope in ("op:First", "op:Second"):
        out = subprocess.run(
            [sys.executable, "-c", _CACHE_PROBE, scope], env=env,
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        got.append(out.stdout.strip().splitlines()[-1])
    assert got == ["0 ['op:First']", "1 ['op:First']"], got
    from trino_tpu import _CACHE_GEN

    assert _CACHE_GEN == "g3"  # bump it with the grammar
