"""trino_tpu — a TPU-native distributed SQL query engine.

A brand-new engine with the capabilities of Trino (reference:
core/trino-main, core/trino-spi of the Java engine), re-designed
TPU-first:

- Columnar data lives as device-resident struct-of-arrays ``Page``s
  (JAX arrays + validity masks + host-side string dictionaries) — the
  analog of the reference's ``io.trino.spi.Page`` / sealed ``Block``
  hierarchy (SPI/Page.java:31, SPI/block/Block.java:26).
- Expressions compile to jitted JAX functions per (expression, layout) —
  the analog of runtime bytecode generation in
  core/trino-main/.../sql/gen/ExpressionCompiler.java:56.
- Relational operators are shape-stable XLA/Pallas computations
  (sort-based segment reduction for aggregation, searchsorted probes for
  hash joins) rather than per-row loops.
- Distribution is a stage DAG whose hash/broadcast exchanges lower to
  ``lax.all_to_all`` / ``all_gather`` over an ICI ``jax.sharding.Mesh``
  (replacing the reference's HTTP page shuffle,
  MAIN/operator/DirectExchangeClient.java:56).
"""

import os

import jax

# SQL semantics need 64-bit integers (BIGINT) and binary64 doubles. The
# engine owns the process the way a Trino server owns its JVM, so we
# enable x64 globally at import.
jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: query-shaped programs compile once
# per machine, not once per process (the analog of the reference's
# warmed JVM — its generated bytecode lives as long as the server).
#
# Where the cache lives is decided from outside, by jax's own variable:
# with JAX_COMPILATION_CACHE_DIR set, jax uses exactly that directory
# and this module sets ``jax_compilation_cache_dir`` nowhere. Unset,
# the cache is the fixed ``<checkout>/.jax_cache`` (the path is part of
# the cache's key, so a directory that moves never hits), under a
# per-host subdirectory: XLA:CPU AOT artifacts embed the COMPILING
# host's CPU features and SIGILL/SIGSEGV when loaded on a host without
# them (heterogeneous VM pools hit this), so the default path keys on a
# fingerprint of the host CPU. ``jax_enable_compilation_cache=False``
# (JAX_ENABLE_COMPILATION_CACHE=false) turns the cache off.


def _cpu_fingerprint() -> str:
    import hashlib

    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.startswith("flags"):
                    return hashlib.sha1(line.encode()).hexdigest()[:12]
    except OSError:
        pass
    import platform

    return hashlib.sha1(platform.processor().encode()).hexdigest()[:12]


#: cache-namespace generation of the default path. jax's cache key
#: deliberately strips location/metadata (so source-line edits don't
#: bust the cache) — which means changes that only manifest in HLO
#: metadata are invisible to it. Bump this when such metadata becomes
#: load-bearing: g2 = operator ``jax.named_scope`` labels, which
#: kernel-profile attribution reads back out of cached executables' HLO;
#: g3 = the whole scope grammar (``op:``, ``k:``, ``s:``; exec/kernels.py),
#: which a device trace hands back as ``tf_op`` — an executable out of
#: an older cache would be traced under the scopes it was compiled with.
#: A cache named by JAX_COMPILATION_CACHE_DIR has no such namespace:
#: whoever sets the variable points it at a fresh directory.
_CACHE_GEN = "g3"

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache", f"{_cpu_fingerprint()}-{_CACHE_GEN}",
        ),
    )
# persist EVERY program: the warm-process target is compile delta <= 1
# for a known operator mix, and the many sub-0.5s helper programs
# (compactions, dynamic filters) would otherwise recompile in every
# process forever
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

__version__ = "0.1.0"

from trino_tpu.types import (  # noqa: E402
    BIGINT,
    BOOLEAN,
    DATE,
    DOUBLE,
    INTEGER,
    REAL,
    SMALLINT,
    TINYINT,
    VARCHAR,
    DecimalType,
    DataType,
)
from trino_tpu.page import Column, Page  # noqa: E402

__all__ = [
    "BIGINT",
    "BOOLEAN",
    "DATE",
    "DOUBLE",
    "INTEGER",
    "REAL",
    "SMALLINT",
    "TINYINT",
    "VARCHAR",
    "DecimalType",
    "DataType",
    "Column",
    "Page",
]
