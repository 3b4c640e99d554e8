"""Starting, watching and stopping the servers of a cell.

The benchmark's process is a client and a supervisor: it never imports
jax or trino_tpu. Every server is a child started with the argv a
deployment uses; the process that owns the chip gets no JAX_PLATFORMS
at all, a host-only role gets ``JAX_PLATFORMS=cpu`` for itself alone.
(The pattern is chip_smoke.py's, copied so that a later PR cannot
change the yardstick by changing that file.)
"""

from __future__ import annotations

import importlib.util
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_client():
    """The real client (trino_tpu/server/client.py is pure stdlib),
    loaded by path so that this process imports neither trino_tpu nor
    jax."""
    path = os.path.join(ROOT, "trino_tpu", "server", "client.py")
    spec = importlib.util.spec_from_file_location("_bench_client", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def child_env(platform: str | None) -> dict:
    """A child's environment: no JAX_PLATFORMS at all unless this child
    is explicitly a host-only role. BENCH_RUN is the driver's own and
    reaches no child."""
    env = os.environ.copy()
    for name in ("JAX_PLATFORMS", "XLA_FLAGS", "BENCH_RUN"):
        env.pop(name, None)
    if platform is not None:
        env["JAX_PLATFORMS"] = platform
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


class Child:
    """A child process: stdout/stderr drained to a log file by a thread
    (a full pipe must never block a server), lines awaited by prefix.
    ``stdin`` is a pipe, which the traced launcher reads commands from."""

    def __init__(self, name: str, argv: list[str], env: dict, logdir: str):
        self.name = name
        self.log_path = os.path.join(logdir, f"{name}.log")
        self.proc = subprocess.Popen(
            argv, env=env, cwd=ROOT, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        self.lines: list[str] = []
        self._cv = threading.Condition()
        self._t = threading.Thread(target=self._drain, daemon=True)
        self._t.start()

    def _drain(self):
        with open(self.log_path, "w") as fh:
            for line in self.proc.stdout:
                fh.write(line)
                fh.flush()
                with self._cv:
                    self.lines.append(line.rstrip("\n"))
                    self._cv.notify_all()
        with self._cv:
            self._cv.notify_all()

    def send(self, line: str) -> None:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def wait_line(self, prefix: str, timeout_s: float, start: int = 0) -> str:
        deadline = time.monotonic() + timeout_s
        seen = start
        with self._cv:
            while True:
                for line in self.lines[seen:]:
                    if line.startswith(prefix):
                        return line
                seen = len(self.lines)
                if self.proc.poll() is not None and not self._t.is_alive():
                    raise RuntimeError(
                        f"{self.name} exited rc={self.proc.returncode} "
                        f"before '{prefix}': {self.tail()}"
                    )
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TimeoutError(
                        f"{self.name}: no '{prefix}' in {timeout_s:.0f}s: "
                        f"{self.tail()}"
                    )
                self._cv.wait(min(left, 1.0))

    def tail(self, n: int = 12) -> str:
        return " | ".join(
            x[:300] for x in self.lines[-n:] if "cpu_aot_loader" not in x
        )

    def stop(self, timeout_s: float = 30.0) -> int | None:
        """SIGTERM, wait, SIGKILL; returns the exit code (None: had to
        be killed). Always waits until the process has ended."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
                return None
        self._t.join(timeout=5)
        return self.proc.returncode


def http_json(url: str, timeout: float = 30.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read())


def http_text(url: str, timeout: float = 30.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def prometheus(text: str) -> dict:
    """``/v1/metrics`` text -> {series name: value summed over labels}.
    Histograms keep their ``_sum`` and ``_count`` series; buckets are
    dropped."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name = line.split("{", 1)[0].split(" ", 1)[0]
        if name.endswith("_bucket"):
            continue
        try:
            out[name] = out.get(name, 0.0) + float(line.rsplit(" ", 1)[1])
        except ValueError:
            continue
    return out


class Servers:
    """The children of one configuration, started in the order its file
    lists them. Each entry of ``children``:

      role        name of the child (its log file)
      module      ``python -m <module>``
      args        argv after the module; ``{port}``, ``{schema}``,
                  ``{spool}`` and ``{uri:<role>}`` are filled in
      ready       the line prefix the child prints when it serves
      owns_chip   true for exactly one child; the others are host-only
                  roles and get JAX_PLATFORMS=cpu
      entry       true for the child the client talks to
    """

    def __init__(self, config: dict, schema: str, workdir: str,
                 traced: bool, own_platform: str | None = None):
        self.config = config
        self.schema = schema
        self.workdir = workdir
        self.traced = traced
        #: rehearsal only: the platform the chip's owner is pinned to
        self.own_platform = own_platform
        self.children: dict[str, Child] = {}
        self.uris: dict[str, str] = {}
        self.entry_uri = ""
        self.chip_uri = ""
        self.chip_child: Child | None = None

    def start(self, timeout_s: float = 600.0) -> None:
        spool = os.path.join(self.workdir, "spool")
        os.makedirs(spool, exist_ok=True)
        for spec in self.config["children"]:
            port = free_port()
            uri = f"http://127.0.0.1:{port}"

            def fill(arg: str) -> str:
                arg = arg.replace("{port}", str(port))
                arg = arg.replace("{schema}", self.schema)
                arg = arg.replace("{spool}", spool)
                for role, u in self.uris.items():
                    arg = arg.replace("{uri:%s}" % role, u)
                return arg

            args = [fill(a) for a in spec["args"]]
            owns = bool(spec.get("owns_chip"))
            if owns and self.traced:
                argv = [sys.executable,
                        os.path.join(HERE, "trace_launch.py"),
                        spec["module"], *args]
            else:
                argv = [sys.executable, "-m", spec["module"], *args]
            platform = self.own_platform if owns else "cpu"
            child = Child(spec["role"], argv, child_env(platform),
                          self.workdir)
            self.children[spec["role"]] = child
            child.wait_line(spec["ready"], timeout_s)
            self.uris[spec["role"]] = uri
            if owns:
                self.chip_uri = uri
                self.chip_child = child
            if spec.get("entry"):
                self.entry_uri = uri
        if not self.entry_uri or not self.chip_uri:
            raise RuntimeError(
                "configuration names no entry child or no chip owner"
            )

    def stop(self) -> list[str]:
        """Stops the children in reverse order; returns the roles that
        had to be killed."""
        killed = []
        for role in reversed(list(self.children)):
            if self.children[role].stop() is None:
                killed.append(role)
        return killed
