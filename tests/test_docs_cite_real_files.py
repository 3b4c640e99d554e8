"""The documents that describe the tree as it is may cite only files
that are there.

A case per document: every repo-relative path it names in backticks (a
trailing ``:line`` or ``::test`` stripped) or runs with ``python`` /
``pytest`` must exist. A path of the upstream project is written
``upstream:<path>`` and is not checked. ``ROADMAP.md``, ``PERF.md`` and
``CHANGES.md`` are histories that rightly name files of the past, so
they are not cases.
"""

import os
import re
import subprocess

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = [
    "README.md",
    "BASELINE.md",
    ".claude/skills/verify/SKILL.md",
    ".github/workflows/verify.yml",
]

_EXTENSIONS = (
    ".py", ".md", ".json", ".jsonl", ".yml", ".yaml", ".ini", ".toml",
    ".txt", ".cfg", ".sh",
)
_TICKED = re.compile(r"`([^`\n]+)`")
#: what follows ``python`` / ``pytest`` on a command line
_COMMAND = re.compile(r"(?:^|[\s;&|(])(?:python3?|pytest)\s+([^\n#|;&]*)")
_SUFFIX = re.compile(r"(::[\w\[\]-]+)+$|:\d+(-\d+)?$|#[\w-]+$")
_PATH = re.compile(r"^\.?[\w-][\w.-]*(/[\w.-]+)*/?$")


def _tracked() -> set[str]:
    """Files git would commit, or every file on disk where the checkout
    is not a repository (the chip tool's copy)."""
    try:
        out = subprocess.run(
            ["git", "ls-files", "--cached", "--others", "--exclude-standard"],
            cwd=ROOT, capture_output=True, text=True, check=True,
        ).stdout.splitlines()
        return {p for p in out if os.path.exists(os.path.join(ROOT, p))}
    except (OSError, subprocess.CalledProcessError):
        found = set()
        for base, dirs, files in os.walk(ROOT):
            dirs[:] = [d for d in dirs if d not in (".git", "__pycache__")]
            for f in files:
                found.add(os.path.relpath(os.path.join(base, f), ROOT))
        return found


def _ignored_roots() -> set[str]:
    """First path components that ``.gitignore`` lists: what running
    the program leaves behind may be named though it is not committed."""
    roots = set()
    with open(os.path.join(ROOT, ".gitignore")) as fh:
        for line in fh:
            line = line.strip().strip("/")
            if line and not line.startswith("#") and "*" not in line:
                roots.add(line.split("/")[0])
    return roots


def _candidates(text: str):
    for span in _TICKED.findall(text):
        yield from span.split()
    for args in _COMMAND.findall(text):
        tokens = args.split()
        for i, tok in enumerate(tokens):
            if tok == "-m" and i + 1 < len(tokens):
                module = tokens[i + 1]
                if re.fullmatch(r"[\w.]+", module) and module != "pytest":
                    yield "module:" + module
            yield tok


def _cited_paths(text: str, top_level: set[str]):
    seen = set()
    for raw in _candidates(text):
        if raw.startswith("module:"):
            parts = raw[len("module:"):].split(".")
            if parts[0] in top_level:
                seen.add(("module", "/".join(parts)))
            continue
        tok = raw.strip("\"'(),;").rstrip(".:")
        tok = _SUFFIX.sub("", tok)
        if not _PATH.match(tok) or tok.startswith("/"):
            continue
        first = tok.split("/")[0]
        if "/" in tok.rstrip("/") and first in top_level:
            seen.add(("path", tok.rstrip("/")))
        elif tok.endswith(_EXTENSIONS):
            seen.add(("path", tok))
    return sorted(seen)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_cites_only_files_that_exist(document):
    tracked = _tracked()
    dirs = set()
    for path in tracked:
        while "/" in path:
            path = path.rsplit("/", 1)[0]
            dirs.add(path)
    basenames = {os.path.basename(p) for p in tracked}
    top_level = {p.split("/")[0] for p in tracked if "/" in p}
    ignored = _ignored_roots()
    with open(os.path.join(ROOT, document)) as fh:
        text = fh.read()

    def exists(kind: str, path: str) -> bool:
        if kind == "module":
            return path + ".py" in tracked or path in dirs
        if path.split("/")[0] in ignored:
            return True
        for base in ("", "trino_tpu/"):
            if base + path in tracked or base + path in dirs:
                return True
        return "/" not in path and path in basenames

    cited = _cited_paths(text, top_level)
    assert cited, f"{document} cites no file at all: the reader is broken"
    missing = [path for kind, path in cited if not exists(kind, path)]
    assert not missing, f"{document} cites files that are not there: {missing}"
