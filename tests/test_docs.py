"""The documents against the program.

(a) Every ``--flag`` a document names is an option of a parser in the
    package, ``tools/``, ``chip_smoke.py``, ``quality.py``,
    ``benchmark/run.py`` or an example (XLA's own ``--xla_*`` pass).
(b) Every ``dir/.../name.py`` a document names exists, from the repo's
    root or the package's.
(c) Outside the history files nothing names the CPU-era benchmark
    program, its records or its tools: ``benchmark/run.py`` measures,
    ``PERF_LEDGER.jsonl`` records.
"""

import ast
import fnmatch
import os
import pathlib
import re

import pytest

from neural_networks_parallel_training_with_mpi_tpu import config

REPO = pathlib.Path(__file__).resolve().parent.parent
PKG = REPO / "neural_networks_parallel_training_with_mpi_tpu"
SKILL = ".claude/skills/verify/SKILL.md"

_FLAG = re.compile(r"(?<![\w-])--[A-Za-z][\w-]*[A-Za-z0-9]")
_PY_PATH = re.compile(r"(?<![\w./<>-])(?:[\w.-]+/)+[\w-]+\.py\b")
_HEREDOC = re.compile(r"python[^\n]*<<-?'?(\w+)'?\n(.*?)\n\1\n", re.S)


def _option_strings(source):
    """Option strings of every ``add_argument`` call in ``source``."""
    found = set()
    for node in ast.walk(ast.parse(source)):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument"):
            continue
        names = [a.value for a in node.args
                 if isinstance(a, ast.Constant) and isinstance(a.value, str)
                 and a.value.startswith("--")]
        found.update(names)
        if any(kw.arg == "action" and "BooleanOptionalAction"
               in ast.unparse(kw.value) for kw in node.keywords):
            found.update("--no-" + n[2:] for n in names)
    return found


@pytest.fixture(scope="module")
def known_flags():
    known = set(config.build_argparser()._option_string_actions)
    sources = [*PKG.rglob("*.py"), *(REPO / "tools").glob("*.py"),
               REPO / "chip_smoke.py", REPO / "quality.py",
               REPO / "benchmark" / "run.py"]
    for path in sources:
        known |= _option_strings(path.read_text())
    for path in (REPO / "examples").glob("*.sh"):
        for _, body in _HEREDOC.findall(path.read_text()):
            known |= _option_strings(body)
    return known


@pytest.mark.parametrize("doc", ["README.md", "DESIGN.md", "PARITY.md",
                                 "examples/README.md", SKILL])
def test_every_flag_a_document_names_exists(doc, known_flags):
    named = set(_FLAG.findall((REPO / doc).read_text()))
    unknown = {f for f in named - known_flags
               if not f.startswith("--xla_")}
    assert unknown == set()


@pytest.mark.parametrize("doc", ["README.md", "DESIGN.md", "PARITY.md",
                                 "PERF.md", SKILL])
def test_every_source_path_a_document_names_exists(doc):
    named = set(_PY_PATH.findall((REPO / doc).read_text()))
    missing = {p for p in named
               if not (REPO / p).is_file() and not (PKG / p).is_file()}
    assert missing == set()


def _committed_text_files():
    """Files under the repo that ``.gitignore`` does not list, read as
    text (the driver's checkout need not be a git repository)."""
    ignored = [line.strip().rstrip("/") for line in
               (REPO / ".gitignore").read_text().splitlines()
               if line.strip() and not line.startswith("#")] + [".git"]

    def kept(rel):
        return not any(fnmatch.fnmatch(rel.name, pat) or str(rel) == pat
                       for pat in ignored)

    for top, dirs, files in os.walk(REPO):
        here = pathlib.Path(top).relative_to(REPO)
        dirs[:] = sorted(d for d in dirs if kept(here / d))
        for name in sorted(files):
            if kept(here / name):
                try:
                    yield here / name, (REPO / here / name).read_text()
                except UnicodeDecodeError:
                    pass


def test_nothing_outside_the_history_names_the_retired_benchmark():
    history = {"CHANGES.md", "ROADMAP.md", "PERF.md", "ISSUE.md",
               "PERF_LEDGER.jsonl"}
    # spelled in pieces so that this file passes its own test
    retired = ["bench" + ".py", "BENCH" + "_", "MULTICHIP" + "_r0",
               "bench" + "_diff", "big_lm" + "_sweep", "big_lm" + "_attrib"]
    holds = [f"{rel}: {word}" for rel, text in _committed_text_files()
             if str(rel) not in history
             for word in retired if word in text]
    assert holds == []
