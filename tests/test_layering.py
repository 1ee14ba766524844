"""The repo's import graph, read from the source and never imported.

(a) Each sub-package of the program imports from its siblings only what
    its layer allows, or one of the back-edges that stood when this file
    was written (``BACK_EDGES``).  A new back-edge fails; so does a listed
    one that has gone: the list can only shrink.  Four modules that
    everything uses live in ``train/`` (the span tracer, the telemetry
    emitter, ``TrainState``, the exit codes with their supervisor), which
    is what most of the list is (ROADMAP D13).
(b) Nothing in the package imports the benchmark, a tool, a test or a
    root script.
(c) Every import of a repo-local module, lazy ones inside functions
    included, names a module that exists and, for ``from m import n``, a
    name that ``m`` defines: a deletion orphans nothing.
"""

import ast
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
PKG_NAME = "neural_networks_parallel_training_with_mpi_tpu"
PKG = REPO / PKG_NAME

ALLOWED = {
    "ops": {"utils"},
    "models": {"ops", "utils", "config"},
    "parallel": {"ops", "models", "utils", "config"},
    "data": {"parallel", "utils", "config"},
    "utils": {"config"},
    "train": {"ops", "models", "parallel", "data", "utils", "config"},
    "serve": {"models", "ops", "parallel", "utils", "config"},
    "rl": {"ops", "models", "parallel", "data", "utils", "train", "config"},
}

# importer -> what it names, one entry however often the module says it
BACK_EDGES = """
models.generate -> parallel.sharding.batch_sharding
models.generate -> parallel.sharding.replicated_sharding
models.generate_tp -> parallel.expert.moe_ffn_fn
models.generate_tp -> parallel.megatron
models.generate_tp -> parallel.pipeline.dense_layer_blocks
models.generate_tp -> parallel.spmd.sp_tp_param_specs
models.moe -> parallel.megatron.make_megatron_ops
models.transformer -> parallel.sequence.global_positions
models.transformer -> parallel.sequence.sequence_sharded_attention
parallel.data_parallel -> train.state.TrainState
parallel.data_parallel -> train.telemetry
parallel.distributed -> train.resilience.EXIT_PEER
parallel.distributed -> train.telemetry
parallel.expert -> train.state.TrainState
parallel.gspmd -> train.state.TrainState
parallel.gspmd -> train.telemetry
parallel.pipeline -> train.state.TrainState
parallel.spmd -> train.state.TrainState
parallel.spmd -> train.telemetry
parallel.update_sharding -> train.state.TrainState
parallel.update_sharding -> train.telemetry
serve.ctrlplane_driver -> train.trace
serve.fleet -> train.resilience.ChildSpec
serve.fleet -> train.resilience.EXIT_ANOMALY
serve.fleet -> train.resilience.EXIT_DECOMMISSION
serve.fleet -> train.resilience.GroupSupervisor
serve.fleet -> train.resilience.PREEMPT_GRACE_ENV
serve.fleet -> train.resilience.PREEMPT_NOTICE_ENV
serve.fleet -> train.resilience.read_preempt_notice
serve.fleet -> train.telemetry
serve.fleet -> train.trace
serve.paged_kv -> train.trace
serve.scheduler -> train.telemetry
serve.scheduler -> train.telemetry.Heartbeat
serve.scheduler -> train.trace
utils.chaos -> serve.autopilot.Autopilot
utils.chaos -> serve.autopilot.AutopilotConfig
utils.chaos -> serve.fleet.launch_fleet
utils.chaos -> serve.loadgen.run_fleet_closed_loop
utils.chaos -> serve.wal
utils.checkpoint -> train.state.TrainState
utils.checkpoint -> train.telemetry
utils.checkpoint -> train.trace
utils.compile_ledger -> train.trace
utils.faults -> train.resilience
utils.faults -> train.resilience.EXIT_PEER
utils.faults -> train.telemetry
""".split("\n")[1:-1]

ROOT_SCRIPTS = ("chip_smoke", "quality", "__graft_entry__")
# top-level names that mean this repo; `bench` stays on the list so that
# an import of the deleted program fails here and is not taken for a
# third-party module
LOCAL_ROOTS = (PKG_NAME, "benchmark", "tools", "tests", "bench") + ROOT_SCRIPTS


def _imports(tree, package):
    """(dotted module, imported name or None) of every import in ``tree``,
    relative ones resolved against ``package`` (a tuple of parts)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield tuple(alias.name.split(".")), None
        elif isinstance(node, ast.ImportFrom):
            base = ()
            if node.level:
                base = package[:len(package) - (node.level - 1)]
            module = base + tuple(node.module.split(".") if node.module
                                  else ())
            for alias in node.names:
                yield module, alias.name


def _package_of(path):
    rel = path.relative_to(REPO).with_suffix("")
    return rel.parts[:-1]


def _module_file(parts):
    path = REPO.joinpath(*parts)
    if path.with_suffix(".py").is_file():
        return path.with_suffix(".py")
    if (path / "__init__.py").is_file():
        return path / "__init__.py"
    return None


def _defined(path):
    """Names bound at a module's top level (through ``if`` / ``try`` /
    loops, not into functions or classes); None where ``import *`` or a
    module ``__getattr__`` makes the set open."""
    names = set()

    def bind(target):
        for node in ast.walk(target):
            if isinstance(node, ast.Name):
                names.add(node.id)

    def walk(body):
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                names.add(node.name)
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    bind(t)
            elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
                bind(node.target)
            elif isinstance(node, ast.Import):
                for a in node.names:
                    names.add(a.asname or a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom):
                for a in node.names:
                    names.add(a.asname or a.name)
            elif isinstance(node, (ast.For, ast.While, ast.If, ast.With,
                                   ast.Try)):
                if isinstance(node, ast.For):
                    bind(node.target)
                for field in ("body", "orelse", "finalbody"):
                    walk(getattr(node, field, []))
                for handler in getattr(node, "handlers", []):
                    walk(handler.body)

    walk(ast.parse(path.read_text()).body)
    return None if {"*", "__getattr__"} & names else names


def _unresolved(source, package, siblings=()):
    """Local imports in ``source`` that name no module or no name of it."""
    bad = []
    for module, name in _imports(ast.parse(source), package):
        if not module:
            continue
        if module[0] in siblings and module[0] not in LOCAL_ROOTS:
            module = package + module
        elif module[0] not in LOCAL_ROOTS:
            continue
        said = ".".join(module) + (f":{name}" if name else "")
        home = _module_file(module)
        if home is None and not REPO.joinpath(*module).is_dir():
            bad.append(said)
        elif name and name != "*" and _module_file(module + (name,)) is None:
            defined = _defined(home) if home else set()
            if defined is not None and name not in defined:
                bad.append(said)
    return bad


def _files(directory, pattern="*.py"):
    return sorted(p for p in (REPO / directory).rglob(pattern)
                  if "__pycache__" not in p.parts)


# --------------------------------------------------------------- (a), (b)

def _sibling_edges(sub):
    """``importer -> target`` for what sub-package ``sub`` imports from
    its siblings outside its allowed set."""
    found = set()
    for path in _files(f"{PKG_NAME}/{sub}"):
        rel = path.relative_to(PKG).with_suffix("")
        importer = ".".join(rel.parts)
        package = _package_of(path)
        for module, name in _imports(ast.parse(path.read_text()), package):
            if module[:1] != (PKG_NAME,) or len(module) + bool(name) < 2:
                continue
            target = module[1:] + ((name,) if name else ())
            if target[0] != sub and target[0] not in ALLOWED[sub]:
                found.add(f"{importer} -> {'.'.join(target)}")
    return found


@pytest.mark.parametrize("sub", sorted(ALLOWED))
def test_a_sub_package_imports_downward_or_by_a_listed_back_edge(sub):
    listed = {e for e in BACK_EDGES if e.startswith(sub + ".")}
    found = _sibling_edges(sub)
    assert found - listed == set(), "new back-edges"
    assert listed - found == set(), "gone: take them off BACK_EDGES"


def test_the_package_imports_nothing_built_on_it():
    outside = set(LOCAL_ROOTS) - {PKG_NAME}
    bad = [f"{path.relative_to(REPO)}: {'.'.join(module)}"
           for path in _files(PKG_NAME)
           for module, _ in _imports(ast.parse(path.read_text()),
                                     _package_of(path))
           if module and module[0] in outside]
    assert bad == []


# -------------------------------------------------------------------- (c)

def _unresolved_in(paths):
    bad = []
    for path in paths:
        # a script's own directory is on its path; a package's is not
        siblings = (() if PKG in path.parents
                    else {p.stem for p in path.parent.glob("*.py")})
        bad += [f"{path.relative_to(REPO)}: {said}" for said in
                _unresolved(path.read_text(), _package_of(path), siblings)]
    return bad


@pytest.mark.parametrize(
    "tool", [p.name for p in sorted((REPO / "tools").glob("*.py"))])
def test_a_tools_local_imports_resolve(tool):
    assert _unresolved_in([REPO / "tools" / tool]) == []


@pytest.mark.parametrize("where", ["root scripts", "package", "tests"])
def test_local_imports_resolve(where):
    paths = {"root scripts": [REPO / f"{s}.py" for s in ROOT_SCRIPTS],
             "package": _files(PKG_NAME), "tests": _files("tests")}[where]
    assert _unresolved_in(paths) == []


_HEREDOC = re.compile(r"python[^\n]*<<-?'?(\w+)'?\n(.*?)\n\1\n", re.S)
_RUN = re.compile(r"python3? +(?:-m +([\w.]+)|((?:tools/)?\w+\.py))")


def test_examples_local_imports_resolve():
    bad = []
    for path in _files("examples", "*.sh"):
        text = path.read_text()
        for body in _HEREDOC.findall(text):
            bad += [f"{path.name}: {said}"
                    for said in _unresolved(body[1], ())]
        commands = "\n".join(line for line in text.splitlines()
                             if not line.lstrip().startswith("#"))
        for module, script in _RUN.findall(commands):
            if module.split(".")[0] in LOCAL_ROOTS:
                if _module_file(tuple(module.split("."))) is None:
                    bad.append(f"{path.name}: -m {module}")
            elif script and not (REPO / script).is_file():
                bad.append(f"{path.name}: {script}")
    assert bad == []
