"""The shape of the repository, read from its source tree alone.

Three things PR 30 established and nothing else held: the documents name
only files that exist, the program's lower packages import nothing that
sits above them, and the environment variables the program reads are a
list somebody edits on purpose. Standard library only: no ``jax``, no
``photon_tpu`` import, nothing run.
"""

from __future__ import annotations

import ast
import functools
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The tree is the root's own files and what lies under these, found by
# walking them: a checkout may have no ``.git``, and a walk from the root
# would also find the ignored proof copy of a whole earlier tree
# (``_archive_check/``), deleted files and all.
TREE_DIRS = ("photon_tpu", "benchmark", "tests", ".github")


@functools.cache
def _tree() -> tuple[str, ...]:
    files = [f for f in os.listdir(ROOT)
             if os.path.isfile(os.path.join(ROOT, f))]
    for top in TREE_DIRS:
        for dirpath, dirnames, filenames in os.walk(os.path.join(ROOT, top)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            files += [os.path.relpath(os.path.join(dirpath, f), ROOT)
                      for f in filenames]
    return tuple(f.replace(os.sep, "/") for f in files)


# --------------------------------------------------------------------------
# (a) a document names only files that exist
# --------------------------------------------------------------------------

DOCUMENTS = (
    "README.md", "PERF.md", "ROADMAP.md", "ANALYSIS.md", "OBSERVABILITY.md",
    "SERVING.md", "DATA.md", "PILOT.md", "RESILIENCE.md", "PARITY.md",
    "PERFORMANCE.md",
)
FILE_ENDINGS = (".py", ".md", ".json", ".yml", ".c")

# Names that look like files of the tree and are not. Each entry says
# whose file it is; a file of this repo that was deleted never belongs
# here: repair the document.
NOT_OF_THE_TREE = frozenset({
    # beside the `model-configs` guide, outside the repo
    "workloads.md",
    # written by the program at run time, into a directory the user names
    "training-summary.json",   # cli/train.py
    "ingest-manifest.json",    # data/stream.py
    "ingest-cursor.json",
    "ingest-vocab.json",
    "ingest-sketch.json",      # obs/health.py
    "manifest.json",           # resilience/checkpoint.py
    "bundle.json",             # obs/fleet.py
    "pilot-state.json",        # pilot/
    "pilot-vocab.json",
    "pilot-health-sketch.json",
    "ring.json",
})

_TICKED = re.compile(r"`([^`\n]+)`")
_LINE_SUFFIX = re.compile(r":[0-9][0-9,\-]*$")


def _file_names(text: str) -> set[str]:
    """Back-ticked tokens that name a file: one of ``FILE_ENDINGS`` once a
    ``:line`` suffix is stripped, and no placeholder, glob or blank."""
    names = set()
    for token in _TICKED.findall(text):
        token = _LINE_SUFFIX.sub("", token)
        if token.endswith(FILE_ENDINGS) and not re.search(r"[<*{\s]", token):
            names.add(token)
    return names


def _read_document(doc: str) -> str:
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        text = f.read()
    if doc == "ROADMAP.md":
        # below the heading it tells history, as CHANGES.md does
        text = text.split("\n## Recent")[0]
    return text


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_files_that_exist(doc):
    tree = _tree()

    def in_tree(name: str) -> bool:
        # whole, or the tail of a path: `generator.py`, `obs/trace.py`
        name = name.removeprefix("./")
        return any(f == name or f.endswith("/" + name) for f in tree)

    missing = sorted(
        name for name in _file_names(_read_document(doc))
        if name not in NOT_OF_THE_TREE and not in_tree(name))
    assert not missing, (
        f"{doc} names files the tree does not hold: {missing}")


# --------------------------------------------------------------------------
# (b) a package imports no layer above it
# --------------------------------------------------------------------------

LOWER_PACKAGES = (
    "utils", "ops", "optim", "models", "data", "io", "evaluation",
    "algorithm", "parallel", "obs",
)
UPPER_LAYERS = (
    "analysis", "cli", "serve", "pilot", "estimators", "hyperparameter",
)

# The known debts (ROADMAP C8a): (importing file, imported module). The
# test fails when an entry no longer exists, so the list only shrinks.
KNOWN_UPWARD_IMPORTS = frozenset({
    ("photon_tpu/algorithm/fused_fit.py", "photon_tpu.analysis.costmodel"),
    ("photon_tpu/obs/ledger.py", "photon_tpu.analysis.costmodel"),
})


def _imported_modules(path: str) -> set[str]:
    """Every absolute module a file imports, function-level imports
    included; ``from photon_tpu import x`` counts as ``photon_tpu.x``."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    modules = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            modules.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, f"{path}: relative import"
            modules.update(f"{node.module}.{a.name}" for a in node.names)
    return modules


def _upward_imports(pkg: str) -> set[tuple[str, str]]:
    found = set()
    for rel in _tree():
        if not (rel.startswith(f"photon_tpu/{pkg}/") and rel.endswith(".py")):
            continue
        for module in _imported_modules(os.path.join(ROOT, rel)):
            parts = module.split(".")
            if module.startswith("photon_tpu.") and parts[1] in UPPER_LAYERS:
                found.add((rel, ".".join(parts[:3])))
    return found


@pytest.mark.parametrize("pkg", LOWER_PACKAGES)
def test_package_imports_no_layer_above_it(pkg):
    known = {e for e in KNOWN_UPWARD_IMPORTS
             if e[0].startswith(f"photon_tpu/{pkg}/")}
    found = _upward_imports(pkg)
    assert not found - known, (
        f"photon_tpu/{pkg} imports a layer above it: {sorted(found - known)}")
    assert not known - found, (
        f"repaired, so take it off KNOWN_UPWARD_IMPORTS: {sorted(known - found)}")


# --------------------------------------------------------------------------
# (c) the environment variables the program reads
# --------------------------------------------------------------------------

PHOTON_VARIABLES = frozenset({
    "PHOTON_NEWTON_KERNEL", "PHOTON_SEGMENT_KERNEL", "PHOTON_SERVE_KERNEL",
    "PHOTON_TPU_SERIAL_INGEST", "PHOTON_TPU_INGEST_THREADS",
    "PHOTON_TPU_TRANSFER_CHUNK_MB", "PHOTON_TPU_FAULT_PLAN",
    "PHOTON_NATIVE_CACHE", "PHOTON_RUN_ID", "PHOTON_FLEET_DIR",
    "PHOTON_DRYRUN_CHILD", "PHOTON_MULTICHIP_ROW",
})


def test_environment_variables_the_program_reads():
    """A new knob takes a deliberate edit of ``PHOTON_VARIABLES``
    (ROADMAP C3: each is an option tests and cells must cover)."""
    sources = ["chip_smoke.py", "__graft_entry__.py"] + [
        f for f in _tree()
        if f.startswith("photon_tpu/") and f.endswith((".py", ".c"))]
    found = set()
    for rel in sources:
        with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
            found.update(re.findall(r"PHOTON_[A-Z_]+", f.read()))
    assert found == PHOTON_VARIABLES
