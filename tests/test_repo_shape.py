"""The shape of the tree, held by tests: what imports what, which files the
documents name, which policy keys have a reader, which ``RSDL_*`` names exist.

Stdlib and ``ast`` only; nothing of the package is imported. Each test is an
invariant a change could break without any other test noticing.
"""

import ast
import functools
import glob
import os
import re

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "ray_shuffling_data_loader_tpu"
PACKAGE_DIR = os.path.join(REPO_ROOT, PACKAGE)


def _read(path):
    with open(path, encoding="utf-8") as f:
        return f.read()


@functools.lru_cache(maxsize=None)
def _ignored_dirs():
    """Directory names ``.gitignore`` lists (``name/``), plus ``.git``."""
    names = {".git"}
    for line in _read(os.path.join(REPO_ROOT, ".gitignore")).splitlines():
        line = line.strip()
        if line.endswith("/") and "*" not in line:
            names.add(line.rstrip("/"))
    return frozenset(names)


@functools.lru_cache(maxsize=None)
def _tree_files():
    """Every file of the tree that git could commit, relative to the root."""
    out = []
    for root, dirs, files in os.walk(REPO_ROOT):
        dirs[:] = [d for d in dirs if d not in _ignored_dirs()]
        rel = os.path.relpath(root, REPO_ROOT)
        out.extend(os.path.normpath(os.path.join(rel, f)) for f in files)
    return tuple(sorted(out))


def _python_files(*roots):
    """The tree's ``.py`` files at or under ``roots``, as absolute paths."""
    return [os.path.join(REPO_ROOT, f) for f in _tree_files()
            if f.endswith(".py")
            and any(f == r or f.startswith(r + os.sep) for r in roots)]


# ---------------------------------------------------------------------------
# The program does not know its benchmark
# ---------------------------------------------------------------------------

#: Top-level names that stand above the package: the benchmark, the
#: reference's CLI, the chip's smoke run, the tools, the tests, the driver's
#: hook. They import the package; the package imports none of them.
ABOVE_THE_PROGRAM = frozenset({"chipbench", "benchmarks", "chip_smoke",
                               "tools", "tests", "__graft_entry__"})

SUBPACKAGES = sorted(
    name for name in os.listdir(PACKAGE_DIR)
    if os.path.isfile(os.path.join(PACKAGE_DIR, name, "__init__.py")))


def _imported_top_levels(path):
    for node in ast.walk(ast.parse(_read(path), filename=path)):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name.split(".")[0], node.lineno
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            yield (node.module or "").split(".")[0], node.lineno


@pytest.mark.parametrize("subpackage", ["<root modules>"] + SUBPACKAGES)
def test_program_imports_nothing_above_it(subpackage):
    if subpackage == "<root modules>":
        paths = sorted(glob.glob(os.path.join(PACKAGE_DIR, "*.py")))
    else:
        paths = _python_files(os.path.join(PACKAGE, subpackage))
    assert paths, subpackage
    found = [f"{os.path.relpath(path, REPO_ROOT)}:{line} imports {name}"
             for path in paths
             for name, line in _imported_top_levels(path)
             if name in ABOVE_THE_PROGRAM]
    assert not found, "\n".join(found)


# ---------------------------------------------------------------------------
# The dry run runs the sharded steps and stops
# ---------------------------------------------------------------------------

#: What ``__graft_entry__.py`` may import of the package: the sharded path
#: (models and ops over parallel's mesh and trainer, fed by jax_dataset from
#: files data_generation could have written) and what the loader-fed step
#: reads of its supervision: the watchdog, its counters, the exposition.
#: A control plane's scene belongs to that plane's file under tests/.
DRY_RUN_IMPORTS = frozenset({
    "models", "parallel", "ops", "jax_dataset", "data_generation",
    "stats", "runtime.watchdog", "runtime.metrics", "runtime.telemetry"})


def _package_imports(path):
    """``(dotted name under the package, line)`` of every name ``path``
    imports from it: ``from pkg.runtime import health`` gives
    ``runtime.health``, ``from pkg.models.dlrm import init``
    ``models.dlrm.init``."""
    for node in ast.walk(ast.parse(_read(path), filename=path)):
        if isinstance(node, ast.ImportFrom) and node.level == 0 and \
                (node.module or "").split(".")[0] == PACKAGE:
            under = (node.module or "").split(".")[1:]
            for alias in node.names:
                yield ".".join(under + [alias.name]), node.lineno
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.split(".")[0] == PACKAGE:
                    yield ".".join(alias.name.split(".")[1:]), node.lineno


def test_the_dry_run_imports_only_the_sharded_path():
    path = os.path.join(REPO_ROOT, "__graft_entry__.py")
    found = list(_package_imports(path))
    assert found, "the dry run imports nothing of the package"
    beyond = [f"__graft_entry__.py:{line} imports {module}"
              for module, line in found
              if not any(module == allowed or module.startswith(allowed + ".")
                         for allowed in DRY_RUN_IMPORTS)]
    assert not beyond, (
        "the dry run holds the steps that depend on the mesh; a control "
        "plane's scene goes to its own test file:\n" + "\n".join(beyond))


# ---------------------------------------------------------------------------
# Documents name only files that exist
# ---------------------------------------------------------------------------

DOCS = (["README.md", "PARITY.md", ".claude/skills/verify/SKILL.md",
         "format.sh"]
        + sorted(os.path.relpath(p, REPO_ROOT) for p in glob.glob(
            os.path.join(REPO_ROOT, "examples", "*.md"))))

_PATH_RE = re.compile(
    r"(?<![\w/<>*{}$~-])([\w.-]+(?:/[\w.-]+)*\.(?:py|sh|json))(?![\w/*-])")

#: Names a document may show though the tree does not hold them: files a
#: run writes or takes as an argument (they exist after the run), and the
#: reference's own file that PARITY.md and the README set beside this repo's.
NOT_OF_THE_TREE = frozenset({
    "capsule.json", "history.json", "policy.json",  # an incident capsule
    "hist.json",          # tools/rsdl_report.py --history <slice>
    "trace.json",         # tools/rsdl_trace.py --perfetto <out>
    "plan.json",          # tools/rsdl_plan.py render <dumped plan>
    "lint.json", "order-graph.json",  # examples/static_analysis.md outputs
    "ray_torch_shuffle.py",  # the reference's example (SURVEY.md)
})


def _named_paths(text):
    """Every word of ``text`` that ends in ``.py``, ``.sh`` or ``.json``,
    back-quoted, in a command or in prose; words of a URL and words with a
    placeholder (``<pid>``, ``*``, ``{a,b}``, ``$VAR``) are not paths of
    the tree. ``./x`` and the README's ``.../x`` are ``x``."""
    for match in _PATH_RE.finditer(text):
        word_start = text.rfind(" ", 0, match.start(1)) + 1
        if "://" in text[word_start:match.start(1)]:
            continue
        name = match.group(1)
        while name.startswith(("./", ".../")):
            name = name.split("/", 1)[1]
        yield name


@pytest.mark.parametrize("doc", DOCS)
def test_docs_name_only_files_that_exist(doc):
    missing = []
    for name in sorted(set(_named_paths(_read(os.path.join(REPO_ROOT,
                                                          doc))))):
        if name.split("/")[0] in _ignored_dirs() or name in NOT_OF_THE_TREE:
            continue
        # A document may name a file from the root, from its own directory
        # or from inside the package (``runtime/policy.py``, ``shuffle.py``).
        if not any(f == name or f.endswith("/" + name)
                   for f in _tree_files()):
            missing.append(name)
    assert not missing, f"{doc} names files the tree does not hold: {missing}"


# ---------------------------------------------------------------------------
# Every policy key has a reader; every RSDL_* name is a key or is listed
# ---------------------------------------------------------------------------


def _policy_keys():
    tree = ast.parse(_read(os.path.join(PACKAGE_DIR, "runtime",
                                        "policy.py")))
    for node in tree.body:
        if isinstance(node, ast.AnnAssign) and \
                getattr(node.target, "id", None) == "_KEYS":
            return [key.value for key in node.value.keys]
    raise AssertionError("runtime/policy.py has no _KEYS dict literal")


def _string_args(call):
    return [a.value for a in call.args
            if isinstance(a, ast.Constant) and isinstance(a.value, str)]


def _keys_read(path, keys):
    """Keys ``path`` reads: ``resolve(component, "key")`` under any name
    that ends in ``resolve`` (``policy.resolve``, a detector's
    ``self._resolve``) or the local ``res("key", ...)`` helper three
    modules wrap it in, and ``<resolve_all result>["key"]``."""
    read = set()
    for node in ast.walk(ast.parse(_read(path), filename=path)):
        if isinstance(node, ast.Call):
            func = node.func
            name = getattr(func, "attr", getattr(func, "id", ""))
            if name.endswith("resolve") or name == "res":
                read.update(k for k in _string_args(node) if k in keys)
        elif isinstance(node, ast.Subscript) and \
                isinstance(node.slice, ast.Constant) and \
                node.slice.value in keys and \
                "policy" in ast.unparse(node.value):
            read.add(node.slice.value)
    return read


def test_every_policy_key_has_a_reader():
    keys = set(_policy_keys())
    read = set()
    for path in _python_files(PACKAGE, "chip_smoke.py", "tools"):
        if path.endswith(os.path.join("runtime", "policy.py")):
            continue
        read |= _keys_read(path, keys)
    unread = sorted(keys - read)
    assert not unread, (
        f"{len(unread)} of {len(keys)} policy keys are resolved nowhere in "
        f"the package, chip_smoke.py or tools/ (a knob to delete): {unread}")


#: ``RSDL_*`` names that are not ``RSDL_<KEY>`` / ``RSDL_<COMPONENT>_<KEY>``
#: of a policy key, each with what reads it. This list's length beside the
#: number of policy keys is the tree's knob census (ROADMAP.md C8).
NAMES_OUTSIDE_POLICY = {
    "RSDL_CHAOS_SPEC": "runtime/faults.py, read at import and by reload",
    "RSDL_CHAOS_SEED": "runtime/faults.py",
    "RSDL_FAULTS_SPEC": "runtime/faults.py, alias of RSDL_CHAOS_SPEC",
    "RSDL_FAULTS_SEED": "runtime/faults.py, alias of RSDL_CHAOS_SEED",
    "RSDL_HOSTS": "examples/jax_train_shuffle.py, the hosts of a slice",
    "RSDL_LOCKSAN": "tests/conftest.py installs runtime/locksan.py",
    "RSDL_LOCKSAN_OUT": "runtime/locksan.py, where the order graph goes",
    "RSDL_LOCKSAN_SLOW_MS": "runtime/locksan.py",
    "RSDL_LOCKSAN_SUITE": "format.sh, the archival run of the suite",
    "RSDL_TELEMETRY_SIGUSR1": "runtime/telemetry.py, at import",
    "RSDL_TPU_DISABLE_NATIVE": "native/__init__.py, native/image.py",
    "RSDL_TPU_LOG_LEVEL": "utils/logger.py",
    "RSDL_EEOF_MID_MESSAGE": "no knob: a constant of shuffle_native.cpp",
}

#: A whole name: ``RSDL_SLO_*`` and ``RSDL_<KEY>`` name families, not knobs.
_NAME_RE = re.compile(r"\bRSDL_[A-Z0-9_]*[A-Z0-9](?![A-Z0-9_*<])")

#: Histories speak of names long gone; SURVEY.md speaks of the reference.
_HISTORIES = frozenset({"PERF.md", "CHANGES.md", "ROADMAP.md", "ISSUE.md",
                        "PERF_LEDGER.jsonl", "SURVEY.md"})


def _rsdl_names():
    """``{name: first file that holds it}`` over the tree's text outside
    the tests and the histories."""
    names = {}
    for rel in _tree_files():
        if rel.split(os.sep)[0] == "tests" or rel in _HISTORIES or \
                not rel.endswith((".py", ".md", ".sh", ".json", ".cpp")):
            continue
        for name in _NAME_RE.findall(_read(os.path.join(REPO_ROOT, rel))):
            names.setdefault(name, rel)
    return names


def test_every_rsdl_env_name_is_a_policy_key_or_listed():
    suffixes = {key.upper() for key in _policy_keys()}

    def is_policy_name(name):
        rest = name[len("RSDL_"):]
        return any(rest == s or rest.endswith("_" + s) for s in suffixes)

    names = _rsdl_names()
    unknown = sorted(n for n in names
                     if not is_policy_name(n)
                     and n not in NAMES_OUTSIDE_POLICY)
    assert not unknown, (
        "RSDL_* names that are neither a policy key's nor listed in "
        "NAMES_OUTSIDE_POLICY: "
        + ", ".join(f"{n} ({names[n]})" for n in unknown))
    stale = sorted(n for n in NAMES_OUTSIDE_POLICY if n not in names)
    assert not stale, f"listed but no longer in the tree: {stale}"
