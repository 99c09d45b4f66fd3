"""Pass 4 — knob registry discipline.

Every ``PERSIA_*`` environment knob must route through the central
typed registry (``persia_tpu/knobs.py``). Rules:

- ``direct-env-read``: an ``os.environ.get``/``os.getenv``/
  ``os.environ[...]`` READ of a ``PERSIA_*`` literal outside knobs.py
  (writes are fine — launchers legitimately export knobs to children);
- ``unregistered-knob``: ``knobs.get``/``knobs.get_raw`` of a name not
  in the registry (typo guard; the runtime twin raises KeyError);
- ``import-time-read``: a knob read at module import time (module
  body, class body, or a function default) for a knob not registered
  ``import_time_safe`` — the freeze that made
  ``PERSIA_SKIP_CHECK_DATA`` ignore the environment for six PRs;
- ``unused-knob``: a registry entry whose name appears nowhere else in
  the tree (dead doc rot);
- ``stale-knob-docs``: docs/KNOBS.md does not match
  ``knobs.render_markdown()`` (only with ``check_docs=True``).
"""

import ast
import os
from typing import Dict, List, Optional, Set, Tuple

from tools.persialint.core import Finding, ParsedFile

PASS_ID = "knob-registry"

_KNOBS_MODULE_SUFFIX = "persia_tpu/knobs.py"
_GET_NAMES = {"get", "get_raw"}


def _const_str(node) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _is_environ(node: ast.AST) -> bool:
    """`os.environ` / `_os.environ` / bare `environ`."""
    if isinstance(node, ast.Attribute) and node.attr == "environ":
        return True
    if isinstance(node, ast.Name) and node.id == "environ":
        return True
    return False


def _load_registry(repo_root: str) -> Tuple[Set[str], Set[str]]:
    """(all names, import_time_safe names), parsed statically from
    knobs.py so the lint never imports the package under test."""
    path = os.path.join(repo_root, "persia_tpu", "knobs.py")
    names: Set[str] = set()
    safe: Set[str] = set()
    try:
        with open(path, "r", encoding="utf-8") as f:
            tree = ast.parse(f.read())
    except (OSError, SyntaxError):
        return names, safe
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fn = node.func
            fname = fn.id if isinstance(fn, ast.Name) else (
                fn.attr if isinstance(fn, ast.Attribute) else None)
            if fname in ("_k", "Knob") and node.args:
                name = _const_str(node.args[0])
                if name:
                    names.add(name)
                    for kw in node.keywords:
                        if (kw.arg == "import_time_safe"
                                and isinstance(kw.value, ast.Constant)
                                and kw.value.value):
                            safe.add(name)
    return names, safe


class _Visitor(ast.NodeVisitor):
    def __init__(self, pf: ParsedFile, registry: Set[str],
                 safe: Set[str], is_knobs_module: bool):
        self.pf = pf
        self.registry = registry
        self.safe = safe
        self.is_knobs_module = is_knobs_module
        self.findings: List[Finding] = []
        self.fn_depth = 0
        self.used: Set[str] = set()

    # -- scope tracking: fn_depth == 0 means import time ------------------
    def visit_FunctionDef(self, node):
        self._visit_fn(node)

    def visit_AsyncFunctionDef(self, node):
        self._visit_fn(node)

    def _visit_fn(self, node):
        # defaults evaluate at import time, body at call time
        for d in list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None]:
            self.visit(d)
        self.fn_depth += 1
        for stmt in node.body:
            self.visit(stmt)
        self.fn_depth -= 1

    def visit_Lambda(self, node):
        self.fn_depth += 1
        self.visit(node.body)
        self.fn_depth -= 1

    def _symbol(self) -> str:
        return "module" if self.fn_depth == 0 else "function"

    def visit_Call(self, node):
        # os.environ.get("PERSIA_X"[, default]) / os.getenv(...)
        fn = node.func
        env_read = None
        if isinstance(fn, ast.Attribute):
            if fn.attr == "get" and _is_environ(fn.value):
                env_read = node.args[0] if node.args else None
            elif fn.attr == "getenv":
                env_read = node.args[0] if node.args else None
            elif (fn.attr in _GET_NAMES and isinstance(fn.value, ast.Name)
                    and fn.value.id == "knobs"):
                self._check_knob_get(node)
        if env_read is not None:
            name = _const_str(env_read)
            if name and name.startswith("PERSIA_"):
                self.used.add(name)
                if not self.is_knobs_module:
                    self.findings.append(Finding(
                        PASS_ID, self.pf.relpath, node.lineno,
                        f"<knob {name}>",
                        f"direct os.environ read of {name} — route it "
                        "through persia_tpu.knobs (typed registry, "
                        "documented defaults, call-time reads)"))
        self.generic_visit(node)

    def _check_knob_get(self, node: ast.Call):
        name = _const_str(node.args[0]) if node.args else None
        if name is None:
            return
        self.used.add(name)
        if name not in self.registry:
            self.findings.append(Finding(
                PASS_ID, self.pf.relpath, node.lineno, f"<knob {name}>",
                f"knobs.get of unregistered name {name!r} — typo, or "
                "add it to persia_tpu/knobs.py REGISTRY"))
        elif self.fn_depth == 0 and name not in self.safe:
            self.findings.append(Finding(
                PASS_ID, self.pf.relpath, node.lineno, f"<knob {name}>",
                f"import-time read of {name} freezes it before "
                "launchers/tests can set the environment; read it "
                "lazily, or register it import_time_safe with a "
                "documented reason"))

    def visit_Subscript(self, node):
        # os.environ["PERSIA_X"] — only LOADS are reads
        if (_is_environ(node.value)
                and isinstance(node.ctx, ast.Load)):
            name = _const_str(node.slice)
            if name and name.startswith("PERSIA_"):
                self.used.add(name)
                if not self.is_knobs_module:
                    self.findings.append(Finding(
                        PASS_ID, self.pf.relpath, node.lineno,
                        f"<knob {name}>",
                        f"direct os.environ[{name!r}] read — route it "
                        "through persia_tpu.knobs"))
        self.generic_visit(node)


def run(files: List[ParsedFile], repo_root: str,
        check_docs: bool = False) -> List[Finding]:
    registry, safe = _load_registry(repo_root)
    findings: List[Finding] = []
    used: Set[str] = set()
    lint_root_has_knobs = bool(registry)
    for pf in files:
        is_knobs = pf.relpath.replace(os.sep, "/").endswith("knobs.py")
        v = _Visitor(pf, registry, safe, is_knobs)
        v.visit(pf.tree)
        findings.extend(v.findings)
        used |= v.used
        # any literal mention (argparse help, subprocess env dicts,
        # k8s manifests) counts as use for the dead-knob check
        for name in registry:
            if name in pf.source and not is_knobs:
                used.add(name)

    if lint_root_has_knobs:
        # knobs referenced only from tests/examples/bench still count:
        # scan the rest of the repo cheaply before calling one dead
        for name in sorted(registry - used):
            if not _mentioned_outside(repo_root, name):
                findings.append(Finding(
                    PASS_ID, "persia_tpu/knobs.py", 1, f"<knob {name}>",
                    f"registered knob {name} is referenced nowhere in "
                    "the tree — dead entry, remove it or wire it up"))

    if check_docs:
        findings.extend(_check_docs(repo_root))
    return findings


def _mentioned_outside(repo_root: str, name: str) -> bool:
    for sub in ("persia_tpu", "tests", "examples", "tools"):
        base = os.path.join(repo_root, sub)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in filenames:
                if not fn.endswith((".py", ".sh", ".yml", ".yaml", ".md")):
                    continue
                p = os.path.join(dirpath, fn)
                if p.endswith("knobs.py"):
                    continue
                try:
                    with open(p, "r", encoding="utf-8") as f:
                        if name in f.read():
                            return True
                except OSError:
                    pass
    for fn in ("README.md", "Dockerfile"):
        p = os.path.join(repo_root, fn)
        try:
            with open(p, "r", encoding="utf-8") as f:
                if name in f.read():
                    return True
        except OSError:
            pass
    return False


def _check_docs(repo_root: str) -> List[Finding]:
    """docs/KNOBS.md must equal knobs.render_markdown(). Renders by
    importing knobs.py as a standalone module file — no package import,
    so the lint works in a bare checkout."""
    import importlib.util

    knobs_path = os.path.join(repo_root, "persia_tpu", "knobs.py")
    docs_path = os.path.join(repo_root, "docs", "KNOBS.md")
    spec = importlib.util.spec_from_file_location("_persialint_knobs",
                                                  knobs_path)
    mod = importlib.util.module_from_spec(spec)
    try:
        spec.loader.exec_module(mod)
    except Exception as e:  # pragma: no cover — knobs.py broken
        return [Finding(PASS_ID, "persia_tpu/knobs.py", 1, "module",
                        f"cannot render knob docs: {e}")]
    want = mod.render_markdown()
    try:
        with open(docs_path, "r", encoding="utf-8") as f:
            have = f.read()
    except OSError:
        have = ""
    if have != want:
        return [Finding(
            PASS_ID, "docs/KNOBS.md", 1, "docs",
            "docs/KNOBS.md is stale — regenerate with "
            "`python -m tools.persialint --render-knobs`")]
    return []
