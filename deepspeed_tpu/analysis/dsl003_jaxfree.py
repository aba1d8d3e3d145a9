"""DSL003 — jax-free operator tools.

Originating incidents: PR 7 (fleet_dump quietly imported the
``deepspeed_tpu`` package — whose ``__init__`` pulls jax — until its
loader was rewritten to go by file path) and PR 9 (tools/router.py's
no-jax contract pinned with a fresh-interpreter subprocess).  The
operator tools must run on boxes with no jax install; one careless
``import`` anywhere in their closure breaks every one of them.

This rule replaces N per-tool subprocess asserts with ONE whole-graph
check: for each tool entry point it computes the static import closure —

- plain ``import`` / ``from ... import`` at any nesting (a lazy jax
  import still violates the operator-box contract; ``if TYPE_CHECKING:``
  blocks are skipped);
- ``importlib.import_module("<literal>")``;
- the file-path loader idiom (``spec_from_file_location``): ``*.py``
  string literals in the call (including constant ``os.path.join``
  parts) resolve to repo files WITHOUT triggering package ``__init__``s
  — that is the idiom's whole point;
- importing ``deepspeed_tpu.a.b`` the normal way adds every package
  ``__init__`` on the chain, which is how jax usually sneaks in —

and reports the full chain when the closure reaches a banned root
(``jax``/``jaxlib``/``flax``/``optax``) at the import that introduces it.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .astutil import const_str, tail_name
from .engine import FileContext, Finding, Project, Rule, register_rule

# the operator-tool entry points under tools/ that carry the no-jax
# contract (each states it in its docstring; dslint itself is one)
JAXFREE_TOOLS = ("router.py", "fleet_dump.py", "ckpt_verify.py",
                 "train_supervisor.py", "serve_supervisor.py",
                 "trace_report.py", "metrics_dump.py", "goodput_report.py",
                 "dslint.py")
BANNED_ROOTS = {"jax", "jaxlib", "flax", "optax"}
PACKAGE = "deepspeed_tpu"


def _guard_polarity(test: ast.AST):
    """Whether ``test`` being TRUE means "cannot newly import at runtime":

    - ``TYPE_CHECKING`` → True (the body never executes);
    - ``"pkg" in sys.modules`` / ``sys.modules.get(x) is not None`` →
      True (the PR 9 package-or-file-path loader idiom: the body only
      runs when the package is ALREADY imported, so it cannot newly drag
      jax onto an operator box);
    - negations flip; anything else → None (both branches are live).
    """
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        inner = _guard_polarity(test.operand)
        return None if inner is None else (not inner)
    if tail_name(test) in ("TYPE_CHECKING", "typing.TYPE_CHECKING"):
        return True
    if isinstance(test, ast.Compare) and len(test.ops) == 1:
        mentions = any(isinstance(s, ast.Attribute) and s.attr == "modules"
                       and tail_name(s.value) == "sys"
                       for s in ast.walk(test))
        if mentions:
            op = test.ops[0]
            if isinstance(op, ast.In):
                return True
            if isinstance(op, ast.NotIn):
                return False
            if isinstance(op, ast.IsNot):   # sys.modules.get(x) is not None
                return True
            if isinstance(op, ast.Is):      # sys.modules.get(x) is None
                return False
    return None


def _skipped_imports(tree: ast.AST) -> Set[ast.AST]:
    """Import nodes that cannot pull new modules at runtime — ONLY the
    dead side of a recognized guard is skipped: the body of a positive
    guard (``if TYPE_CHECKING:`` / ``if "pkg" in sys.modules:``), or the
    ``else`` of a negated one.  ``if "pkg" not in sys.modules: import
    jax`` runs exactly on the operator box and stays checked."""
    skip: Set[ast.AST] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.If):
            continue
        polarity = _guard_polarity(node.test)
        if polarity is None:
            continue
        dead = node.body if polarity else node.orelse
        for stmt in dead:
            for sub in ast.walk(stmt):
                skip.add(sub)
    return skip


def _module_to_rel(name: str, importer_rel: str, level: int,
                   root: str) -> List[str]:
    """Repo-relative candidate files a module name resolves to.

    Returns [] for stdlib/third-party.  Package imports include every
    ``__init__.py`` on the chain (they execute)."""
    out: List[str] = []
    if level:
        # relative import: resolve against the importer's directory
        base = os.path.dirname(importer_rel)
        for _ in range(level - 1):
            base = os.path.dirname(base)
        parts = [p for p in name.split(".") if p] if name else []
        target = "/".join([base] + parts) if base else "/".join(parts)
        for cand in (target + ".py", target + "/__init__.py"):
            if os.path.isfile(os.path.join(root, cand)):
                out.append(cand)
        return out
    parts = name.split(".")
    if parts[0] == PACKAGE:
        # executing a package import runs every __init__ on the chain
        for i in range(1, len(parts)):
            init = "/".join(parts[:i]) + "/__init__.py"
            if os.path.isfile(os.path.join(root, init)):
                out.append(init)
        leaf = "/".join(parts)
        for cand in (leaf + ".py", leaf + "/__init__.py"):
            if os.path.isfile(os.path.join(root, cand)):
                out.append(cand)
        return out
    # tools import their siblings bare (tools/ is put on sys.path)
    if importer_rel.startswith("tools/"):
        cand = "tools/" + parts[0] + ".py"
        if os.path.isfile(os.path.join(root, cand)):
            out.append(cand)
            return out
    # a bare module that happens to live at repo root (chip_smoke etc.)
    cand = parts[0] + ".py"
    if os.path.isfile(os.path.join(root, cand)):
        out.append(cand)
    return out


def _py_consts_in(node: ast.AST) -> List[str]:
    """``*.py`` path literals in one expression subtree: constant-tailed
    ``os.path.join`` calls, plus bare constants that are not join
    components (a lone ``"__init__.py"`` join part is not a path)."""
    consts: List[str] = []
    join_parts: set = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) and tail_name(sub.func) == "join":
            parts = [const_str(a) for a in sub.args]
            for a in sub.args:
                join_parts.add(id(a))
            if parts and parts[-1] and parts[-1].endswith(".py") \
                    and all(p is not None for p in parts[1:]):
                consts.append("/".join(p for p in parts if p is not None))
    for sub in ast.walk(node):
        s = const_str(sub)
        if s and s.endswith(".py") and id(sub) not in join_parts:
            consts.append(s)
    return consts


def _literal_py_paths(scope: ast.AST, importer_rel: str,
                      root: str) -> List[str]:
    """Repo files loaded via the file-path idiom
    (``spec_from_file_location``): literals inside the loader calls,
    plus — because the path is often built a few lines away — literals
    in assignments to any name that (transitively) feeds a loader call.
    A ``.py`` constant elsewhere in the file (an argv default, say) is
    NOT treated as loaded."""
    spec_calls = [n for n in ast.walk(scope)
                  if isinstance(n, ast.Call)
                  and tail_name(n.func) == "spec_from_file_location"]
    consts: List[str] = []
    relevant: set = set()
    for call in spec_calls:
        consts.extend(_py_consts_in(call))
        for sub in ast.walk(call):
            if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load):
                relevant.add(sub.id)
    assigns = [n for n in ast.walk(scope)
               if isinstance(n, ast.Assign) and len(n.targets) == 1
               and isinstance(n.targets[0], ast.Name)]
    changed = True
    while changed:
        changed = False
        for a in assigns:
            if a.targets[0].id in relevant:
                for sub in ast.walk(a.value):
                    if isinstance(sub, ast.Name) \
                            and isinstance(sub.ctx, ast.Load) \
                            and sub.id not in relevant:
                        relevant.add(sub.id)
                        changed = True
    for a in assigns:
        if a.targets[0].id in relevant:
            consts.extend(_py_consts_in(a.value))
    out: List[str] = []
    importer_dir = os.path.dirname(importer_rel)
    for c in consts:
        c = c.replace(os.sep, "/").lstrip("./")
        for base in ("", importer_dir, "tools", PACKAGE):
            cand = "/".join([p for p in (base, c) if p])
            if os.path.isfile(os.path.join(root, cand)):
                out.append(cand)
                break
        else:
            # suffix match anywhere under the package tree
            suffix = "/" + c
            for dirpath, dirnames, filenames in os.walk(
                    os.path.join(root, PACKAGE)):
                dirnames[:] = [d for d in dirnames if d != "__pycache__"]
                for fn in filenames:
                    full = os.path.join(dirpath, fn)
                    relc = os.path.relpath(full, root).replace(os.sep, "/")
                    if relc.endswith(suffix):
                        out.append(relc)
    return out


class _Edge:
    __slots__ = ("dest", "line", "end_line", "banned")

    def __init__(self, dest: Optional[str], line: int, end_line: int = 0,
                 banned: Optional[str] = None):
        self.dest = dest        # repo-relative file, or None for banned
        self.line = line
        self.end_line = end_line or line   # imports can span lines
        self.banned = banned    # banned root name when dest is None


def _edges(ctx: FileContext, root: str) -> List[_Edge]:
    """Outgoing import edges of one file."""
    skip = _skipped_imports(ctx.tree)
    edges: List[_Edge] = []

    def add_module(name: str, level: int, line: int,
                   end_line: int = 0) -> None:
        if not level and name.split(".")[0] in BANNED_ROOTS:
            edges.append(_Edge(None, line, end_line,
                               banned=name.split(".")[0]))
            return
        for rel in _module_to_rel(name, ctx.rel, level, root):
            edges.append(_Edge(rel, line, end_line))

    for node in ast.walk(ctx.tree):
        if node in skip:
            continue
        if isinstance(node, ast.Import):
            for alias in node.names:
                add_module(alias.name, 0, node.lineno,
                           node.end_lineno or node.lineno)
        elif isinstance(node, ast.ImportFrom):
            end = node.end_lineno or node.lineno
            add_module(node.module or "", node.level, node.lineno, end)
            if node.level:
                # ``from . import engine`` binds submodules by name
                base = (node.module + "." if node.module else "")
                for alias in node.names:
                    for rel in _module_to_rel(base + alias.name, ctx.rel,
                                              node.level, root):
                        edges.append(_Edge(rel, node.lineno, end))
            # ``from pkg import submodule`` may bind a module, not an
            # attribute; resolve those too (conservative: only when the
            # name is a file next to the package)
            if node.level == 0 and node.module \
                    and node.module.split(".")[0] == PACKAGE:
                for alias in node.names:
                    sub = node.module + "." + alias.name
                    for rel in _module_to_rel(sub, ctx.rel, 0, root):
                        if rel.endswith(alias.name + ".py") \
                                or rel.endswith(alias.name + "/__init__.py"):
                            edges.append(_Edge(rel, node.lineno, end))
        elif isinstance(node, ast.Call):
            t = tail_name(node.func)
            if t == "import_module" and node.args:
                name = const_str(node.args[0])
                if name:
                    add_module(name, 0, node.lineno,
                               node.end_lineno or node.lineno)
            elif t == "spec_from_file_location":
                for rel in _literal_py_paths(ctx.tree, ctx.rel, root):
                    edges.append(_Edge(rel, node.lineno,
                                       node.end_lineno or node.lineno))
    return edges


class JaxFreeToolsRule(Rule):
    id = "DSL003"
    title = "operator tools must not reach jax in their import closure"
    incident = ("PR 7/9 — fleet_dump imported the jax-pulling package "
                "__init__; per-tool subprocess asserts replaced by one "
                "whole-graph closure check")

    def check_project(self, project: Project) -> Iterable[Finding]:
        findings: List[Finding] = []
        for tool in JAXFREE_TOOLS:
            rel = "tools/" + tool
            ctx = project.context_for(rel)
            if ctx is None:
                continue
            findings.extend(self._check_tool(project, rel))
        return findings

    def _check_tool(self, project: Project, entry: str) -> List[Finding]:
        root = project.root
        # BFS with parent pointers; report once per (entry, banned edge)
        visited: Set[str] = {entry}
        parent: Dict[str, Tuple[str, int]] = {}
        queue: List[str] = [entry]
        findings: List[Finding] = []
        reported: Set[Tuple[str, int]] = set()

        def chain(rel: str) -> str:
            hops = [rel]
            while hops[-1] in parent:
                hops.append(parent[hops[-1]][0])
            return " <- ".join(hops)

        while queue:
            rel = queue.pop(0)
            ctx = project.context_for(rel)
            if ctx is None:
                continue
            for edge in _edges(ctx, root):
                # a line-level ``# dslint: disable=DSL003 -- reason`` on an
                # import PRUNES that edge: the annotation documents why the
                # import cannot run on the jax-less path (e.g. a lazy
                # import only reached from live-capture code)
                if ctx.suppressed(Finding(self.id, ctx.rel, edge.line, 0,
                                          "", end_line=edge.end_line)):
                    continue
                if edge.banned is not None:
                    # one finding per (tool, banned root): BFS order makes
                    # this the SHORTEST offending chain — fixing it either
                    # clears the tool or surfaces the next chain
                    key = (entry, edge.banned)
                    if key in reported:
                        continue
                    reported.add(key)
                    f = Finding(
                        self.id, ctx.rel, edge.line, 0,
                        f"jax-free tool {entry!r} reaches {edge.banned!r} "
                        f"via: {chain(rel)} — load repo modules by file "
                        f"path (the fleet_dump idiom) or make the import "
                        f"lazy behind the jax-needing call",
                        end_line=edge.end_line)
                    if not ctx.suppressed(f):
                        findings.append(f)
                elif edge.dest not in visited:
                    visited.add(edge.dest)
                    parent[edge.dest] = (rel, edge.line)
                    queue.append(edge.dest)
        return findings


register_rule(JaxFreeToolsRule())


# --- selftest fixtures (project trees, built by the selftest) --------------
SELFTEST_BAD_TREE = {
    "tools/router.py": "import helper\n",
    "tools/helper.py": "from deepspeed_tpu.monitor import metrics\n",
    "deepspeed_tpu/__init__.py": "import jax\n",
    "deepspeed_tpu/monitor/__init__.py": "",
    "deepspeed_tpu/monitor/metrics.py": "import json\n",
}

# the inverted loader guard: the import runs EXACTLY on the jax-less
# path — only the dead side of a guard may be skipped
SELFTEST_BAD_NEGATED_GUARD_TREE = {
    "tools/router.py": (
        "import sys\n"
        "if 'deepspeed_tpu' not in sys.modules:\n"
        "    import jax  # runs precisely on the operator box\n"
    ),
}

SELFTEST_GOOD_TREE = {
    "tools/router.py": (
        "import importlib.util, os\n"
        "spec = importlib.util.spec_from_file_location(\n"
        "    '_m', os.path.join(_R, 'deepspeed_tpu', 'monitor',"
        " 'metrics.py'))\n"
    ),
    "deepspeed_tpu/__init__.py": "import jax\n",
    "deepspeed_tpu/monitor/__init__.py": "",
    "deepspeed_tpu/monitor/metrics.py": "import json\n",
}
