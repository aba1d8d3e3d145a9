"""dslint engine: file walking, suppression parsing, rule running.

Stdlib-``ast`` only — this module (and every rule module) must be
importable WITHOUT jax, because ``tools/dslint.py`` loads the package by
file path on operator boxes and in pre-commit hooks (the
fleet_dump/ckpt_verify idiom).  Do not add package-absolute imports here:
``deepspeed_tpu/__init__`` pulls jax, which is exactly the class of
regression rule DSL003 exists to catch.

Suppression syntax (checked, not free-form):

    x = risky()  # dslint: disable=DSL002 -- <why this site is safe>
    # dslint: disable-file=DSL004 -- <why this whole file is exempt>

A ``disable`` without the `` -- reason`` tail, or naming an unknown rule,
is itself a finding (DSL000): the incident log is the point — a
suppression that doesn't say WHY rots into cargo cult.  ``disable``
applies to the physical lines its statement spans; ``disable-file``
applies to the whole file.  ``# dslint: hot`` on a ``def`` line opts that
function into the DSL002 hot-zone set without touching the rule config.
"""

from __future__ import annotations

import ast
import io
import os
import tokenize
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

__all__ = ["Finding", "FileContext", "Project", "Rule", "run_paths",
           "iter_python_files", "RULES", "register_rule", "rule_ids",
           "META_RULE"]

META_RULE = "DSL000"   # suppression hygiene (always on)

# populated by the rule modules at import time (see __init__.py)
RULES: List["Rule"] = []


def register_rule(rule: "Rule") -> "Rule":
    RULES.append(rule)
    return rule


def rule_ids() -> Set[str]:
    return {r.id for r in RULES} | {META_RULE}


@dataclass
class Finding:
    rule: str
    path: str            # as scanned (repo-relative when run from root)
    line: int
    col: int
    message: str
    end_line: int = 0    # last physical line of the flagged node

    def __post_init__(self):
        if not self.end_line:
            self.end_line = self.line

    def to_dict(self) -> dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "col": self.col, "message": self.message}

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"

    def sort_key(self):
        return (self.path, self.line, self.col, self.rule)


_DIRECTIVE = "dslint:"


@dataclass
class _Suppression:
    line: int
    rules: Tuple[str, ...]
    reason: str
    file_level: bool


class FileContext:
    """One parsed source file plus its dslint comment directives."""

    def __init__(self, path: str, rel: str, source: str):
        self.path = path
        self.rel = rel.replace(os.sep, "/")
        self.source = source
        self.tree = ast.parse(source, filename=path)
        self.line_suppressions: Dict[int, Set[str]] = {}
        self.file_suppressions: Set[str] = set()
        self.hot_lines: Set[int] = set()
        self.directive_findings: List[Finding] = []
        self._parse_directives()

    # -- comment directives --------------------------------------------
    def _parse_directives(self) -> None:
        try:
            tokens = tokenize.generate_tokens(io.StringIO(self.source).readline)
            comments = [(t.start[0], t.string) for t in tokens
                        if t.type == tokenize.COMMENT]
        except tokenize.TokenError:  # pragma: no cover - ast parsed already
            comments = []
        known = rule_ids()
        for line, text in comments:
            body = text.lstrip("#").strip()
            if not body.startswith(_DIRECTIVE):
                continue
            directive = body[len(_DIRECTIVE):].strip()
            if directive == "hot":
                self.hot_lines.add(line)
                continue
            kind, _, rest = directive.partition("=")
            kind = kind.strip()
            if kind not in ("disable", "disable-file"):
                self.directive_findings.append(Finding(
                    META_RULE, self.rel, line, 0,
                    f"unknown dslint directive {kind!r} (expected "
                    f"disable / disable-file / hot)"))
                continue
            spec, sep, reason = rest.partition("--")
            rules = tuple(r.strip() for r in spec.split(",") if r.strip())
            reason = reason.strip()
            if not rules:
                self.directive_findings.append(Finding(
                    META_RULE, self.rel, line, 0,
                    "dslint disable names no rules"))
                continue
            bad = [r for r in rules if r not in known]
            if bad:
                self.directive_findings.append(Finding(
                    META_RULE, self.rel, line, 0,
                    f"dslint disable names unknown rule(s) {', '.join(bad)}"))
                continue
            if not sep or not reason:
                self.directive_findings.append(Finding(
                    META_RULE, self.rel, line, 0,
                    "dslint disable without a justification: write "
                    "'# dslint: disable=RULE -- <reason>'"))
                continue
            if kind == "disable-file":
                self.file_suppressions.update(rules)
            else:
                self.line_suppressions.setdefault(line, set()).update(rules)

    # -- suppression check ---------------------------------------------
    def suppressed(self, finding: Finding) -> bool:
        if finding.rule in self.file_suppressions:
            return True
        for line in range(finding.line, max(finding.line,
                                            finding.end_line) + 1):
            if finding.rule in self.line_suppressions.get(line, ()):
                return True
        return False


class Project:
    """The full scanned file set plus the repo root (for whole-project
    rules: DSL003's import closure, DSL004's docs cross-check)."""

    def __init__(self, root: str, files: Sequence[FileContext]):
        self.root = os.path.abspath(root)
        self.files = list(files)
        self.by_rel: Dict[str, FileContext] = {f.rel: f for f in self.files}

    def context_for(self, rel: str) -> Optional[FileContext]:
        """The scanned context for a repo-relative path; parses the file
        fresh when it exists on disk but was outside the scan set (an
        import-closure node still gets local suppressions honored)."""
        rel = rel.replace(os.sep, "/")
        ctx = self.by_rel.get(rel)
        if ctx is not None:
            return ctx
        path = os.path.join(self.root, rel)
        if os.path.isfile(path):
            try:
                with open(path, encoding="utf-8") as fh:
                    ctx = FileContext(path, rel, fh.read())
            except (SyntaxError, UnicodeDecodeError, ValueError):
                return None
            self.by_rel[rel] = ctx
            return ctx
        return None


class Rule:
    """Base rule.  Subclasses set ``id``/``title``/``incident`` and
    implement ``check_file`` and/or ``check_project``."""

    id = "DSL???"
    title = ""
    incident = ""      # the originating failure (docs/LINT.md pulls this)

    def check_file(self, ctx: FileContext,
                   project: "Project") -> Iterable[Finding]:
        return ()

    def check_project(self, project: "Project") -> Iterable[Finding]:
        return ()


def iter_python_files(paths: Sequence[str], root: str) -> List[Tuple[str, str]]:
    """Expand files/dirs into (abspath, relpath) pairs, skipping caches
    and build output."""
    out: List[Tuple[str, str]] = []
    seen: Set[str] = set()
    skip_dirs = {"__pycache__", ".git", "build", ".eggs", "node_modules"}
    for p in paths:
        ap = p if os.path.isabs(p) else os.path.join(root, p)
        ap = os.path.abspath(ap)
        if os.path.isfile(ap):
            candidates = [ap]
        elif os.path.isdir(ap):
            candidates = []
            for dirpath, dirnames, filenames in os.walk(ap):
                dirnames[:] = sorted(d for d in dirnames
                                     if d not in skip_dirs)
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        candidates.append(os.path.join(dirpath, fn))
        else:
            raise FileNotFoundError(f"dslint: no such path: {p}")
        for c in candidates:
            if c in seen:
                continue
            seen.add(c)
            rel = os.path.relpath(c, root)
            out.append((c, rel))
    return out


def load_context(path: str, rel: str) -> Optional[FileContext]:
    with open(path, encoding="utf-8") as fh:
        source = fh.read()
    return FileContext(path, rel, source)


def run_paths(paths: Sequence[str], root: Optional[str] = None,
              rules: Optional[Sequence[Rule]] = None,
              ) -> Tuple[List[Finding], Project]:
    """Lint ``paths`` (files or directories).  Returns the surviving
    (non-suppressed) findings sorted by location, plus the Project for
    callers that want the file census."""
    root = os.path.abspath(root or os.getcwd())
    active = list(rules if rules is not None else RULES)
    contexts: List[FileContext] = []
    findings: List[Finding] = []
    for path, rel in iter_python_files(paths, root):
        try:
            ctx = load_context(path, rel)
        except SyntaxError as exc:
            findings.append(Finding(META_RULE, rel.replace(os.sep, "/"),
                                    exc.lineno or 1, 0,
                                    f"syntax error: {exc.msg}"))
            continue
        except (UnicodeDecodeError, ValueError) as exc:
            # non-UTF-8 bytes / embedded NULs: a finding, not a crash
            findings.append(Finding(META_RULE, rel.replace(os.sep, "/"),
                                    1, 0, f"unparseable source: {exc}"))
            continue
        contexts.append(ctx)
    project = Project(root, contexts)
    for ctx in contexts:
        findings.extend(ctx.directive_findings)   # never suppressible
        for rule in active:
            for f in rule.check_file(ctx, project):
                if not ctx.suppressed(f):
                    findings.append(f)
    for rule in active:
        for f in rule.check_project(project):
            ctx = project.context_for(f.path)
            if ctx is None or not ctx.suppressed(f):
                findings.append(f)
    # dedupe: one finding per (rule, site, message) — nested AST walks may
    # visit a call from more than one enclosing statement
    seen: Set[Tuple] = set()
    unique: List[Finding] = []
    for f in findings:
        key = (f.rule, f.path, f.line, f.col, f.message)
        if key not in seen:
            seen.add(key)
            unique.append(f)
    unique.sort(key=Finding.sort_key)
    return unique, project
