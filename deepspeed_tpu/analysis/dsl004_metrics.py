"""DSL004 — metric-namespace literals.

Originating incident: PR 2 established the runtime namespace guard
(every REGISTERED metric must be ``ds_``-prefixed and documented in
docs/OBSERVABILITY.md) — but the runtime guard only sees a name when its
registration branch executes; a metric born behind a rarely-taken branch
escapes until production takes that branch.  This rule extracts every
``Counter``/``Gauge``/``Histogram`` name LITERAL (and every f-string
prefix) statically and applies the same two checks at parse time.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Iterable, List, Optional, Set, Tuple

from .astutil import const_str
from .engine import FileContext, Finding, Project, Rule, register_rule

FAMILY_METHODS = {"counter", "gauge", "histogram"}
FAMILY_CLASSES = {"Counter", "Gauge", "Histogram"}
DOCS_REL = "docs/OBSERVABILITY.md"
PREFIX = "ds_"

# files that mint names from caller input rather than literals (the
# registry itself, and the dump/render tools)
EXEMPT_SUFFIXES = ("deepspeed_tpu/monitor/metrics.py",)

_WILD = "\x00"  # internal wildcard marker for f-string segments


def _extract_name(call: ast.Call) -> Optional[Tuple[str, bool]]:
    """(name_pattern, is_literal) for a family-creating call; the pattern
    uses a wildcard marker for formatted f-string fields."""
    func = call.func
    is_family = False
    if isinstance(func, ast.Attribute) and func.attr in FAMILY_METHODS:
        is_family = True
    elif isinstance(func, ast.Name) and func.id in FAMILY_CLASSES:
        is_family = True
    if not is_family or not call.args:
        return None
    arg = call.args[0]
    s = const_str(arg)
    if s is not None:
        return s, True
    if isinstance(arg, ast.JoinedStr):
        parts = []
        for v in arg.values:
            if isinstance(v, ast.Constant):
                parts.append(str(v.value))
            else:
                parts.append(_WILD)
        return "".join(parts), False
    return None   # dynamic name: the runtime guard owns it


def _docs_patterns(text: str) -> Set[str]:
    """Normalized metric tokens from the docs: backtick tokens starting
    with ds_, label blocks stripped, ``<op>``-style holes -> wildcard."""
    out: Set[str] = set()
    for tok in re.findall(r"`([^`]+)`", text):
        tok = tok.strip()
        if not tok.startswith(PREFIX):
            continue
        tok = re.sub(r"\{[^}]*\}", "", tok)          # label blocks
        tok = re.sub(r"<[^>]*>", _WILD, tok)         # <op> holes
        tok = tok.strip()
        if tok:
            out.add(tok)
    return out


def _pattern_matches(name: str, patterns: Set[str], raw_text: str) -> bool:
    if _WILD not in name:
        if name in patterns or name in raw_text:
            return True
        # a literal name may be documented as a <hole> pattern row
        for p in patterns:
            if _WILD in p and re.fullmatch(
                    re.escape(p).replace(re.escape(_WILD), r"[A-Za-z0-9_]+"),
                    name):
                return True
        return False
    # f-string: compare skeletons (wildcards collapse)
    skel = re.sub(_WILD + "+", _WILD, name)
    for p in patterns:
        if re.sub(_WILD + "+", _WILD, p) == skel:
            return True
    # fall back: the static prefix must at least appear in the docs
    prefix = name.split(_WILD, 1)[0]
    return bool(prefix) and prefix in raw_text


class MetricNamespaceRule(Rule):
    id = "DSL004"
    title = "metric name literals: ds_ prefix + documented"
    incident = ("PR 2's runtime namespace guard only fires when the "
                "registration branch executes")

    def check_file(self, ctx: FileContext,
                   project: Project) -> Iterable[Finding]:
        if ctx.rel.endswith(EXEMPT_SUFFIXES):
            return []
        return self._check_names(ctx, project)

    @staticmethod
    def _docs(project: Project):
        """(docs text, normalized pattern set), cached per Project — the
        docs depend only on the root, not on the file being checked."""
        cached = getattr(project, "_dsl004_docs", None)
        if cached is not None:
            return cached
        docs_text = ""
        docs_path = os.path.join(project.root, DOCS_REL)
        if os.path.isfile(docs_path):
            with open(docs_path, encoding="utf-8") as fh:
                docs_text = fh.read()
        patterns = _docs_patterns(docs_text) if docs_text else set()
        project._dsl004_docs = (docs_text, patterns)
        return project._dsl004_docs

    # -- metric name literals ------------------------------------------
    def _check_names(self, ctx: FileContext,
                     project: Project) -> List[Finding]:
        findings: List[Finding] = []
        docs_text, patterns = self._docs(project)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            got = _extract_name(node)
            if got is None:
                continue
            name, literal = got
            display = name.replace(_WILD, "{...}")
            lead = name.split(_WILD, 1)[0]
            if not lead.startswith(PREFIX):
                findings.append(Finding(
                    self.id, ctx.rel, node.lineno, node.col_offset,
                    f"metric name {display!r} outside the ds_ namespace "
                    f"(docs/OBSERVABILITY.md contract; the runtime guard "
                    f"only sees executed branches)",
                    end_line=node.end_lineno or node.lineno))
                continue
            if docs_text and not _pattern_matches(name, patterns,
                                                  docs_text):
                findings.append(Finding(
                    self.id, ctx.rel, node.lineno, node.col_offset,
                    f"metric name {display!r} not documented in "
                    f"{DOCS_REL} — add its schema row",
                    end_line=node.end_lineno or node.lineno))
        return findings


register_rule(MetricNamespaceRule())


# --- selftest fixtures -----------------------------------------------------
SELFTEST_BAD = '''\
from deepspeed_tpu.monitor.metrics import get_registry

reg = get_registry()
bad = reg.counter("serve_requests_total", "missing ds_ prefix")  # <- BAD
'''

SELFTEST_GOOD = '''\
from deepspeed_tpu.monitor.metrics import get_registry

reg = get_registry()
ok = reg.counter("ds_serve_requests_total", "documented name")
dyn = reg.counter(name_variable)          # dynamic: runtime guard owns it
'''

# the ds_prof_* continuous-profiler family (docs/OBSERVABILITY.md
# "Continuous profiling"): the documented-name check must cover it like
# any other ds_ family — including the labeled {scope=} rows, whose docs
# tokens carry a label block the normalizer strips
SELFTEST_PROF_DOCS = '''\
# Observability
| `ds_prof_windows_total` | counter | completed windows |
| `ds_prof_scope_device_seconds{scope=}` | gauge | per-scope seconds |
'''

SELFTEST_BAD_PROF = '''\
from deepspeed_tpu.monitor.metrics import get_registry

reg = get_registry()
bad = reg.counter("ds_prof_bogus_total", "undocumented ds_prof name")
'''

SELFTEST_GOOD_PROF = '''\
from deepspeed_tpu.monitor.metrics import get_registry

reg = get_registry()
ok = reg.counter("ds_prof_windows_total", "documented")
lab = reg.gauge("ds_prof_scope_device_seconds", labels={"scope": "comm"})
'''
