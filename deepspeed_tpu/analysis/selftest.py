"""dslint selftest: every rule must fire on its seeded fixture and stay
quiet on its clean twin, and the suppression machinery must enforce the
reason requirement.  Pure stdlib + temp files, so ``tools/dslint.py
--selftest`` runs on an operator box and is wired tier-1 (the
fleet_dump/ckpt_verify idiom: the offline tool cannot silently rot).
"""

from __future__ import annotations

import os
import tempfile
from typing import Dict, List, Tuple

from . import (dsl002_sync, dsl003_jaxfree, dsl004_metrics, dsl005_scope,
               dsl006_shared)
from .engine import META_RULE, run_paths

# (rule id, bad source, good source, in-tree filename) — file-level rules
# (DSL005 is scoped to comm/ directories, so its fixture lives there)
_FILE_CASES = [
    ("DSL002", dsl002_sync.SELFTEST_BAD, dsl002_sync.SELFTEST_GOOD,
     "case.py"),
    ("DSL004", dsl004_metrics.SELFTEST_BAD, dsl004_metrics.SELFTEST_GOOD,
     "case.py"),
    ("DSL005", dsl005_scope.SELFTEST_BAD, dsl005_scope.SELFTEST_GOOD,
     "deepspeed_tpu/comm/case.py"),
    ("DSL006", dsl006_shared.SELFTEST_BAD, dsl006_shared.SELFTEST_GOOD,
     "case.py"),
]


def _lint_source(source: str, root: str, name: str = "case.py"):
    path = os.path.join(root, *name.split("/"))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(source)
    findings, _ = run_paths([path], root=root)
    return findings


def _write_tree(root: str, tree: Dict[str, str]) -> None:
    for rel, src in tree.items():
        path = os.path.join(root, *rel.split("/"))
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as fh:
            fh.write(src)


def run_selftest(verbose: bool = False) -> List[str]:
    """Returns a list of failure strings (empty = OK)."""
    failures: List[str] = []

    def check(cond: bool, msg: str) -> None:
        if not cond:
            failures.append(msg)
        elif verbose:
            print(f"  ok: {msg}")

    with tempfile.TemporaryDirectory(prefix="dslint_selftest_") as td:
        for rule_id, bad, good, fname in _FILE_CASES:
            sub = os.path.join(td, rule_id.lower())
            os.makedirs(sub, exist_ok=True)
            hits = [f for f in _lint_source(bad, sub, fname)
                    if f.rule == rule_id]
            check(bool(hits), f"{rule_id} fires on its seeded fixture")
            clean = [f for f in _lint_source(good, sub, fname)
                     if f.rule == rule_id]
            check(not clean,
                  f"{rule_id} stays quiet on the clean fixture "
                  f"(got {[f.render() for f in clean]})")

        # DSL004 documented-name check over the ds_prof_* continuous-
        # profiler family: a fixture docs file documents two names (one
        # labeled); an undocumented ds_prof_ literal must be flagged, the
        # documented pair (labels stripped by the normalizer) must pass
        sub = os.path.join(td, "dsl004_prof")
        _write_tree(sub, {"docs/OBSERVABILITY.md":
                          dsl004_metrics.SELFTEST_PROF_DOCS})
        hits = [f for f in _lint_source(dsl004_metrics.SELFTEST_BAD_PROF,
                                        sub) if f.rule == "DSL004"]
        check(bool(hits), "DSL004 flags an undocumented ds_prof_* name")
        clean = [f for f in _lint_source(dsl004_metrics.SELFTEST_GOOD_PROF,
                                         sub) if f.rule == "DSL004"]
        check(not clean, "DSL004 accepts documented ds_prof_* names "
                         f"(got {[f.render() for f in clean]})")

        # DSL003 import-graph closure (project trees)
        for name, tree, expect in (
                ("bad", dsl003_jaxfree.SELFTEST_BAD_TREE, True),
                ("bad_negated_guard",
                 dsl003_jaxfree.SELFTEST_BAD_NEGATED_GUARD_TREE, True),
                ("good", dsl003_jaxfree.SELFTEST_GOOD_TREE, False)):
            sub = os.path.join(td, f"dsl003_{name}")
            _write_tree(sub, tree)
            findings, _ = run_paths(["tools"], root=sub)
            hits = [f for f in findings if f.rule == "DSL003"]
            if expect:
                check(bool(hits), f"DSL003 fires on the {name} tree")
                if name == "bad":
                    check(any("deepspeed_tpu/__init__.py" in f.message
                              for f in hits),
                          "DSL003 reports the full import chain")
            else:
                check(not hits, "DSL003 accepts the file-path loader "
                                f"idiom (got {[f.render() for f in hits]})")

        # suppression machinery (DSL005 fixture, in its comm/ home)
        sub = os.path.join(td, "suppress")
        os.makedirs(sub, exist_ok=True)
        comm = "deepspeed_tpu/comm/"
        bad_line = dsl005_scope.SELFTEST_BAD
        suppressed = bad_line.replace(
            "return lax.psum(x, axis)          # <- no ds_comm_ scope",
            "return lax.psum(x, axis)  "
            "# dslint: disable=DSL005 -- eager debug helper, never traced")
        hits = [f for f in _lint_source(suppressed, sub, comm + "s1.py")]
        check(not any(f.rule == "DSL005" and f.line == 7 for f in hits),
              "a disable with a reason suppresses its line")
        no_reason = bad_line.replace(
            "return lax.psum(x, axis)          # <- no ds_comm_ scope",
            "return lax.psum(x, axis)  # dslint: disable=DSL005")
        hits = _lint_source(no_reason, sub, comm + "s2.py")
        check(any(f.rule == META_RULE for f in hits),
              "a disable WITHOUT a reason is itself a finding (DSL000)")
        check(any(f.rule == "DSL005" for f in hits),
              "a reasonless disable does not suppress the finding")
        unknown = "x = 1  # dslint: disable=DSL999 -- no such rule\n"
        hits = _lint_source(unknown, sub, "s3.py")
        check(any(f.rule == META_RULE for f in hits),
              "naming an unknown rule is a DSL000 finding")

    return failures
