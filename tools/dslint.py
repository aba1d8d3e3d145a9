#!/usr/bin/env python
"""dslint — AST-level invariant checker for this repo's incident-derived
correctness rules (donation safety, sync-free hot paths, jax-free tools,
telemetry contracts).  See docs/LINT.md for the rule catalogue and the
suppression syntax.

    python tools/dslint.py                          # deepspeed_tpu tools
    python tools/dslint.py --json                   # machine-readable
    python tools/dslint.py --rules DSL003,DSL004    # subset
    python tools/dslint.py --list-rules
    python tools/dslint.py --selftest               # seeded fixtures

Exit codes: 0 clean, 1 findings, 2 usage/selftest failure.

Zero dependencies beyond the stdlib — **no jax import**.  The analyzer
package (``deepspeed_tpu/analysis``) is loaded by FILE PATH (the
fleet_dump/ckpt_verify idiom) so importing it never executes the
jax-pulling ``deepspeed_tpu/__init__``; rule DSL003 checks this tool's
own closure along with the other operator tools.
"""

from __future__ import annotations

import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# literal so the DSL003 resolver can follow this loader statically
_ANALYSIS_INIT = os.path.join("deepspeed_tpu", "analysis", "__init__.py")

DEFAULT_PATHS = ("deepspeed_tpu", "tools")


def _load_analysis():
    """The analysis package: reuse it when the repo package is already
    imported (in-process test callers), else load by file path under a
    private name so no jax-importing ``__init__`` runs."""
    mod = sys.modules.get("deepspeed_tpu.analysis")
    if mod is not None:
        return mod
    mod = sys.modules.get("_ds_analysis")
    if mod is not None:
        return mod
    import importlib.util

    path = os.path.join(_REPO, _ANALYSIS_INIT)
    spec = importlib.util.spec_from_file_location(
        "_ds_analysis", path,
        submodule_search_locations=[os.path.dirname(path)])
    mod = importlib.util.module_from_spec(spec)
    sys.modules["_ds_analysis"] = mod
    spec.loader.exec_module(mod)
    return mod


def main(argv) -> int:
    args = list(argv[1:])
    as_json = "--json" in args
    verbose = "--verbose" in args
    for flag in ("--json", "--verbose"):
        while flag in args:
            args.remove(flag)
    rule_filter = None
    if "--rules" in args:
        i = args.index("--rules")
        try:
            rule_filter = {r.strip() for r in args[i + 1].split(",")
                           if r.strip()}
        except IndexError:
            print("dslint: --rules needs a comma-separated id list",
                  file=sys.stderr)
            return 2
        del args[i:i + 2]

    analysis = _load_analysis()

    if "--list-rules" in args:
        for rule in analysis.RULES:
            print(f"{rule.id}  {rule.title}")
        return 0

    if "--selftest" in args:
        failures = analysis.run_selftest(verbose=verbose)
        if failures:
            for f in failures:
                print(f"dslint selftest FAILED: {f}", file=sys.stderr)
            return 2
        # the operator-box contract this tool documents (standalone runs
        # only — in-process tier-1 callers already carry jax)
        if os.path.basename(sys.argv[0]).startswith("dslint"):
            assert "jax" not in sys.modules, "tools/dslint.py imported jax"
        print("dslint selftest: OK "
              f"({len(analysis.RULES)} rules + suppression machinery)")
        return 0

    paths = args or [os.path.join(_REPO, p) for p in DEFAULT_PATHS]
    rules = analysis.RULES
    if rule_filter is not None:
        unknown = rule_filter - analysis.rule_ids()
        if unknown:
            print(f"dslint: unknown rule(s): {', '.join(sorted(unknown))}",
                  file=sys.stderr)
            return 2
        rules = [r for r in analysis.RULES if r.id in rule_filter]
    try:
        findings, project = analysis.run_paths(paths, root=_REPO,
                                               rules=rules)
    except FileNotFoundError as exc:
        print(str(exc), file=sys.stderr)
        return 2

    counts = {}
    for f in findings:
        counts[f.rule] = counts.get(f.rule, 0) + 1
    if as_json:
        print(json.dumps({
            "version": 1,
            "root": project.root,
            "files": len(project.files),
            "rules": sorted(r.id for r in rules),
            "findings": [f.to_dict() for f in findings],
            "counts": counts,
            "ok": not findings,
        }, indent=None, separators=(",", ":"), sort_keys=True))
    else:
        for f in findings:
            print(f.render())
        n = len(findings)
        print(f"dslint: {len(project.files)} files, {n} finding"
              f"{'' if n == 1 else 's'}"
              + (f" ({', '.join(f'{k}={v}' for k, v in sorted(counts.items()))})"
                 if counts else ""))
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
