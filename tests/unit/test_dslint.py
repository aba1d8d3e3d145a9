"""dslint (tools/dslint.py + deepspeed_tpu/analysis): the whole-repo
zero-violations tier-1 gate, per-rule seeded fixtures, the suppression
reason requirement, the --json schema round-trip, and the DSL003
import-graph check that replaces the per-tool no-jax subprocess asserts
(one subprocess smoke per tool keeps the runtime contract pinned)."""

import json
import os
import subprocess
import sys

import pytest

_TOOLS = os.path.join(os.path.dirname(__file__), "..", "..", "tools")
_REPO = os.path.abspath(os.path.join(_TOOLS, ".."))
_FIXTURES = os.path.join(_REPO, "tests", "fixtures", "dslint")


def _tool(name):
    sys.path.insert(0, _TOOLS)
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


def _analysis():
    return _tool("dslint")._load_analysis()


def _lint(paths, root, rules=None):
    analysis = _analysis()
    active = None
    if rules is not None:
        active = [r for r in analysis.RULES if r.id in rules]
    findings, project = analysis.run_paths(paths, root=root, rules=active)
    return findings


# ---------------------------------------------------------------------------
# THE tier-1 gate: the whole repo lints clean
# ---------------------------------------------------------------------------


def test_repo_zero_violations(capsys):
    """``python tools/dslint.py deepspeed_tpu tools`` reports
    ZERO violations — every incident-derived invariant (donation safety,
    sync-free hot paths, jax-free tools, telemetry contracts) holds
    across the package, and every deliberate exception carries a
    reasoned suppression."""
    dslint = _tool("dslint")
    rc = dslint.main(["dslint", os.path.join(_REPO, "deepspeed_tpu"),
                      os.path.join(_REPO, "tools")])
    out = capsys.readouterr().out
    assert rc == 0, f"dslint found violations:\n{out}"
    assert "0 findings" in out


def test_selftest_wired():
    """Every rule fires on its embedded seeded fixture and stays quiet on
    the clean twin (the fleet_dump/ckpt_verify idiom: the offline tool
    cannot silently rot)."""
    dslint = _tool("dslint")
    assert dslint.main(["dslint", "--selftest"]) == 0


# ---------------------------------------------------------------------------
# per-rule seeded fixtures (tests/fixtures/dslint/)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fixture,rule,min_hits", [
    ("dsl002_bad.py", "DSL002", 3),           # disabled branch + 2 syncs
    ("dsl004_bad.py", "DSL004", 1),           # non-ds_ literal
    ("deepspeed_tpu/comm/dsl005_bad.py", "DSL005", 2),  # no scope + cond
    # pipeline boundary form: bare ring hop + scope under a telemetry if
    ("deepspeed_tpu/runtime/pipe/dsl005_pipe_bad.py", "DSL005", 2),
    ("dsl006_bad.py", "DSL006", 3),           # nested / torn / unlocked
])
def test_rule_fires_on_seeded_fixture(fixture, rule, min_hits):
    findings = _lint([os.path.join(_FIXTURES, fixture)], root=_FIXTURES)
    hits = [f for f in findings if f.rule == rule]
    assert len(hits) >= min_hits, \
        f"{rule} expected >= {min_hits} on {fixture}, got " \
        f"{[f.render() for f in findings]}"


def test_dsl003_fires_on_seeded_tree():
    """The DSL003 fixture tree: a 'jax-free' tool reaching jax through a
    helper's normal package import — the finding carries the full chain."""
    root = os.path.join(_FIXTURES, "dsl003_tree")
    findings = _lint(["tools"], root=root)
    hits = [f for f in findings if f.rule == "DSL003"]
    assert hits, [f.render() for f in findings]
    assert "deepspeed_tpu/__init__.py" in hits[0].message
    assert "tools/router.py" in hits[0].message


def test_clean_fixture_zero_findings():
    findings = _lint([os.path.join(_FIXTURES, "clean.py")], root=_FIXTURES)
    assert findings == [], [f.render() for f in findings]


def test_dsl005_pipe_good_twin_clean():
    """The pipeline boundary idiom (conditional RECORD, unconditional
    hop + scope) passes the extended runtime/pipe/ rule scope."""
    findings = _lint([os.path.join(
        _FIXTURES, "deepspeed_tpu/runtime/pipe/dsl005_pipe_good.py")],
        root=_FIXTURES)
    assert findings == [], [f.render() for f in findings]


def test_suppression_without_reason_fails():
    """``# dslint: disable=RULE`` with no ``-- reason``: the original
    finding SURVIVES and the bad directive is its own DSL000 finding."""
    findings = _lint([os.path.join(_FIXTURES, "suppression_no_reason.py")],
                     root=_FIXTURES)
    rules = {f.rule for f in findings}
    assert "DSL002" in rules          # not suppressed
    assert "DSL000" in rules          # the reasonless directive itself
    meta = next(f for f in findings if f.rule == "DSL000")
    assert "justification" in meta.message


def test_suppression_with_reason_suppresses(tmp_path):
    src = (open(os.path.join(_FIXTURES, "suppression_no_reason.py")).read()
           .replace("# dslint: disable=DSL002",
                    "# dslint: disable=DSL002 -- deliberate deferred "
                    "fetch, pinned structurally"))
    p = tmp_path / "case.py"
    p.write_text(src)
    findings = _lint([str(p)], root=str(tmp_path))
    assert findings == [], [f.render() for f in findings]


def test_unknown_rule_in_suppression_is_flagged(tmp_path):
    p = tmp_path / "case.py"
    p.write_text("x = 1  # dslint: disable=DSL999 -- no such rule\n")
    findings = _lint([str(p)], root=str(tmp_path))
    assert [f.rule for f in findings] == ["DSL000"]
    assert "unknown rule" in findings[0].message


# ---------------------------------------------------------------------------
# --json schema round-trip
# ---------------------------------------------------------------------------


def test_json_schema_roundtrip(capsys):
    """The --json output is a single JSON object with the pinned schema —
    CI parses it, so the shape is a contract."""
    dslint = _tool("dslint")
    rc = dslint.main(["dslint", "--json",
                      os.path.join(_FIXTURES, "dsl002_bad.py")])
    out = capsys.readouterr().out
    assert rc == 1
    doc = json.loads(out)
    assert set(doc) == {"version", "root", "files", "rules", "findings",
                        "counts", "ok"}
    assert doc["version"] == 1 and doc["ok"] is False
    assert doc["files"] == 1 and doc["counts"]["DSL002"] >= 3
    for f in doc["findings"]:
        assert set(f) == {"rule", "path", "line", "col", "message"}
        assert f["rule"].startswith("DSL")
    # clean run: ok=true, empty findings — same schema
    rc = dslint.main(["dslint", "--json",
                      os.path.join(_FIXTURES, "clean.py")])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0 and doc["ok"] is True and doc["findings"] == []


# ---------------------------------------------------------------------------
# DSL003 as THE no-jax contract: import-graph wrapper + runtime smokes
# ---------------------------------------------------------------------------


def test_jax_free_tools_import_graph():
    """The whole-graph replacement for the per-tool 'no jax in a fresh
    interpreter' subprocess asserts: every operator tool's static import
    closure (router, fleet_dump, ckpt_verify, train_supervisor,
    trace_report, metrics_dump, dslint itself) stays jax-free."""
    findings = _lint([os.path.join(_REPO, "tools")], root=_REPO,
                     rules={"DSL003"})
    assert findings == [], "\n".join(f.render() for f in findings)


@pytest.mark.parametrize("tool,args,expect", [
    ("dslint.py", ["--selftest"], "dslint selftest: OK"),
    ("fleet_dump.py", ["--selftest"], "fleet_dump selftest: OK"),
    ("ckpt_verify.py", ["--selftest"], "ckpt_verify selftest: OK"),
    ("trace_report.py", ["--selftest"], "trace_report selftest: OK"),
])
def test_tool_subprocess_smoke(tool, args, expect):
    """ONE fresh-interpreter smoke per tool pins the RUNTIME half of the
    no-jax contract (DSL003 pins the static half); tools/router.py's
    smoke lives in test_router.py."""
    proc = subprocess.run(
        [sys.executable, os.path.join(_TOOLS, tool)] + args,
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr
    assert expect in proc.stdout


# ---------------------------------------------------------------------------
# regression pins: the rules catch the ORIGINAL incidents re-introduced
# into the real files (mutation tests on copies)
# ---------------------------------------------------------------------------


def _mutate(tmp_path, rel, old, new):
    src = open(os.path.join(_REPO, rel)).read()
    assert old in src, f"mutation anchor drifted in {rel}"
    dst = tmp_path / rel
    dst.parent.mkdir(parents=True, exist_ok=True)
    dst.write_text(src.replace(old, new))
    return str(dst)


def test_dsl005_catches_stripped_scope(tmp_path):
    """Deleting a ds_comm_ named_scope from the real comm wrapper file
    re-fires DSL005 (the PR 3 compiled-program-stability contract)."""
    p = _mutate(
        tmp_path, "deepspeed_tpu/comm/comm.py",
        '    with _scope("ds_comm_all_gather"):\n'
        "        return lax.all_gather(x, axis, axis=gather_dim, tiled=tiled)",
        "    return lax.all_gather(x, axis, axis=gather_dim, tiled=tiled)")
    findings = _lint([p], root=str(tmp_path), rules={"DSL005"})
    assert any("all_gather" in f.message for f in findings), \
        [f.render() for f in findings]


