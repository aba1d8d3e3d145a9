"""DSL002 — sync-free hot paths.

Originating incidents: PR 3 (``ds_train_loss`` publication paid a
``float()`` device sync even with telemetry disabled) and PR 7 (the
request tracer's disabled path had to be pinned to one branch / zero
alloc).  The serving decode/drain loops and the training step boundary
are dispatch pipelines: a stray ``float()`` / ``.item()`` /
``np.asarray`` / ``jax.device_get`` / ``block_until_ready`` on a
device value stalls the pipeline for a full device round-trip — and the
cheapest place to hide one is a telemetry branch that only executes when
metrics are OFF, where no test ever times it.

Checked regions:

- functions named in ``HOT_ZONES`` (per-file allowlists of the engine
  step / decode / drain loops), plus any function whose ``def`` line
  carries a ``# dslint: hot`` tag;
- within those, statements are EXEMPT when they can only run with
  telemetry enabled: the body of ``if <x>.enabled:`` (or of a local
  flag assigned from an ``.enabled`` expression), and everything after
  an ``if not <x>.enabled: return`` early-out;
- the body of ``if not <x>.enabled:`` itself is the DISABLED path — it
  is checked extra strictly (that's the never-executed-branch class).

Nested ``def``/``lambda`` bodies are skipped: inside ``jit`` those calls
are trace-time ops, not host syncs.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Sequence, Set

from .astutil import FUNC_NODES, tail_name, terminates
from .engine import FileContext, Finding, Project, Rule, register_rule

# function-name allowlists per path suffix: the engine step/decode/drain
# loops and their telemetry helpers (reachable every iteration)
HOT_ZONES = {
    "deepspeed_tpu/serving/engine.py": {
        "step", "_decode_block", "_drain_one", "_flush_outstanding",
        "_fetch_block", "_materialize", "_prefill_one_chunk",
        "_settle_first_tokens", "_admit_prefix", "_release",
    },
    "deepspeed_tpu/runtime/engine.py": {
        "step", "train_step", "train_batch", "forward",
        "_micro_telemetry", "_boundary_telemetry", "_report",
    },
    "deepspeed_tpu/runtime/zero/streaming.py": {
        "prefetch", "_dispatch", "take", "_put", "_restage_into_slot",
        "record_d2h",
    },
}

# calls that force a device->host round-trip on a device value
SYNC_NAME_CALLS = {"float"}
SYNC_TAIL_CALLS = {"asarray", "array", "device_get", "block_until_ready"}
SYNC_METHODS = {"item"}
# receivers whose asarray/array is jnp (dispatch, not a host sync)
_DEVICE_NS = {"jnp", "jax.numpy"}
# benign argument shapes for float(...): literals and wall-clock reads
_TIME_CALLS = {"perf_counter", "time", "monotonic"}


def _enabled_expr(node: ast.AST, enabled_locals: Set[str]) -> bool:
    """Whether ``node`` mentions a telemetry-enabled flag."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr == "enabled":
            return True
        if isinstance(sub, ast.Name) and sub.id in enabled_locals:
            return True
    return False


def _not_enabled_test(test: ast.AST, enabled_locals: Set[str]) -> bool:
    return (isinstance(test, ast.UnaryOp)
            and isinstance(test.op, ast.Not)
            and _enabled_expr(test.operand, enabled_locals))


def _benign_float_arg(arg: ast.AST) -> bool:
    if isinstance(arg, ast.Constant):
        return True
    if isinstance(arg, ast.Call) and tail_name(arg.func) in _TIME_CALLS:
        return True
    return False


def _sync_call(node: ast.Call) -> Optional[str]:
    """A short description when ``node`` is a suspected sync, else None."""
    func = node.func
    if isinstance(func, ast.Name) and func.id in SYNC_NAME_CALLS:
        if node.args and not _benign_float_arg(node.args[0]):
            return f"{func.id}(...)"
        return None
    tail = tail_name(func)
    if tail in SYNC_METHODS and not node.args and not node.keywords:
        return ".item()"
    if tail in SYNC_TAIL_CALLS and isinstance(func, ast.Attribute):
        recv = func.value
        recv_name = tail_name(recv) if not isinstance(recv, ast.Name) \
            else recv.id
        # np.asarray / numpy.array sync; jnp.asarray is device dispatch
        if tail in ("asarray", "array"):
            if recv_name in ("np", "numpy"):
                return f"{recv_name}.{tail}(...)"
            return None
        return f"{tail}(...)"
    return None


class SyncFreeHotPathRule(Rule):
    id = "DSL002"
    title = "no hidden device syncs in hot loops / disabled-telemetry paths"
    incident = ("PR 3/7 — float()/np.asarray device syncs hiding in "
                "telemetry branches that only run with metrics disabled, "
                "stalling the async dispatch pipeline")

    def check_file(self, ctx: FileContext,
                   project: Project) -> Iterable[Finding]:
        zone = None
        for suffix, names in HOT_ZONES.items():
            if ctx.rel.endswith(suffix):
                zone = names
                break
        findings: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, FUNC_NODES):
                continue
            tagged = any(ln in ctx.hot_lines for ln in
                         range(min(d.lineno for d in
                                   node.decorator_list + [node]),
                               node.lineno + 1))
            if tagged or (zone is not None and node.name in zone):
                self._check_hot_function(ctx, node, findings)
        return findings

    # ------------------------------------------------------------------
    def _check_hot_function(self, ctx: FileContext, fn, findings) -> None:
        enabled_locals: Set[str] = set()

        def scan_expr(node: ast.AST) -> None:
            stack = [node]
            while stack:
                n = stack.pop()
                if isinstance(n, FUNC_NODES + (ast.Lambda,)):
                    continue
                if isinstance(n, ast.Call):
                    desc = _sync_call(n)
                    if desc:
                        findings.append(Finding(
                            self.id, ctx.rel, n.lineno, n.col_offset,
                            f"suspected device sync {desc} in hot path "
                            f"{fn.name!r} (reachable with telemetry "
                            f"disabled) — defer the fetch or gate it on "
                            f"registry.enabled (PR 3/7)",
                            end_line=n.end_lineno or n.lineno))
                stack.extend(ast.iter_child_nodes(n))

        def walk(stmts: Sequence[ast.stmt], exempt: bool) -> None:
            rest_exempt = exempt
            for stmt in stmts:
                if isinstance(stmt, FUNC_NODES):
                    continue
                if isinstance(stmt, ast.Assign) \
                        and len(stmt.targets) == 1 \
                        and isinstance(stmt.targets[0], ast.Name) \
                        and _enabled_expr(stmt.value, enabled_locals):
                    enabled_locals.add(stmt.targets[0].id)
                if isinstance(stmt, ast.If):
                    if not rest_exempt:
                        scan_expr(stmt.test)
                    if _not_enabled_test(stmt.test, enabled_locals):
                        # body = the telemetry-DISABLED path: checked
                        walk(stmt.body, rest_exempt)
                        walk(stmt.orelse, True)
                        if terminates(stmt.body):
                            rest_exempt = True   # early-out guard
                    elif _enabled_expr(stmt.test, enabled_locals):
                        walk(stmt.body, True)    # enabled-only branch
                        walk(stmt.orelse, rest_exempt)
                    else:
                        walk(stmt.body, rest_exempt)
                        walk(stmt.orelse, rest_exempt)
                    continue
                # non-If compound statements: scan headers, recurse bodies
                if not rest_exempt:
                    for field in ("value", "test", "iter", "items",
                                  "exc", "cause", "targets", "target"):
                        sub = getattr(stmt, field, None)
                        if isinstance(sub, ast.AST):
                            scan_expr(sub)
                        elif isinstance(sub, list):
                            for s in sub:
                                if isinstance(s, ast.AST):
                                    scan_expr(s)
                for field in ("body", "orelse", "finalbody"):
                    sub = getattr(stmt, field, None)
                    if isinstance(sub, list) and sub \
                            and isinstance(sub[0], ast.stmt):
                        walk(sub, rest_exempt)
                if isinstance(stmt, ast.Try):
                    for h in stmt.handlers:
                        walk(h.body, rest_exempt)

        walk(fn.body, False)


register_rule(SyncFreeHotPathRule())


# --- selftest fixtures -----------------------------------------------------
SELFTEST_BAD = '''\
import numpy as np


class Engine:
    def _decode_block(self):   # dslint: hot
        toks = self._dispatch()
        if not self.registry.enabled:
            # disabled-telemetry branch paying a device sync  <- BAD
            self._last = float(toks.sum())
        vals = np.asarray(toks)                              # <- BAD
        return vals
'''

SELFTEST_GOOD = '''\
import time


class Engine:
    def _decode_block(self):   # dslint: hot
        toks = self._dispatch()
        if self.registry.enabled:
            self._m.record(float(toks.sum()))   # enabled-only: exempt
        t0 = float(time.perf_counter())         # wall clock: benign
        metered = self.registry.enabled
        if metered:
            self._m.record(float(toks[0]))      # enabled local: exempt
        if not self.registry.enabled:
            return toks
        return float(toks.sum())                # post-guard: enabled-only
'''
