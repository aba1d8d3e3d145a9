"""dslint: AST-level invariant checker for this repo's incident-derived
correctness rules (see docs/LINT.md for the catalogue):

- DSL002 sync-free hot paths (no hidden device syncs in step/decode/drain
  loops or disabled-telemetry branches)
- DSL003 jax-free operator tools (whole import-graph closure)
- DSL004 metric-namespace literals
- DSL005 unconditional ds_comm_<op> named_scope on collective wrappers
- DSL006 flight/trace shared-structure mutation discipline

This package is stdlib-only and uses RELATIVE imports exclusively:
``tools/dslint.py`` loads it by file path on boxes with no jax (and the
package's own DSL003 closure check keeps it that way).  Run via::

    python tools/dslint.py deepspeed_tpu tools
    python tools/dslint.py --selftest
    make lint
"""

from .engine import (Finding, META_RULE, Project, RULES, Rule,  # noqa: F401
                     rule_ids, run_paths)
from . import dsl002_sync  # noqa: F401  (registration side effect)
from . import dsl003_jaxfree  # noqa: F401
from . import dsl004_metrics  # noqa: F401
from . import dsl005_scope  # noqa: F401
from . import dsl006_shared  # noqa: F401
from .selftest import run_selftest  # noqa: F401

__all__ = ["Finding", "META_RULE", "Project", "RULES", "Rule", "rule_ids",
           "run_paths", "run_selftest"]
