"""memory-hygiene rules: device staging must be accounted.

The MemoryLedger (`utils/memledger.py`) is only as accurate as its coverage:
one staging site that bypasses `staged()` and the reconciliation drift gate
starts lying. This pack makes coverage a static property instead of a code
review hope:

* `memory-untracked-staging` — `jnp.asarray` / `jax.device_put` staging calls
  in the engine/segment/cluster layers (the layers that put long-lived data
  on device) must flow through the `staged(...)` registration wrapper.
  Transient math inside jit'd kernels is NOT staging — the rule skips calls
  inside jit-decorated functions — and deliberate exceptions suppress with
  a rationale.
"""

from __future__ import annotations

import ast
from typing import Iterable, List

from .core import AnalysisContext, Finding, Module, Rule, dotted_name

#: layers whose device staging is long-lived (resident HBM) and must be
#: ledger-accounted; parallel/query transports stage per-request transients
#: covered by the ledger's transient gauge instead
_SCOPED_PREFIXES = ("pinot_tpu/engine/", "pinot_tpu/segment/",
                    "pinot_tpu/cluster/")

#: device staging entry points (import-alias variants included)
_STAGING_CALLS = ("jnp.asarray", "jax.numpy.asarray", "jax.device_put")


def _inside_sanctioned_wrapper(node: ast.AST) -> bool:
    """True when the call's result flows straight into the ledger helper:
    `staged(jnp.asarray(...), ...)` or `memledger.staged(...)` anywhere up
    the expression spine."""
    cur = getattr(node, "graft_parent", None)
    while cur is not None and not isinstance(
            cur, (ast.stmt, ast.FunctionDef, ast.AsyncFunctionDef,
                  ast.ClassDef)):
        if isinstance(cur, ast.Call) and \
                dotted_name(cur.func).split(".")[-1] == "staged":
            return True
        cur = getattr(cur, "graft_parent", None)
    return False


def _enclosing_jit_function(node: ast.AST) -> bool:
    """True when the call sits inside a jit-decorated function — traced
    device math, not host->device staging."""
    cur = getattr(node, "graft_parent", None)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in cur.decorator_list:
                name = dotted_name(dec if not isinstance(dec, ast.Call)
                                   else dec.func)
                if name.endswith("jit"):
                    return True
        cur = getattr(cur, "graft_parent", None)
    return False


class UntrackedStagingRule(Rule):
    id = "memory-untracked-staging"
    description = ("device staging (jnp.asarray / jax.device_put) in the "
                   "engine/segment/cluster layers must register with the "
                   "MemoryLedger via the staged() wrapper — untracked "
                   "staging makes the residency ledger drift")

    def check_module(self, module: Module, ctx: AnalysisContext
                     ) -> Iterable[Finding]:
        if not module.rel.startswith(_SCOPED_PREFIXES):
            return ()
        out: List[Finding] = []
        for node in module.nodes_of(ast.Call):
            name = dotted_name(node.func)
            if name not in _STAGING_CALLS:
                continue
            if _inside_sanctioned_wrapper(node):
                continue
            if _enclosing_jit_function(node):
                continue
            out.append(Finding(
                self.id, module.rel, node.lineno,
                f"`{name}(...)` stages device memory outside the "
                "MemoryLedger — wrap it with utils.memledger.staged(arr, "
                "segment, kind) so residency (and release) is accounted"))
        return out


def rules() -> List[Rule]:
    return [UntrackedStagingRule()]
