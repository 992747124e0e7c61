"""fused-path-materialization: decoded-column materialization inside the
fused kernel modules.

The fused single-launch plan (PR 16) keeps value columns in their compressed
resident forms — dict ids decoded through the column's table inside the
kernel (selects over a small table; `take_along_axis` over a wide one, which
on the v5e still writes the decoded column from a pass of its own: PERF.md,
PR 27), FOR deltas re-based in the kernel body — so filter+aggregate keeps no
decoded full-width column resident in HBM. What silently regresses it is a
"convenience" decode inside the
kernel builders: a `jnp.take`/`np.take` dict-LUT gather that materializes the
whole column, or a call back into the staged decode surface
(`block.values(...)` / `block.decoded(...)`) from code that is supposed to
consume compressed forms.

This rule flags, in the fused kernel hot modules only:

* any `jnp.take` / `np.take` / `jax.numpy.take` call (the full-column gather
  shape; `take_along_axis` on an in-register LUT is the sanctioned fused
  decode and is NOT flagged), and
* any `.values(...)` / `.decoded(...)` method call (the staged decoded-HBM
  column surface),

unless the nearest enclosing function chain includes a name the module
declares in `__graft_slow_paths__ = ("fn", ...)` — the explicit allowlist of
staged/fallback decode paths — or the line carries an inline suppression
with a reason.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Set

from .core import AnalysisContext, Finding, Module, Rule, dotted_name
from .ingest_hot_loop import slow_path_names

#: fused-execution hot modules (repo-relative suffixes): the kernel builder
#: and the compressed-form datablock. The
#: executor routes between fused and staged plans, so its staged input
#: builder legitimately calls `block.values(...)` — it is not listed here.
HOT_MODULES = (
    "pinot_tpu/engine/kernels.py",
    "pinot_tpu/engine/datablock.py",
)

#: the full-column gather spellings (exact names: `take_along_axis` is the
#: in-register fused decode and must stay legal)
_TAKE_NAMES = ("jnp.take", "np.take", "jax.numpy.take", "numpy.take")

#: the staged decoded-column surface
_DECODE_ATTRS = ("values", "decoded")


class FusedPathMaterializationRule(Rule):
    id = "fused-path-materialization"
    description = ("decoded-column materialization (`jnp.take` dict gather "
                   "or a `.values()`/`.decoded()` staged-surface call) "
                   "inside a fused kernel module outside a declared "
                   "__graft_slow_paths__ function")

    def check_module(self, module: Module, ctx: AnalysisContext
                     ) -> Iterable[Finding]:
        if not any(module.rel.endswith(suffix) for suffix in HOT_MODULES):
            return ()
        slow = slow_path_names(module)
        out: List[Finding] = []
        seen_lines: Set[int] = set()

        def _enclosing(node: ast.AST) -> Set[str]:
            names: Set[str] = set()
            cur = getattr(node, "graft_parent", None)
            while cur is not None:
                if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    names.add(cur.name)
                cur = getattr(cur, "graft_parent", None)
            return names

        def _flag(node: ast.AST, message: str) -> None:
            fns = _enclosing(node)
            if fns & slow:
                return
            if node.lineno in seen_lines:
                return
            seen_lines.add(node.lineno)
            where = (f"`{sorted(fns)[0]}`" if fns else "module scope")
            out.append(Finding(self.id, module.rel, node.lineno,
                               f"{message} in {where} — fused kernels "
                               "consume compressed forms (in-register LUT "
                               "gather / FOR re-base); move the decode to a "
                               "declared __graft_slow_paths__ function"))

        for node in module.nodes_of(ast.Call):
            name = dotted_name(node.func)
            if name in _TAKE_NAMES:
                _flag(node, f"full-column dict gather `{name}(...)`")
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _DECODE_ATTRS:
                _flag(node, "staged decoded-column surface "
                            f"`.{node.func.attr}(...)`")
        return out


def rules() -> List[Rule]:
    return [FusedPathMaterializationRule()]
