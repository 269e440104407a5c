"""trilint pass: recompile hazards at jit/pallas boundaries.

The engine promises O(log m) distinct compilations per workload: every
shape that reaches a jitted kernel or ``pallas_call`` is first rounded to a
pow2 bucket (``next_pow2`` via the chunk planners), so truss peeling and
incremental probe sessions reuse a logarithmic number of cache entries
instead of tracing once per round.  ``CompileAuditor`` (repro.check.runtime)
verifies the bound dynamically; this pass catches the static pattern that
breaks it:

* ``R1-unbucketed-shape`` — a call to a known jit entry point where an
  argument is derived from a runtime shape (``.shape`` / ``len()`` /
  ``.size``, with one level of local-variable substitution) inside a
  function that never invokes a bucket helper.  Each distinct data size
  then mints a fresh cache key: the cache-key-explosion pattern.
"""

from __future__ import annotations

import ast

from .base import (
    Finding,
    ModuleInfo,
    build_parent_map,
    call_name,
    function_calls,
    register_pass,
)

# Call targets that hit the jit trace cache.  Names, not objects: this is a
# repo-specific lint and these are the repo's kernel entry points.
JIT_ENTRY_POINTS = {
    "chunk_count_kernel",
    "chunk_per_node_kernel",
    "chunk_support_kernel",
    "gather_panels",
    "gather_panels_arrays",
    "striped_workload_fn",
    "count_wedges_found",
    "pallas_call",
    "pl.pallas_call",
}

# Helpers that quantize shapes to a bounded bucket set.  Calling any of
# these in the enclosing function means shape-derived arguments are assumed
# bucketed (the planners bake pow2 rounding into the chunk objects).
BUCKET_HELPERS = {
    "next_pow2",
    "_next_pow2",
    "round_up_pow2",
    "plan_edge_chunks",
    "plan_striped_chunks",
    "make_wedge_plan",
    "bucketize_edges",
    "search_steps",
    "candidate_tiles",
    "_pick_tiles",
    "check_tiles",
    "pad_to_bucket",
}


def _shape_derived(node: ast.AST, assigns: "dict[str, ast.AST]") -> bool:
    def direct(n: ast.AST) -> bool:
        for sub in ast.walk(n):
            if isinstance(sub, ast.Attribute) and sub.attr in ("shape", "size"):
                return True
            if isinstance(sub, ast.Call) and call_name(sub) == "len":
                return True
        return False

    if direct(node):
        return True
    # one-level substitution: `n, lu = a.shape` handled below; `k = len(x)`
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in assigns and direct(assigns[sub.id]):
            return True
    return False


def _collect_assigns(scope: ast.AST) -> "dict[str, ast.AST]":
    assigns: "dict[str, ast.AST]" = {}
    for node in ast.walk(scope):
        if not isinstance(node, ast.Assign):
            continue
        for tgt in node.targets:
            if isinstance(tgt, ast.Name):
                assigns[tgt.id] = node.value
            elif isinstance(tgt, (ast.Tuple, ast.List)):
                # `n, lu = a.shape`: every unpacked name derives from the RHS
                for el in tgt.elts:
                    if isinstance(el, ast.Name):
                        assigns[el.id] = node.value
    return assigns


@register_pass("recompile")
def check_recompile(mod: ModuleInfo) -> "list[Finding]":
    findings: "list[Finding]" = []
    tree = mod.tree
    parents = build_parent_map(tree)

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        short = name.rsplit(".", 1)[-1]
        if name not in JIT_ENTRY_POINTS and short not in JIT_ENTRY_POINTS:
            continue

        # Enclosing function stack.
        stack = []
        cur = node
        while cur in parents:
            cur = parents[cur]
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                stack.append(cur)
        if any(BUCKET_HELPERS & function_calls(fn) for fn in stack):
            continue

        scope = stack[0] if stack else tree
        assigns = _collect_assigns(scope)
        shapey = [
            arg for arg in list(node.args) + [kw.value for kw in node.keywords]
            if _shape_derived(arg, assigns)
        ]
        if shapey:
            findings.append(
                mod.finding(
                    "recompile",
                    "R1-unbucketed-shape",
                    node,
                    f"shape-derived argument reaches jit entry `{short}` in a function "
                    "with no pow2 bucket helper; each data size mints a new trace "
                    "(cache-key explosion)",
                )
            )
    return findings
