"""Pallas kernel checker: BlockSpec/grid/index-map consistency and a
static VMEM-footprint estimate.

* ``pallas-index-map-arity`` — a BlockSpec index_map whose lambda cannot
  accept the grid's rank (Mosaic fails at lowering, i.e. on-device);
* ``pallas-block-rank`` — index_map returns a different number of block
  coordinates than the block shape has dims (or out_specs/out_shape
  length mismatch);
* ``pallas-dim-semantics`` — ``dimension_semantics`` length differs from
  the grid rank;
* ``pallas-vmem-budget`` — the per-grid-step working set (in/out blocks
  + scratch + one fp32 score tile for attention-shaped kernels),
  evaluated at the tuned default blocks from ``tune_attention_blocks``
  via constant folding of the enclosing function (including ``min``-
  clamp chains), exceeds the module's explicit ``_VMEM_CLAMP`` budget.

The folder follows the codebase's own sizing arithmetic: ``min``-clamp
chains over literals fold, and the footprint is checked *after* them.  A
block that comes out of a function call (the attention backward's
``_bwd_block_q``) keeps its prior binding, the conservative upper bound.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Tuple

from .core import Finding, ModuleInfo
from .jitgraph import (PackageIndex, call_target_name, call_target_parts,
                       fold_or_none, shallow_walk)

RULES = {
    "pallas-index-map-arity":
        "BlockSpec index_map arity incompatible with the grid rank",
    "pallas-block-rank":
        "BlockSpec block shape rank differs from the index_map's "
        "coordinate count (or out_specs/out_shape mismatch)",
    "pallas-dim-semantics":
        "compiler_params dimension_semantics length differs from the "
        "grid rank",
    "pallas-vmem-budget":
        "estimated per-grid-step VMEM working set exceeds the module's "
        "_VMEM_CLAMP budget at the tuned default block sizes",
}

_DEFAULT_CLAMP = 12 * 1024 * 1024
_DEFAULT_DIM = 128          # substituted for unfoldable block dims
_F32 = {"float32", "f32", "int32", "uint32"}


def _module_env(module: ModuleInfo) -> Dict[str, object]:
    env: Dict[str, object] = {}
    for stmt in module.tree.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 and \
                isinstance(stmt.targets[0], ast.Name):
            v = fold_or_none(stmt.value, env)
            if v is not None:
                env[stmt.targets[0].id] = v
    return env


def _tuned_defaults(index: PackageIndex) -> Tuple[int, int]:
    """Streaming-path default (block_q, block_k) parsed out of
    tune_attention_blocks (`block_q, block_k = 1024, 2048`)."""
    for fi in index.functions:
        if fi.name != "tune_attention_blocks":
            continue
        for stmt in shallow_walk(fi.node):
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Tuple):
                names = [t.id for t in stmt.targets[0].elts
                         if isinstance(t, ast.Name)]
                if names == ["block_q", "block_k"]:
                    v = fold_or_none(stmt.value)
                    if isinstance(v, tuple) and len(v) == 2:
                        return int(v[0]), int(v[1])
    return 1024, 2048


def _global_clamp(index: PackageIndex) -> int:
    for m in index.modules:
        env = _module_env(m)
        if isinstance(env.get("_VMEM_CLAMP"), int):
            return env["_VMEM_CLAMP"]
    return _DEFAULT_CLAMP


def _local_env(module, fi, call_line, base: Dict[str, object]
               ) -> Dict[str, object]:
    """Fold the enclosing function's assignments (source order, up to the
    call) over ``base``.  On fold failure the existing binding is KEPT —
    the clamp chains this codebase writes only shrink blocks via min(),
    so a stale binding is the conservative upper bound."""
    env = dict(base)
    if fi is None:
        return env
    stmts = [s for s in shallow_walk(fi.node)
             if isinstance(s, ast.Assign) and s.lineno < call_line]
    for stmt in sorted(stmts, key=lambda s: s.lineno):
        if len(stmt.targets) != 1:
            continue
        t = stmt.targets[0]
        if isinstance(t, ast.Name):
            v = fold_or_none(stmt.value, env)
            if v is not None:
                env[t.id] = v
        elif isinstance(t, ast.Tuple) and \
                all(isinstance(e, ast.Name) for e in t.elts):
            v = fold_or_none(stmt.value, env)
            if isinstance(v, tuple) and len(v) == len(t.elts):
                for e, x in zip(t.elts, v):
                    env[e.id] = x
    return env


def _spec_elements(expr: Optional[ast.expr]
                   ) -> Tuple[List[ast.Call], bool]:
    """BlockSpec Call nodes out of an in_specs/out_specs expression;
    second value = True when the list is complete (no `+ extra` tail)."""
    if expr is None:
        return [], False
    complete = True
    lists: List[ast.List] = []
    if isinstance(expr, (ast.List, ast.Tuple)):
        lists.append(expr)
    elif isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Add):
        complete = False
        for side in (expr.left, expr.right):
            if isinstance(side, (ast.List, ast.Tuple)):
                lists.append(side)
    else:
        return [], False
    out: List[ast.Call] = []
    for li in lists:
        for e in li.elts:
            if isinstance(e, ast.Call) and \
                    call_target_name(e) == "BlockSpec":
                out.append(e)
    return out, complete


def _lambda_arity(lam: ast.Lambda) -> Tuple[int, int]:
    a = lam.args
    total = len(a.posonlyargs) + len(a.args)
    return total - len(a.defaults), total


def _index_map_coords(lam: ast.Lambda) -> Optional[int]:
    body = lam.body
    if isinstance(body, ast.Tuple):
        return len(body.elts)
    return 1


def _block_dims(spec: ast.Call) -> Optional[ast.expr]:
    if spec.args:
        return spec.args[0]
    for kw in spec.keywords:
        if kw.arg == "block_shape":
            return kw.value
    return None


def _spec_index_map(spec: ast.Call) -> Optional[ast.Lambda]:
    cand = None
    if len(spec.args) >= 2:
        cand = spec.args[1]
    else:
        for kw in spec.keywords:
            if kw.arg == "index_map":
                cand = kw.value
    return cand if isinstance(cand, ast.Lambda) else None


def _is_smem(spec: ast.Call) -> bool:
    for kw in spec.keywords:
        if kw.arg == "memory_space":
            return "SMEM" in ast.dump(kw.value)
    return False


def _fold_dims(expr: Optional[ast.expr], env) -> Optional[List[int]]:
    if expr is None:
        return None
    if not isinstance(expr, (ast.Tuple, ast.List)):
        return None
    dims = []
    for e in expr.elts:
        v = fold_or_none(e, env)
        if isinstance(v, (int, float)):
            dims.append(int(v))
        elif isinstance(e, ast.Constant) and e.value is None:
            dims.append(1)      # a squeezed dim: one element a grid step
        else:
            dims.append(_DEFAULT_DIM)
    return dims


def _dtype_size(expr: Optional[ast.expr]) -> int:
    """Itemsize of a dtype expression; unknown -> 2 (the tuned kernels'
    bf16 operand dtype — tune_attention_blocks halves blocks for wider
    dtypes before the kernels ever see them)."""
    if expr is None:
        return 2
    text = ast.dump(expr)
    if any(t in text for t in ("float64", "int64")):
        return 8
    if any(t in text for t in _F32):
        return 4
    if any(t in text for t in ("bfloat16", "float16", "int16")):
        return 2
    if any(t in text for t in ("int8", "uint8")):
        return 1
    return 2


def _kw(call: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def check(module: ModuleInfo, index: PackageIndex) -> List[Finding]:
    findings: List[Finding] = []
    calls = [cs for cs in index.calls_in(module)
             if call_target_name(cs.node) == "pallas_call"]
    if not calls:
        return findings

    bq, bk = _tuned_defaults(index)
    clamp = _global_clamp(index)
    base = _module_env(module)
    base.setdefault("block_q", bq)
    base.setdefault("block_k", bk)
    base.setdefault("Dp", _DEFAULT_DIM)

    for cs in calls:
        node = cs.node
        ctx = cs.scope.qualname if cs.scope else "<module>"
        env = _local_env(module, cs.scope, node.lineno, base)
        grid_expr = _kw(node, "grid")
        grid = fold_or_none(grid_expr, env) if grid_expr is not None \
            else None
        if isinstance(grid, (int, float)):
            grid = (int(grid),)
        grid_rank = len(grid) if isinstance(grid, tuple) else None

        in_specs, _ = _spec_elements(_kw(node, "in_specs"))
        out_specs, out_complete = _spec_elements(_kw(node, "out_specs"))
        out_shape_expr = _kw(node, "out_shape")
        out_shapes: List[ast.Call] = []
        if isinstance(out_shape_expr, (ast.List, ast.Tuple)):
            out_shapes = [e for e in out_shape_expr.elts
                          if isinstance(e, ast.Call)]

        if out_complete and out_shapes and \
                len(out_specs) != len(out_shapes):
            findings.append(Finding(
                "pallas-block-rank", module.relpath,
                node.lineno, node.col_offset,
                "pallas_call has %d out_specs but %d out_shape entries"
                % (len(out_specs), len(out_shapes)), ctx))

        total_bytes = 0
        est_ok = True
        for i, spec in enumerate(in_specs + out_specs):
            is_out = i >= len(in_specs)
            lam = _spec_index_map(spec)
            if lam is not None and grid_rank is not None:
                lo, hi = _lambda_arity(lam)
                if not (lo <= grid_rank <= hi):
                    findings.append(Finding(
                        "pallas-index-map-arity", module.relpath,
                        spec.lineno, spec.col_offset,
                        "index_map takes %s args but the grid has rank "
                        "%d" % ("%d-%d" % (lo, hi) if lo != hi else lo,
                                grid_rank), ctx))
            dims_expr = _block_dims(spec)
            if lam is not None and \
                    isinstance(dims_expr, (ast.Tuple, ast.List)):
                coords = _index_map_coords(lam)
                if coords is not None and \
                        coords != len(dims_expr.elts):
                    findings.append(Finding(
                        "pallas-block-rank", module.relpath,
                        spec.lineno, spec.col_offset,
                        "block shape has %d dims but index_map returns "
                        "%d coordinates"
                        % (len(dims_expr.elts), coords), ctx))
            if _is_smem(spec):
                continue
            dims = _fold_dims(dims_expr, env)
            if dims is None:
                est_ok = False
                continue
            size = 1
            for d in dims:
                size *= max(int(d), 1)
            if is_out:
                oi = i - len(in_specs)
                dt = None
                if oi < len(out_shapes) and \
                        len(out_shapes[oi].args) >= 2:
                    dt = out_shapes[oi].args[1]
                total_bytes += size * _dtype_size(dt)
            else:
                total_bytes += size * 2

        scratch_expr = _kw(node, "scratch_shapes")
        if isinstance(scratch_expr, (ast.List, ast.Tuple)):
            for e in scratch_expr.elts:
                if not (isinstance(e, ast.Call) and e.args):
                    continue
                dims = _fold_dims(e.args[0], env)
                if dims is None:
                    est_ok = False
                    continue
                size = 1
                for d in dims:
                    size *= max(int(d), 1)
                dt = e.args[1] if len(e.args) >= 2 else None
                # scratch is VMEM((dims), dtype) — fp32 when unspecified
                total_bytes += size * (_dtype_size(dt)
                                       if dt is not None else 4)

        # attention-shaped kernels materialize one fp32 score tile
        # (block_q, block_k) that no spec describes
        names_used = {n.id for spec in in_specs + out_specs
                      for n in ast.walk(spec)
                      if isinstance(n, ast.Name)}
        if "block_q" in names_used and "block_k" in names_used and \
                isinstance(env.get("block_q"), int) and \
                isinstance(env.get("block_k"), int):
            total_bytes += env["block_q"] * env["block_k"] * 4

        if est_ok and total_bytes and in_specs and \
                total_bytes > clamp:
            findings.append(Finding(
                "pallas-vmem-budget", module.relpath,
                node.lineno, node.col_offset,
                "estimated per-step VMEM working set %.1f MiB exceeds "
                "the %.1f MiB _VMEM_CLAMP budget at default blocks "
                "(block_q=%s, block_k=%s)" % (
                    total_bytes / 1048576.0, clamp / 1048576.0,
                    env.get("block_q"), env.get("block_k")), ctx))

        sem = None
        for sub in ast.walk(node):
            if isinstance(sub, ast.keyword) and \
                    sub.arg == "dimension_semantics":
                sem = sub.value
        if sem is not None and grid_rank is not None and \
                isinstance(sem, (ast.Tuple, ast.List)) and \
                len(sem.elts) != grid_rank:
            findings.append(Finding(
                "pallas-dim-semantics", module.relpath,
                sem.lineno, sem.col_offset,
                "dimension_semantics has %d entries but the grid has "
                "rank %d" % (len(sem.elts), grid_rank), ctx))
    return findings
