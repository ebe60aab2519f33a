"""Per-function tracer-taint analysis (shared by the trace-safety and
retrace checkers, and by the PackageIndex config-param fixpoint).

Flow-insensitive and monotone: values derived from tracer params are
tainted; shape/dtype/len reads, ``is None`` checks, numpy results and
host-sync results are not.  ``for`` targets bind *pairwise* through
``zip``/``enumerate`` so a static index iterated next to a traced value
stays static.
"""
from __future__ import annotations

import ast
from typing import Optional, Set

from .jitgraph import call_target_name, call_target_parts, shallow_walk

# attributes whose value is trace-time Python data even on a tracer
STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "itemsize",
                "sharding", "device", "devices", "aval", "weak_type",
                "committed", "grad_req", "name", "stype", "context"}

# builtins whose result is host/static data regardless of args
STATIC_FUNCS = {"len", "isinstance", "issubclass", "type", "hasattr",
                "getattr", "callable", "id", "repr", "str", "format",
                "range", "print", "sorted_keys",
                # context.on_tpu(*arrays): a Python bool from where concrete
                # operands are committed; a tracer only says "default backend"
                "on_tpu"}

SYNC_BUILTINS = {"float", "int", "bool", "complex"}
SYNC_METHODS = {"item", "tolist", "block_until_ready",
                "copy_to_host_async", "asnumpy"}
NUMPY_ROOTS = {"np", "onp", "numpy"}
ARRAY_ROOTS = {"jnp", "lax", "jax", "pl", "pltpu", "nd", "npx"}

# iteration adapters: Python-level iteration over containers, never a
# direct tracer concretization
_ITER_ADAPTERS = {"zip", "enumerate", "reversed", "sorted", "list",
                  "tuple", "items", "keys", "values"}


class Taint:
    """Taint over one function; closure variables inherit the enclosing
    reachable functions' tracer params."""

    def __init__(self, index, fi):
        self.index = index
        self.fi = fi
        self.tainted: Set[str] = set(index.tracer_params(fi))
        p = fi.parent
        depth = 0
        while p is not None and depth < 4:
            if p.reachable:
                self.tainted |= set(index.tracer_params(p))
            p = p.parent
            depth += 1
        self._fixpoint()

    def _fixpoint(self):
        nodes = self.index.shallow_nodes(self.fi)
        for _ in range(4):
            before = len(self.tainted)
            for stmt in nodes:
                self._visit_binding(stmt)
            if len(self.tainted) == before:
                break

    def _visit_binding(self, node):
        if isinstance(node, ast.Assign):
            if len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Tuple) and \
                    isinstance(node.value, ast.Call) and \
                    self._bind_call_return(node.targets[0], node.value):
                return
            if self.expr(node.value):
                for t in node.targets:
                    self._taint_target(t)
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            if self.expr(node.value):
                self._taint_target(node.target)
        elif isinstance(node, ast.AugAssign):
            if self.expr(node.value) or self.expr(node.target):
                self._taint_target(node.target)
        elif isinstance(node, ast.NamedExpr):
            if self.expr(node.value):
                self._taint_target(node.target)
        elif isinstance(node, ast.For):
            self.bind_loop_target(node.target, node.iter)
        elif isinstance(node, ast.withitem):
            if node.optional_vars is not None and \
                    self.expr(node.context_expr):
                self._taint_target(node.optional_vars)
        elif isinstance(node, (ast.ListComp, ast.SetComp,
                               ast.GeneratorExp, ast.DictComp)):
            for gen in node.generators:
                self.bind_loop_target(gen.target, gen.iter)

    def _bind_call_return(self, target: ast.Tuple, call: ast.Call) -> bool:
        """Per-element taint for `a, b, n = local_helper(...)` when the
        helper's return tuple is statically visible: a helper returning
        (padded_array, ..., new_len) must not taint the shape ints.
        Returns True when handled."""
        callee = self.index.resolve_call(self.fi.module, self.fi,
                                         call.func)
        if callee is None or isinstance(callee.node, ast.Lambda):
            return False
        ct = self.index.taint(callee)
        if ct is None:           # recursion guard hit — stay conservative
            return False
        rets = [r.value for r in self.index.shallow_nodes(callee)
                if isinstance(r, ast.Return) and r.value is not None]
        if len(rets) != 1 or not isinstance(rets[0], ast.Tuple) or \
                len(rets[0].elts) != len(target.elts):
            return False
        for t, e in zip(target.elts, rets[0].elts):
            if ct.expr(e):
                self._taint_target(t)
        return True

    def bind_loop_target(self, target, it):
        """Pairwise binding through zip/enumerate so static loop indices
        next to traced values stay static."""
        if isinstance(it, ast.Call):
            name = call_target_name(it)
            if name == "zip" and isinstance(target, ast.Tuple) and \
                    len(target.elts) == len(it.args):
                for t, a in zip(target.elts, it.args):
                    self.bind_loop_target(t, a)
                return
            if name == "enumerate" and isinstance(target, ast.Tuple) \
                    and len(target.elts) == 2 and it.args:
                # the counter is always a Python int
                self.bind_loop_target(target.elts[1], it.args[0])
                return
            if name in ("reversed", "sorted", "list", "tuple") and \
                    it.args:
                self.bind_loop_target(target, it.args[0])
                return
            if name == "range":
                if any(self.expr(a) for a in it.args):
                    self._taint_target(target)
                return
        if self.expr(it):
            self._taint_target(target)

    def _taint_target(self, t):
        if isinstance(t, ast.Name):
            self.tainted.add(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                self._taint_target(e)
        elif isinstance(t, ast.Starred):
            self._taint_target(t.value)

    # -- expression taint ----------------------------------------------
    def expr(self, node: Optional[ast.expr]) -> bool:
        if node is None:
            return False
        if isinstance(node, ast.Constant):
            return False
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in STATIC_ATTRS:
                return False
            return self.expr(node.value)
        if isinstance(node, ast.Subscript):
            return self.expr(node.value) or self.expr(node.slice)
        if isinstance(node, ast.Compare):
            # `x is None` is an identity check on the Python object
            if all(isinstance(op, (ast.Is, ast.IsNot))
                   for op in node.ops):
                return False
            return self.expr(node.left) or \
                any(self.expr(c) for c in node.comparators)
        if isinstance(node, ast.BoolOp):
            return any(self.expr(v) for v in node.values)
        if isinstance(node, ast.BinOp):
            return self.expr(node.left) or self.expr(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.expr(node.operand)
        if isinstance(node, ast.IfExp):
            return self.expr(node.body) or self.expr(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self.expr(e) for e in node.elts)
        if isinstance(node, ast.Dict):
            return any(self.expr(v) for v in node.values) or \
                any(self.expr(k) for k in node.keys if k is not None)
        if isinstance(node, ast.Starred):
            return self.expr(node.value)
        if isinstance(node, (ast.ListComp, ast.SetComp,
                             ast.GeneratorExp)):
            # targets were pairwise-bound in _visit_binding; the
            # comprehension's value is its element expression
            return self.expr(node.elt)
        if isinstance(node, ast.DictComp):
            return self.expr(node.key) or self.expr(node.value)
        if isinstance(node, ast.Slice):
            return any(self.expr(e) for e in
                       (node.lower, node.upper, node.step))
        if isinstance(node, ast.Call):
            return self.call_taint(node)
        if isinstance(node, (ast.JoinedStr, ast.Lambda)):
            return False
        return any(self.expr(v) for v in ast.iter_child_nodes(node)
                   if isinstance(v, ast.expr))

    def call_taint(self, node: ast.Call) -> bool:
        name = call_target_name(node)
        parts = call_target_parts(node)
        if name in STATIC_FUNCS or name in SYNC_BUILTINS or \
                name in SYNC_METHODS:
            # syncs are flagged elsewhere; their RESULT is host data
            return False
        if name in ("issubdtype", "result_type", "promote_types",
                    "can_cast", "iinfo", "finfo"):
            return False          # dtype algebra is trace-time Python
        if parts and parts[0] in NUMPY_ROOTS:
            return False          # numpy result is host data
        if parts and parts[0] in ARRAY_ROOTS:
            return True           # jnp./lax./jax. produce traced values
        if isinstance(node.func, ast.Attribute):
            # method on a traced object (x.astype, x.sum, x.at[..].set)
            if self.expr(node.func.value):
                return True
        return any(self.expr(a) for a in node.args) or \
            any(self.expr(k.value) for k in node.keywords)


# calls whose result differs per mesh member / host process — the seed
# of the sharding checker's divergent-control-flow analysis
DIVERGENT_CALLS = {"axis_index", "process_index"}
DIVERGENT_ATTRS = {"rank", "process_index"}


class Divergence:
    """Names in one function holding per-shard/per-host varying values
    (derived from ``lax.axis_index``/``jax.process_index``/``.rank``).

    A Python branch over such a value inside a shard_map body executes a
    DIFFERENT trace per member — collectives under it are issued by some
    members and not others, the classic multi-host deadlock.  Same
    flow-insensitive fixpoint shape as :class:`Taint`, but the property
    tracked is member-divergence, not tracedness: shapes and dtypes of
    divergent values are NOT divergent, arithmetic over them is.
    """

    def __init__(self, index, fi):
        self.index = index
        self.fi = fi
        self.divergent: Set[str] = set()
        nodes = index.shallow_nodes(fi)
        for _ in range(4):
            before = len(self.divergent)
            for node in nodes:
                self._visit(node)
            if len(self.divergent) == before:
                break

    def _visit(self, node):
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign,
                             ast.NamedExpr)):
            value = getattr(node, "value", None)
            if value is not None and self.expr(value):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            self.divergent.add(n.id)

    def expr(self, node: Optional[ast.expr]) -> bool:
        if node is None or isinstance(node, (ast.Constant, ast.Lambda)):
            return False
        if isinstance(node, ast.Name):
            return node.id in self.divergent
        if isinstance(node, ast.Attribute):
            if node.attr in DIVERGENT_ATTRS:
                return True
            if node.attr in STATIC_ATTRS:
                return False
            return self.expr(node.value)
        if isinstance(node, ast.Call):
            if call_target_name(node) in DIVERGENT_CALLS:
                return True
            return any(self.expr(a) for a in node.args) or \
                any(self.expr(k.value) for k in node.keywords) or \
                self.expr(node.func)
        return any(self.expr(v) for v in ast.iter_child_nodes(node)
                   if isinstance(v, ast.expr))


def is_iter_adapter(it: ast.expr) -> bool:
    """True when a for-loop's iterable is Python-level container
    iteration (zip/enumerate/.items()/list literals/comprehensions) —
    unrolled at trace time, not a tracer concretization."""
    if isinstance(it, (ast.List, ast.Tuple, ast.ListComp,
                       ast.GeneratorExp, ast.Dict, ast.Set)):
        return True
    if isinstance(it, ast.Call):
        return call_target_name(it) in _ITER_ADAPTERS
    return False
