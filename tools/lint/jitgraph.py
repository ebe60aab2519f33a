"""Shared framework-aware AST analysis for graftlint.

Builds, over the whole scanned file set:

* a function table (module-level defs, methods, nested defs, lambdas)
  and a class table with package-internal inheritance, so gluon
  ``forward``/``hybrid_forward`` methods of Block-like classes are
  recognized as trace entry points;
* a call-site table with lexical scopes, feeding three analyses:
* **jit-reachability**: a function is jit-reachable when it is
  (a) decorated with / passed to a JAX tracing wrapper (``jax.jit``,
  ``vmap``, ``grad``, ``lax.scan``, ``pl.pallas_call``, ``defvjp``, …),
  (b) registered as a graph op via ``@register`` (ops run under the
  executor's jit), (c) a ``forward``/``hybrid_forward`` method of a
  Block-like class, or (d) called (directly, via ``self.``, or through a
  jit-forwarding helper parameter like ``_mirror_wrap``) from a
  jit-reachable function;
* **config params**: an interprocedural fixpoint marking parameters that
  only ever receive trace-time Python configuration (scalar defaults,
  keyword-only params, ``static_argnums``/``static_argnames``
  declarations, or call sites that always pass literals / other config
  params) — everything else positional is a *tracer param*;
* a small constant folder (ints/tuples, ``min``/``max``/shifts/
  ``bit_length``) used to evaluate ``donate_argnums`` and Pallas block
  shapes statically.
"""
from __future__ import annotations

import ast
from collections import deque
from typing import Dict, List, Optional, Sequence, Set, Tuple

# tracing wrappers: any function-valued argument of a call to one of
# these is traced (and therefore jit-reachable).  Matched on the LAST
# attribute segment so jax.jit / pl.pallas_call / lax.scan all resolve
# without import tracking.
TRACING_WRAPPERS = {
    "jit", "pjit", "pmap", "vmap", "grad", "value_and_grad", "vjp",
    "jvp", "linearize", "checkpoint", "remat", "custom_vjp",
    "custom_jvp", "pallas_call", "scan", "fori_loop", "while_loop",
    "cond", "switch", "associative_scan", "defvjp", "defjvp",
    "named_call", "shard_map", "xmap",
}
# "map" only counts when spelled lax.map / jax.lax.map (bare map() is
# the builtin)
_QUALIFIED_ONLY = {"map": ("lax", "jax")}

# keyword arguments of wrapper calls that are never traced functions
_NON_FN_KWARGS = {"static_argnums", "static_argnames", "donate_argnums",
                  "donate_argnames", "policy", "in_axes", "out_axes",
                  "axis_name", "grid", "in_specs", "out_specs",
                  "out_shape", "scratch_shapes", "compiler_params",
                  "interpret", "length", "reverse", "unroll",
                  "has_aux", "prevent_cse", "dimension_semantics"}

# decorators that make a function a trace entry on their own
ENTRY_DECORATORS = {"register", "custom_vjp", "custom_jvp"}

# gluon Block-like root classes: forward/hybrid_forward methods of their
# (transitive, package-internal) subclasses run under the fused train
# step's jit
BLOCK_ROOTS = {"Block", "HybridBlock", "SymbolBlock", "Loss"}
BLOCK_ENTRY_METHODS = {"forward", "hybrid_forward"}

_SCALAR_CONST = (int, float, bool, str, bytes)


def shallow_walk(node):
    """ast.walk that does NOT descend into nested function/class bodies:
    the caller analyzes exactly one function's own statements (a nested
    def has its own reachability and its own tracer params)."""
    todo = deque(ast.iter_child_nodes(node))
    while todo:
        n = todo.popleft()
        yield n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda, ast.ClassDef)):
            continue
        todo.extend(ast.iter_child_nodes(n))


def call_target_name(node: ast.Call) -> Optional[str]:
    """Last dotted segment of the callee ('jax.jit' -> 'jit')."""
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def call_target_parts(node: ast.Call) -> Tuple[str, ...]:
    parts: List[str] = []
    f = node.func
    while isinstance(f, ast.Attribute):
        parts.append(f.attr)
        f = f.value
    if isinstance(f, ast.Name):
        parts.append(f.id)
    return tuple(reversed(parts))


def is_tracing_wrapper_call(node: ast.Call) -> bool:
    name = call_target_name(node)
    if name is None:
        return False
    if name in _QUALIFIED_ONLY:
        parts = call_target_parts(node)
        return len(parts) >= 2 and parts[-2] in _QUALIFIED_ONLY[name]
    return name in TRACING_WRAPPERS


def _is_scalar_config(node: ast.expr) -> bool:
    if isinstance(node, ast.Constant):
        return node.value is None or isinstance(node.value, _SCALAR_CONST)
    if isinstance(node, ast.UnaryOp) and \
            isinstance(node.op, (ast.USub, ast.UAdd)):
        return _is_scalar_config(node.operand)
    if isinstance(node, (ast.Tuple, ast.List)):
        return all(_is_scalar_config(e) for e in node.elts)
    return False


def _has_scalar_default(fi: "FunctionInfo", name: str) -> bool:
    ps = fi.params()
    a = fi.node.args
    if a.defaults:
        for p, d in zip(ps[len(ps) - len(a.defaults):], a.defaults):
            if p.arg == name:
                # None defaults stay traced: optional array operands
                # (kv_lens=None) are the dominant pattern
                return not (isinstance(d, ast.Constant)
                            and d.value is None) and _is_scalar_config(d)
    return False


class FunctionInfo:
    """One function/method/lambda definition."""

    def __init__(self, module, node, qualname: str,
                 parent: Optional["FunctionInfo"], cls: Optional[str]):
        self.module = module                  # core.ModuleInfo
        self.node = node
        self.qualname = qualname
        self.parent = parent
        self.cls = cls                        # enclosing class name or None
        self.reachable = False
        self.entry_reason: Optional[str] = None
        # static params declared at jit sites wrapping this function
        self.static_params: Set[str] = set()
        self.is_method = cls is not None

    @property
    def name(self) -> str:
        if isinstance(self.node, ast.Lambda):
            return "<lambda>"
        return self.node.name

    def params(self) -> List[ast.arg]:
        a = self.node.args
        return list(a.posonlyargs) + list(a.args)

    def param_names(self) -> List[str]:
        return [p.arg for p in self.params()]

    def kwonly_names(self) -> List[str]:
        return [p.arg for p in self.node.args.kwonlyargs]

    def default_expr(self, name: str) -> Optional[ast.expr]:
        """The default-value AST node of parameter ``name`` (positional
        or keyword-only), or None."""
        a = self.node.args
        ps = self.params()
        if a.defaults:
            for p, d in zip(ps[len(ps) - len(a.defaults):], a.defaults):
                if p.arg == name:
                    return d
        for p, d in zip(a.kwonlyargs, a.kw_defaults):
            if p.arg == name and d is not None:
                return d
        return None


class CallSite:
    __slots__ = ("module", "scope", "node", "callee")

    def __init__(self, module, scope, node, callee):
        self.module = module
        self.scope = scope        # FunctionInfo containing the call (or None)
        self.node = node
        self.callee = callee      # resolved FunctionInfo or None


class _ClassInfo:
    def __init__(self, module, node: ast.ClassDef):
        self.module = module
        self.node = node
        self.name = node.name
        self.base_names = []
        for b in node.bases:
            if isinstance(b, ast.Name):
                self.base_names.append(b.id)
            elif isinstance(b, ast.Attribute):
                self.base_names.append(b.attr)


class PackageIndex:
    """Cross-file function/class index + jit-reachability fixpoint."""

    def __init__(self, modules: Sequence):
        self.modules = list(modules)
        self.functions: List[FunctionInfo] = []
        self.by_node: Dict[int, FunctionInfo] = {}
        self.toplevel: Dict[Tuple[str, str], FunctionInfo] = {}
        self.methods: Dict[Tuple[str, str, str], FunctionInfo] = {}
        self.classes: Dict[Tuple[str, str], _ClassInfo] = {}
        self.imports: Dict[str, Dict[str, str]] = {}
        # per-module absolute dotted import candidates (module-dep graph
        # feeding the --changed reverse-dependency closure)
        self._import_targets: Dict[str, Set[str]] = {}
        # direct named children per function node (nested-def lookup)
        self._children: Dict[int, Dict[str, FunctionInfo]] = {}
        for m in modules:
            self._collect(m)
        self._toplevel_by_name: Dict[str, List[FunctionInfo]] = {}
        for (rel, nm), fi in self.toplevel.items():
            self._toplevel_by_name.setdefault(nm, []).append(fi)
        self.call_sites: List[CallSite] = []
        self._calls_by_scope: Dict[int, List[CallSite]] = {}
        self._calls_by_callee: Dict[int, List[CallSite]] = {}
        for m in modules:
            self._collect_calls(m)
        self._blocklike = self._compute_blocklike()
        self._jit_forwarding = self._compute_jit_forwarding_params()
        self._mark_entries()
        self._propagate()
        self._config = self._compute_config_params()
        self._taint_cache: Dict[int, object] = {}
        self._taint_in_progress: Set[int] = set()
        self._shallow_cache: Dict[int, List] = {}
        self._refine_config()

    # -- collection -----------------------------------------------------
    def _collect(self, module):
        imports: Dict[str, str] = {}
        targets: Set[str] = set()
        pkg = module.relpath.rsplit("/", 1)[0].split("/") \
            if "/" in module.relpath else []

        def walk(node, parent_fn, cls_name, prefix):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.Import, ast.ImportFrom)):
                    if isinstance(child, ast.Import):
                        for alias in child.names:
                            targets.add(alias.name)
                    else:
                        # resolve relative levels against this module's
                        # package: level=1 -> same package, level=2 ->
                        # parent, ...; each imported name may itself be
                        # a submodule (`from . import telemetry`)
                        base = pkg[:len(pkg) - (child.level - 1)] \
                            if child.level else []
                        parts = base + (child.module.split(".")
                                        if child.module else [])
                        mod = ".".join(parts)
                        if mod:
                            targets.add(mod)
                        for alias in child.names:
                            if mod and alias.name != "*":
                                targets.add(mod + "." + alias.name)
                    for alias in child.names:
                        local = alias.asname or alias.name.split(".")[0]
                        imports[local] = alias.name
                    continue
                if isinstance(child, ast.ClassDef):
                    self.classes[(module.relpath, child.name)] = \
                        _ClassInfo(module, child)
                    walk(child, None, child.name,
                         prefix + child.name + ".")
                    continue
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    fi = FunctionInfo(module, child, prefix + child.name,
                                      parent_fn, cls_name)
                    self._register_fn(fi)
                    walk(child, fi, cls_name if parent_fn is None
                         else None, prefix + child.name + ".")
                    continue
                if isinstance(child, ast.Lambda):
                    fi = FunctionInfo(
                        module, child,
                        prefix + "<lambda@%d>" % child.lineno,
                        parent_fn, cls_name)
                    self._register_fn(fi)
                    walk(child, fi, None, fi.qualname + ".")
                    continue
                walk(child, parent_fn, cls_name, prefix)

        walk(module.tree, None, None, "")
        self.imports[module.relpath] = imports
        self._import_targets[module.relpath] = targets

    def _register_fn(self, fi: FunctionInfo):
        self.functions.append(fi)
        self.by_node[id(fi.node)] = fi
        if fi.parent is not None and \
                not isinstance(fi.node, ast.Lambda):
            self._children.setdefault(id(fi.parent.node), {}) \
                .setdefault(fi.name, fi)
        if fi.parent is None and fi.cls is None and \
                not isinstance(fi.node, ast.Lambda):
            self.toplevel.setdefault((fi.module.relpath, fi.name), fi)
        if fi.parent is None and fi.cls is not None and \
                not isinstance(fi.node, ast.Lambda):
            self.methods[(fi.module.relpath, fi.cls, fi.name)] = fi

    def _collect_calls(self, module):
        def walk(node, scope):
            for child in ast.iter_child_nodes(node):
                inner = self.by_node.get(id(child))
                nscope = inner if inner is not None else scope
                if isinstance(child, ast.Call):
                    callee = self.resolve_call(module, nscope, child.func)
                    cs = CallSite(module, nscope, child, callee)
                    self.call_sites.append(cs)
                    if nscope is not None:
                        self._calls_by_scope.setdefault(
                            id(nscope.node), []).append(cs)
                    if callee is not None:
                        self._calls_by_callee.setdefault(
                            id(callee.node), []).append(cs)
                walk(child, nscope)

        walk(module.tree, None)

    # -- class hierarchy ------------------------------------------------
    def _compute_blocklike(self) -> Set[Tuple[str, str]]:
        blocklike: Set[Tuple[str, str]] = set()
        names_block: Set[str] = set(BLOCK_ROOTS)
        changed = True
        while changed:
            changed = False
            for key, ci in self.classes.items():
                if key in blocklike:
                    continue
                if any(b in names_block for b in ci.base_names):
                    blocklike.add(key)
                    names_block.add(ci.name)
                    changed = True
        return blocklike

    # -- resolution -----------------------------------------------------
    def resolve_call(self, module, scope: Optional[FunctionInfo],
                     node: ast.expr) -> Optional[FunctionInfo]:
        """Resolve a callee/argument expression to a FunctionInfo."""
        if isinstance(node, ast.Lambda):
            return self.by_node.get(id(node))
        if isinstance(node, ast.Call):
            # functools.partial(f, ...) — analysis follows f
            if call_target_name(node) == "partial" and node.args:
                return self.resolve_call(module, scope, node.args[0])
            return None
        if isinstance(node, ast.Name):
            s = scope
            while s is not None:
                hit = self._nested_def(s, node.id)
                if hit is not None:
                    return hit
                s = s.parent
            hit = self.toplevel.get((module.relpath, node.id))
            if hit is not None:
                return hit
            target = self.imports.get(module.relpath, {}).get(node.id)
            lookup = target.split(".")[-1] if target else node.id
            cands = self._toplevel_by_name.get(lookup, ())
            if len(cands) == 1:
                return cands[0]
            return None
        if isinstance(node, ast.Attribute):
            if isinstance(node.value, ast.Name) and \
                    node.value.id == "self" and scope is not None:
                s, cls = scope, None
                while s is not None and cls is None:
                    cls = s.cls
                    s = s.parent
                if cls is not None:
                    return self.methods.get(
                        (module.relpath, cls, node.attr))
            cands = self._toplevel_by_name.get(node.attr, ())
            if len(cands) == 1:
                return cands[0]
        return None

    def _nested_def(self, scope: FunctionInfo, name: str
                    ) -> Optional[FunctionInfo]:
        return self._children.get(id(scope.node), {}).get(name)

    # -- jit-forwarding helper params -----------------------------------
    def _compute_jit_forwarding_params(self) -> Dict[int, Set[int]]:
        """For helpers like ``_mirror_wrap(fn, mode)`` that pass a
        parameter into a tracing wrapper (``jax.checkpoint(fn)``): the
        parameter indices that forward their argument into a trace."""
        out: Dict[int, Set[int]] = {}
        for fi in self.functions:
            if isinstance(fi.node, ast.Lambda):
                continue
            names = fi.param_names()
            fwd: Set[int] = set()
            for sub in ast.walk(fi.node):
                if isinstance(sub, ast.Call) and \
                        is_tracing_wrapper_call(sub):
                    for a in sub.args:
                        if isinstance(a, ast.Name) and a.id in names:
                            fwd.add(names.index(a.id))
            if fwd:
                out[id(fi.node)] = fwd
        return out

    # -- entry marking --------------------------------------------------
    def _static_decls(self, call: ast.Call, target: FunctionInfo):
        names = target.param_names()
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                for v in _iter_str_constants(kw.value):
                    target.static_params.add(v)
            elif kw.arg == "static_argnums":
                for v in _iter_int_constants(kw.value):
                    if 0 <= v < len(names):
                        target.static_params.add(names[v])

    def _mark_entry(self, fi: FunctionInfo, reason: str):
        if not fi.reachable:
            fi.reachable = True
            fi.entry_reason = reason

    def _custom_vjp_links(self):
        """custom_vjp nondiff awareness: ``@partial(jax.custom_vjp,
        nondiff_argnums=(i,...))`` marks those params static on the
        primal; ``primal.defvjp(fwd, bwd)`` mirrors them onto the fwd
        (same positions) and the bwd (its LEADING len(nondiff) params —
        jax passes nondiff args first to the bwd)."""
        nondiff: Dict[int, Tuple[int, ...]] = {}
        for fi in self.functions:
            if isinstance(fi.node, ast.Lambda):
                continue
            for dec in fi.node.decorator_list:
                if not isinstance(dec, ast.Call):
                    continue
                if call_target_name(dec) != "partial" or not dec.args:
                    continue
                wrapped = dec.args[0]
                wname = wrapped.attr if isinstance(wrapped, ast.Attribute) \
                    else (wrapped.id if isinstance(wrapped, ast.Name)
                          else None)
                if wname != "custom_vjp":
                    continue
                inner = dec
                idxs = []
                for kw in inner.keywords:
                    if kw.arg == "nondiff_argnums":
                        idxs = list(_iter_int_constants(kw.value))
                names = fi.param_names()
                for i in idxs:
                    if 0 <= i < len(names):
                        fi.static_params.add(names[i])
                if idxs:
                    nondiff[id(fi.node)] = tuple(sorted(idxs))
        for cs in self.call_sites:
            node = cs.node
            if not (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "defvjp" and len(node.args) >= 2):
                continue
            primal = self.resolve_call(cs.module, cs.scope,
                                       node.func.value)
            if primal is None:
                continue
            idxs = nondiff.get(id(primal.node), ())
            if not idxs:
                continue
            fwd = self.resolve_call(cs.module, cs.scope, node.args[0])
            bwd = self.resolve_call(cs.module, cs.scope, node.args[1])
            if fwd is not None:
                names = fwd.param_names()
                for i in idxs:
                    if 0 <= i < len(names):
                        fwd.static_params.add(names[i])
            if bwd is not None:
                names = bwd.param_names()
                for n in names[:len(idxs)]:
                    bwd.static_params.add(n)

    def _mark_entries(self):
        self._custom_vjp_links()
        for fi in self.functions:
            node = fi.node
            if not isinstance(node, ast.Lambda):
                for dec in node.decorator_list:
                    dname = None
                    if isinstance(dec, ast.Call):
                        dname = call_target_name(dec)
                    elif isinstance(dec, ast.Name):
                        dname = dec.id
                    elif isinstance(dec, ast.Attribute):
                        dname = dec.attr
                    if dname in ENTRY_DECORATORS or \
                            dname in TRACING_WRAPPERS:
                        self._mark_entry(fi, "decorator:%s" % dname)
                        if isinstance(dec, ast.Call):
                            self._static_decls(dec, fi)
            if fi.is_method and fi.parent is None and \
                    fi.name in BLOCK_ENTRY_METHODS and \
                    (fi.module.relpath, fi.cls) in self._blocklike and \
                    "gluon/data/" not in fi.module.relpath:
                # gluon.data transforms are Blocks by API but execute
                # host-side in DataLoader workers — not trace entries
                self._mark_entry(fi, "block-forward")
        for cs in self.call_sites:
            if not is_tracing_wrapper_call(cs.node):
                continue
            for a in list(cs.node.args) + \
                    [k.value for k in cs.node.keywords
                     if k.arg not in _NON_FN_KWARGS]:
                fi = self.resolve_call(cs.module, cs.scope, a)
                if fi is not None:
                    self._mark_entry(fi, "wrapped:%s"
                                     % call_target_name(cs.node))
                    if call_target_name(cs.node) in ("jit", "pjit"):
                        self._static_decls(cs.node, fi)

    # -- propagation ----------------------------------------------------
    def _propagate(self):
        changed = True
        while changed:
            changed = False
            for cs in self.call_sites:
                if cs.scope is None or not cs.scope.reachable:
                    continue
                if cs.callee is not None and not cs.callee.reachable:
                    cs.callee.reachable = True
                    cs.callee.entry_reason = \
                        "called-from:%s" % cs.scope.qualname
                    changed = True
                if cs.callee is not None:
                    fwd = self._jit_forwarding.get(id(cs.callee.node), ())
                    for idx in fwd:
                        if idx < len(cs.node.args):
                            g = self.resolve_call(cs.module, cs.scope,
                                                  cs.node.args[idx])
                            if g is not None and not g.reachable:
                                g.reachable = True
                                g.entry_reason = "forwarded-via:%s" % \
                                    cs.callee.qualname
                                changed = True

    # -- config params --------------------------------------------------
    def _bind_args(self, cs: CallSite) -> Optional[Dict[str, ast.expr]]:
        """Map call arguments onto the callee's parameter names; None if
        the call uses */** unpacking (binding unknown)."""
        fi = cs.callee
        if any(isinstance(a, ast.Starred) for a in cs.node.args) or \
                any(k.arg is None for k in cs.node.keywords):
            return None
        names = fi.param_names()
        if names and names[0] in ("self", "cls") and fi.is_method and \
                isinstance(cs.node.func, ast.Attribute):
            names = names[1:]
        bound: Dict[str, ast.expr] = {}
        for i, a in enumerate(cs.node.args):
            if i < len(names):
                bound[names[i]] = a
        for k in cs.node.keywords:
            bound[k.arg] = k.value
        return bound

    def _compute_config_params(self) -> Set[Tuple[int, str]]:
        """Fixpoint of (function-node-id, param) pairs that are
        trace-time Python config rather than traced arrays."""
        config: Set[Tuple[int, str]] = set()
        for fi in self.functions:
            # mxnet op convention: a @register-ed op's params WITH
            # defaults (None included) are op ATTRIBUTES — Python config
            # baked into the graph — only default-less positionals are
            # tensor inputs
            is_op = not isinstance(fi.node, ast.Lambda) and any(
                (isinstance(d, ast.Call)
                 and call_target_name(d) == "register")
                or (isinstance(d, ast.Name) and d.id == "register")
                for d in fi.node.decorator_list)
            defaulted: Set[str] = set()
            ps = fi.params()
            nd = len(fi.node.args.defaults)
            if nd:
                defaulted = {p.arg for p in ps[len(ps) - nd:]}
            for n in fi.param_names():
                if n in ("self", "cls") or n in fi.static_params or \
                        _has_scalar_default(fi, n) or \
                        (is_op and n in defaulted):
                    config.add((id(fi.node), n))
            for n in fi.kwonly_names():
                config.add((id(fi.node), n))

        def arg_is_config(cs: CallSite, expr: ast.expr) -> bool:
            if _is_scalar_config(expr):
                return True
            if isinstance(expr, ast.Name) and cs.scope is not None:
                return (id(cs.scope.node), expr.id) in config
            return False

        changed = True
        while changed:
            changed = False
            for fi in self.functions:
                sites = self._calls_by_callee.get(id(fi.node), ())
                if not sites:
                    continue
                bindings = [self._bind_args(cs) for cs in sites]
                if any(b is None for b in bindings):
                    continue
                for n in fi.param_names():
                    if (id(fi.node), n) in config or n in ("self", "cls"):
                        continue
                    exprs = [(cs, b[n]) for cs, b in zip(sites, bindings)
                             if n in b]
                    if exprs and all(arg_is_config(cs, e)
                                     for cs, e in exprs):
                        config.add((id(fi.node), n))
                        changed = True
        return config

    def _refine_config(self):
        """Second config fixpoint using caller taint: a parameter whose
        every observed argument is UNTAINTED in its caller (a loop index,
        a shape read, a folded constant) is trace-time config, not a
        tracer.  Monotone — config only grows, taint only shrinks.
        Runs to convergence (bound = #functions, the longest possible
        caller->helper chain): config-hood must reach the bottom of
        deep trace-time helper chains."""
        for _ in range(max(2, len(self.functions))):
            self._taint_cache = {}
            changed = False
            for fi in self.functions:
                sites = self._calls_by_callee.get(id(fi.node), ())
                if not sites:
                    continue
                bindings = [self._bind_args(cs) for cs in sites]
                if any(b is None for b in bindings):
                    continue
                ps = fi.params()
                nd = len(fi.node.args.defaults)
                defaulted = {p.arg for p in ps[len(ps) - nd:]} if nd \
                    else set()
                for n in fi.param_names():
                    if (id(fi.node), n) in self._config or \
                            n in ("self", "cls"):
                        continue
                    exprs = [(cs, b[n]) for cs, b in zip(sites, bindings)
                             if n in b]
                    if exprs:
                        ok = all(self._arg_untainted(cs, e)
                                 for cs, e in exprs)
                    else:
                        # bound at NO observed site: the param always
                        # takes its (scalar) default
                        ok = n in defaulted
                    if ok:
                        self._config.add((id(fi.node), n))
                        changed = True
            if not changed:
                break
        self._taint_cache = {}

    def _arg_untainted(self, cs: CallSite, expr: ast.expr) -> bool:
        if cs.scope is None:
            return _is_scalar_config(expr)
        if not cs.scope.reachable:
            # a caller that is not jit-reachable executes host-side
            # only — its arguments are plain Python values by
            # construction and cannot carry tracers into the callee.
            # Without this, host-only entry points poison config-hood
            # of the trace-time helpers they share with a jitted caller.
            return True
        t = self.taint(cs.scope)
        return t is not None and not t.expr(expr)

    def shallow_nodes(self, fi: FunctionInfo):
        """Cached list(shallow_walk(fi.node)) — taint fixpoints and the
        per-function checkers traverse each function many times."""
        nodes = self._shallow_cache.get(id(fi.node))
        if nodes is None:
            nodes = list(shallow_walk(fi.node))
            self._shallow_cache[id(fi.node)] = nodes
        return nodes

    def taint(self, fi: FunctionInfo):
        """Cached per-function Taint analysis.  Returns None when ``fi``
        is already being analyzed (recursive helper chains) — callers
        fall back to conservative whole-value taint."""
        key = id(fi.node)
        t = self._taint_cache.get(key)
        if t is not None:
            return t
        if key in self._taint_in_progress:
            return None
        self._taint_in_progress.add(key)
        try:
            from .tainting import Taint
            t = Taint(self, fi)
        finally:
            self._taint_in_progress.discard(key)
        self._taint_cache[key] = t
        return t

    # -- module-dependency graph (--changed closure) --------------------
    @staticmethod
    def module_dotted(relpath: str) -> str:
        """Dotted module name of a repo-relative path
        ('mxnet_tpu/parallel/mesh.py' -> 'mxnet_tpu.parallel.mesh';
        a package __init__ maps to the package name)."""
        p = relpath[:-3] if relpath.endswith(".py") else relpath
        parts = p.split("/")
        if parts and parts[-1] == "__init__":
            parts = parts[:-1]
        return ".".join(parts)

    def module_deps(self) -> Dict[str, Set[str]]:
        """relpath -> set of relpaths (within the scanned set) it
        imports, resolved through relative levels and
        `from pkg import submodule` forms."""
        by_name = {self.module_dotted(m.relpath): m.relpath
                   for m in self.modules}
        deps: Dict[str, Set[str]] = {}
        for m in self.modules:
            out: Set[str] = set()
            for cand in self._import_targets.get(m.relpath, ()):
                hit = by_name.get(cand)
                if hit is not None and hit != m.relpath:
                    out.add(hit)
            deps[m.relpath] = out
        return deps

    def reverse_dependency_closure(self, changed) -> Set[str]:
        """relpaths that transitively import any of ``changed``
        (changed files themselves included) — the set whose findings can
        move when ``changed`` moves."""
        deps = self.module_deps()
        rev: Dict[str, Set[str]] = {}
        for src, outs in deps.items():
            for dst in outs:
                rev.setdefault(dst, set()).add(src)
        known = {m.relpath for m in self.modules}
        todo = deque(c for c in changed if c in known)
        seen: Set[str] = set(todo)
        while todo:
            cur = todo.popleft()
            for imp in rev.get(cur, ()):
                if imp not in seen:
                    seen.add(imp)
                    todo.append(imp)
        return seen

    # -- host-thread entries (concurrency checker) ----------------------
    def _resolve_thread_target(self, cs: CallSite, node: ast.expr
                               ) -> Optional[FunctionInfo]:
        """Resolve a ``threading.Thread(target=...)`` expression: plain
        names and ``partial`` ride :meth:`resolve_call`; ``Cls.method``
        spellings (the prefetcher's ``DevicePrefetchIter._feed``) and
        ``self.method`` resolve through the method table."""
        if isinstance(node, ast.Call) and \
                call_target_name(node) == "partial" and node.args:
            return self._resolve_thread_target(cs, node.args[0])
        if isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name):
            hit = self.methods.get(
                (cs.module.relpath, node.value.id, node.attr))
            if hit is not None:
                return hit
        return self.resolve_call(cs.module, cs.scope, node)

    def thread_entries(self) -> Dict[int, str]:
        """{function-node-id: entry description} for every function a
        ``threading.Thread(target=...)`` call site names (the host-side
        analogue of :meth:`_mark_entries`' tracing wrappers)."""
        cached = getattr(self, "_thread_entries", None)
        if cached is not None:
            return cached
        entries: Dict[int, str] = {}
        for cs in self.call_sites:
            if call_target_name(cs.node) != "Thread":
                continue
            parts = call_target_parts(cs.node)
            if len(parts) > 1 and parts[-2] != "threading":
                continue
            target = None
            for kw in cs.node.keywords:
                if kw.arg == "target":
                    target = kw.value
            if target is None and len(cs.node.args) > 1:
                # threading.Thread(group, target, ...) positional form
                target = cs.node.args[1]
            if target is None:
                continue
            fi = self._resolve_thread_target(cs, target)
            if fi is not None:
                entries.setdefault(
                    id(fi.node), "%s:%d" % (cs.module.relpath,
                                            cs.node.lineno))
        self._thread_entries = entries
        return entries

    def thread_reachable(self) -> Set[int]:
        """Function-node-ids reachable from a thread entry — the code
        that runs OFF the main thread.  Propagation follows resolved
        call sites plus one receiver-blind step: inside a
        thread-reachable function of class ``C``, an unresolved
        ``<expr>.m(...)`` call resolves to ``C.m`` when it exists (the
        weakref-deref idiom ``it = wref(); it._ship(...)``)."""
        cached = getattr(self, "_thread_reachable", None)
        if cached is not None:
            return cached
        reach: Set[int] = set(self.thread_entries())
        changed = True
        while changed:
            changed = False
            for cs in self.call_sites:
                if cs.scope is None or id(cs.scope.node) not in reach:
                    continue
                callee = cs.callee
                if callee is None and \
                        isinstance(cs.node.func, ast.Attribute):
                    s, cls = cs.scope, None
                    while s is not None and cls is None:
                        cls = s.cls
                        s = s.parent
                    if cls is not None:
                        callee = self.methods.get(
                            (cs.module.relpath, cls, cs.node.func.attr))
                if callee is not None and id(callee.node) not in reach:
                    reach.add(id(callee.node))
                    changed = True
        self._thread_reachable = reach
        return reach

    # -- queries --------------------------------------------------------
    def function_at(self, node) -> Optional[FunctionInfo]:
        return self.by_node.get(id(node))

    def functions_in(self, module) -> List[FunctionInfo]:
        # cached: every checker iterates per module, and a linear scan
        # of the whole function table per (checker, module) pair is the
        # dominant cost of a full-package run
        cache = getattr(self, "_fns_by_module", None)
        if cache is None:
            cache = {}
            for fi in self.functions:
                cache.setdefault(id(fi.module), []).append(fi)
            self._fns_by_module = cache
        return cache.get(id(module), [])

    def calls_in(self, module) -> List[CallSite]:
        """All call sites lexically in ``module`` (cached, source
        order)."""
        cache = getattr(self, "_calls_by_module", None)
        if cache is None:
            cache = {}
            for cs in self.call_sites:
                cache.setdefault(id(cs.module), []).append(cs)
            self._calls_by_module = cache
        return cache.get(id(module), [])

    def calls_in_scope(self, fi: FunctionInfo) -> List[CallSite]:
        return self._calls_by_scope.get(id(fi.node), [])

    def is_config_param(self, fi: FunctionInfo, name: str) -> bool:
        return (id(fi.node), name) in self._config

    def tracer_params(self, fi: FunctionInfo) -> Set[str]:
        """Positional parameters treated as traced array values."""
        out: Set[str] = set()
        for n in fi.param_names():
            if n in ("self", "cls"):
                continue
            if (id(fi.node), n) in self._config:
                continue
            out.add(n)
        return out


def _iter_str_constants(node):
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
            yield sub.value


def _iter_int_constants(node):
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, int) \
                and not isinstance(sub.value, bool):
            yield sub.value


# ---------------------------------------------------------------------------
# constant folding
# ---------------------------------------------------------------------------

class NotConst(Exception):
    pass


_BINOPS = {
    ast.Add: lambda a, b: a + b,
    ast.Sub: lambda a, b: a - b,
    ast.Mult: lambda a, b: a * b,
    ast.FloorDiv: lambda a, b: a // b,
    ast.Div: lambda a, b: a / b,
    ast.Mod: lambda a, b: a % b,
    ast.Pow: lambda a, b: a ** b,
    ast.LShift: lambda a, b: a << b,
    ast.RShift: lambda a, b: a >> b,
    ast.BitOr: lambda a, b: a | b,
    ast.BitAnd: lambda a, b: a & b,
}

_CMPOPS = {
    ast.Lt: lambda a, b: a < b, ast.LtE: lambda a, b: a <= b,
    ast.Gt: lambda a, b: a > b, ast.GtE: lambda a, b: a >= b,
    ast.Eq: lambda a, b: a == b, ast.NotEq: lambda a, b: a != b,
}


def fold(node: ast.expr, env: Optional[Dict[str, object]] = None):
    """Evaluate an int/tuple expression statically; raises NotConst.

    Supports the arithmetic this codebase uses for block sizing:
    literals, names from ``env``, +,-,*,//,/,%,**,<<,>>, unary -,
    min/max/abs/int/round, ``x.bit_length()``, tuples, subscripts, and
    conditional expressions with foldable tests."""
    env = env or {}
    if isinstance(node, ast.Constant):
        if node.value is None or isinstance(node.value, _SCALAR_CONST):
            return node.value
        raise NotConst()
    if isinstance(node, ast.Name):
        if node.id in env and env[node.id] is not None:
            return env[node.id]
        raise NotConst()
    if isinstance(node, ast.BinOp):
        op = _BINOPS.get(type(node.op))
        if op is None:
            raise NotConst()
        return op(fold(node.left, env), fold(node.right, env))
    if isinstance(node, ast.UnaryOp):
        v = fold(node.operand, env)
        if isinstance(node.op, ast.USub):
            return -v
        if isinstance(node.op, ast.UAdd):
            return +v
        if isinstance(node.op, ast.Not):
            return not v
        raise NotConst()
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(fold(e, env) for e in node.elts)
    if isinstance(node, ast.Call):
        name = call_target_name(node)
        if name in ("min", "max", "abs", "int", "float", "round") \
                and node.args and not node.keywords:
            args = [fold(a, env) for a in node.args]
            return {"min": min, "max": max, "abs": abs, "int": int,
                    "float": float, "round": round}[name](*args)
        if name == "bit_length" and isinstance(node.func, ast.Attribute):
            return fold(node.func.value, env).bit_length()
        raise NotConst()
    if isinstance(node, ast.IfExp):
        return fold(node.body, env) if fold(node.test, env) \
            else fold(node.orelse, env)
    if isinstance(node, ast.Compare) and len(node.ops) == 1:
        f = _CMPOPS.get(type(node.ops[0]))
        if f is None:
            raise NotConst()
        return f(fold(node.left, env), fold(node.comparators[0], env))
    if isinstance(node, ast.Subscript):
        v = fold(node.value, env)
        i = fold(node.slice, env)
        return v[i]
    raise NotConst()


def fold_or_none(node, env=None):
    try:
        return fold(node, env)
    except Exception:
        return None
