"""Cross-module jit-reachability: which function defs get TRACED.

Used by the jit-purity and recompile-hazard passes. A function is
considered traced (its body runs under ``jax.jit``/``pjit``/
``pallas_call``/another tracing HOF) when:

* it is decorated with a jit wrapper (``@jax.jit``, ``@pjit``,
  ``@partial(jax.jit, ...)``), or
* it is passed to a jit wrapper or tracing higher-order function
  (``jax.jit(f)``, ``jax.jit(self._step)``, ``pl.pallas_call(kern)``,
  ``lax.scan(body, ...)``, ``jax.grad(f)``, ...), or
* it is called (by bare name / ``self.X`` / imported name /
  imported-module attribute) from a traced function, transitively —
  resolution follows ``from X import Y`` edges between the analyzed
  files, so e.g. ``models/generation._sample`` is traced because
  ``serving/engine._ragged_step`` (a ``jax.jit`` root) calls it;
* it is lexically nested inside a traced function (``lax.scan``
  bodies, closure helpers — conservatively traced).

This is a lint heuristic, not a soundness proof: dynamic dispatch
(``self._ad.ragged_chunk``) and call-by-value function arguments are
invisible, and a function traced via an un-analyzed path is missed.
That trade keeps the false-positive rate near zero, which is what lets
tier-1 fail hard on every finding.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Sequence, Set, Tuple

# wrappers whose first positional callable argument gets traced (matched
# on the LAST dotted segment: jax.jit, jax.experimental.pjit.pjit, ...)
_JIT_LAST = {"jit", "pjit", "pallas_call"}
# tracing higher-order functions: callable args get traced too
_HOF_LAST = {"scan", "cond", "while_loop", "fori_loop", "switch",
             "vmap", "pmap", "grad", "value_and_grad", "remat",
             "checkpoint", "shard_map", "custom_vjp", "custom_jvp",
             "associated_scan"}


def dotted(node: ast.AST) -> Optional[str]:
    """'jax.jit' for Attribute chains / Names; None for anything else."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _last(dot: Optional[str]) -> str:
    return dot.rsplit(".", 1)[-1] if dot else ""


def is_jit_wrapper(func: ast.AST) -> bool:
    return _last(dotted(func)) in _JIT_LAST


def _callable_args(call: ast.Call) -> List[ast.AST]:
    """Positional args of a wrapper/HOF call that may be callables."""
    return [a for a in call.args
            if isinstance(a, (ast.Name, ast.Attribute))]


class FileInfo:
    def __init__(self, relpath: str, tree: ast.AST):
        self.relpath = relpath
        self.tree = tree
        # bare function name -> def nodes (module fns, methods, nested)
        self.funcs: Dict[str, List[ast.AST]] = {}
        # local name -> ("mod", relpath) | ("func", relpath, origname)
        self.bindings: Dict[str, Tuple] = {}
        self.roots: Set[ast.AST] = set()
        # def node -> directly nested def nodes
        self.children: Dict[ast.AST, List[ast.AST]] = {}
        # defs that are class methods: a BARE-name call can never reach
        # these (only self.X / cls.X can), so bare-name resolution must
        # skip them or `run(...)` on a local wrongly marks Executor.run
        self.method_defs: Set[ast.AST] = set()


_DEFS = (ast.FunctionDef, ast.AsyncFunctionDef)


def _scan_file(relpath: str, tree: ast.AST,
               known: Set[str]) -> FileInfo:
    info = FileInfo(relpath, tree)
    for node in ast.walk(tree):
        if isinstance(node, _DEFS):
            info.funcs.setdefault(node.name, []).append(node)
            info.children[node] = [c for c in ast.walk(node)
                                   if isinstance(c, _DEFS) and c is not node]
        elif isinstance(node, ast.ClassDef):
            info.method_defs.update(
                c for c in node.body if isinstance(c, _DEFS))
        elif isinstance(node, ast.ImportFrom):
            _bind_import(info, node, relpath, known)
    # jit roots: decorators + wrapper/HOF call sites. Walk with the
    # enclosing-def stack so a local variable shadowing a def name
    # (`run, ... = trace(...); jax.jit(run)`) doesn't mark the def.
    def visit(node, stack):
        if isinstance(node, _DEFS):
            for dec in node.decorator_list:
                if _decorator_is_jit(dec):
                    info.roots.add(node)
            stack = stack + [node]
        elif isinstance(node, ast.Call):
            last = _last(dotted(node.func))
            if last in _JIT_LAST or last in _HOF_LAST:
                for a in _callable_args(node):
                    if isinstance(a, ast.Name) and any(
                            a.id in _local_bindings(d) for d in stack):
                        continue
                    for fn in _resolve_local(info, a):
                        info.roots.add(fn)
        for child in ast.iter_child_nodes(node):
            visit(child, stack)

    visit(tree, [])
    return info


def _decorator_is_jit(dec: ast.AST) -> bool:
    if _last(dotted(dec)) in _JIT_LAST:
        return True
    if isinstance(dec, ast.Call):
        last = _last(dotted(dec.func))
        if last in _JIT_LAST:
            return True  # @jax.jit(...)-style factory (defensive)
        if last == "partial" and dec.args and \
                _last(dotted(dec.args[0])) in _JIT_LAST:
            return True
    return False


def _bind_import(info: FileInfo, node: ast.ImportFrom, relpath: str,
                 known: Set[str]) -> None:
    """Resolve `from X import Y [as Z]` to an analyzed file, if any."""
    if node.level:
        base = os.path.dirname(relpath)
        for _ in range(node.level - 1):
            base = os.path.dirname(base)
        mod_dir = base
    else:
        mod_dir = ""
    parts = node.module.split(".") if node.module else []
    mod_path = "/".join(([mod_dir] if mod_dir else []) + parts)
    for alias in node.names:
        local = alias.asname or alias.name
        # `from pkg import module` -> pkg/module.py analyzed?
        as_mod = f"{mod_path}/{alias.name}.py" if mod_path else \
            f"{alias.name}.py"
        as_pkg = f"{mod_path}/{alias.name}/__init__.py" if mod_path \
            else f"{alias.name}/__init__.py"
        # `from pkg.module import func` -> pkg/module.py
        as_func = f"{mod_path}.py"
        if as_mod in known:
            info.bindings[local] = ("mod", as_mod)
        elif as_pkg in known:
            info.bindings[local] = ("mod", as_pkg)
        elif as_func in known:
            info.bindings[local] = ("func", as_func, alias.name)


def _resolve_local(info: FileInfo, node: ast.AST) -> List[ast.AST]:
    """Def nodes a Name / self.X expression may refer to in this file."""
    if isinstance(node, ast.Name):
        return [n for n in info.funcs.get(node.id, ())
                if n not in info.method_defs]
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and node.value.id == "self":
        return list(info.funcs.get(node.attr, ()))
    return []


def _local_bindings(fn: ast.AST) -> Set[str]:
    """Names bound inside ``fn`` (params + any Store target): a call to
    such a name is NOT a call to a same-named module/class function, so
    the resolver must skip it (e.g. ``run, ... = trace(...); run(x)``
    shadowing an ``Executor.run`` method)."""
    bound = set(fn_params(fn))
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and \
                isinstance(node.ctx, (ast.Store, ast.Del)):
            bound.add(node.id)
    return bound


def _call_edges(info: FileInfo, fn: ast.AST,
                infos: Dict[str, FileInfo]) -> List[Tuple[str, ast.AST]]:
    """(relpath, def node) pairs this function's body may invoke."""
    out: List[Tuple[str, ast.AST]] = []
    nested = set(info.children.get(fn, ()))
    shadowed = _local_bindings(fn)
    for node in ast.walk(fn):
        if node is not fn and node in nested and isinstance(node, _DEFS):
            continue  # nested defs traverse on their own
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if isinstance(func, ast.Name):
            name = func.id
            if name in shadowed:
                continue
            if name in info.funcs:
                out.extend((info.relpath, n) for n in info.funcs[name]
                           if n not in info.method_defs)
            elif name in info.bindings:
                b = info.bindings[name]
                if b[0] == "func" and b[1] in infos:
                    tgt = infos[b[1]]
                    out.extend((tgt.relpath, n)
                               for n in tgt.funcs.get(b[2], ()))
        elif isinstance(func, ast.Attribute):
            base = func.value
            if isinstance(base, ast.Name) and base.id == "self":
                out.extend((info.relpath, n)
                           for n in info.funcs.get(func.attr, ()))
            elif isinstance(base, ast.Name) and \
                    base.id in info.bindings:
                b = info.bindings[base.id]
                if b[0] == "mod" and b[1] in infos:
                    tgt = infos[b[1]]
                    out.extend((tgt.relpath, n)
                               for n in tgt.funcs.get(func.attr, ()))
    return out


def traced_functions(files: Sequence) -> Dict[str, Set[ast.AST]]:
    """relpath -> set of FunctionDef nodes whose bodies are traced.

    ``files`` is a sequence of objects with ``.relpath`` and ``.tree``
    (ptlint ``SourceFile``); files that failed to parse are skipped.
    """
    known = {f.relpath for f in files if f.tree is not None}
    infos: Dict[str, FileInfo] = {}
    for f in files:
        if f.tree is not None:
            infos[f.relpath] = _scan_file(f.relpath, f.tree, known)

    traced: Dict[str, Set[ast.AST]] = {rel: set() for rel in infos}
    work: List[Tuple[str, ast.AST]] = []
    for rel, info in infos.items():
        for fn in info.roots:
            work.append((rel, fn))
    while work:
        rel, fn = work.pop()
        if fn in traced[rel]:
            continue
        traced[rel].add(fn)
        info = infos[rel]
        for child in info.children.get(fn, ()):
            work.append((rel, child))
        for edge in _call_edges(info, fn, infos):
            work.append(edge)
    return traced


def fn_params(fn: ast.AST) -> Set[str]:
    """Parameter names of a def, minus self/cls."""
    a = fn.args
    names = [p.arg for p in
             getattr(a, "posonlyargs", []) + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return {n for n in names if n not in ("self", "cls")}
