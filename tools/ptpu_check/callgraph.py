"""Cross-file call graph + jit/pjit/trace-entry reachability.

Scope and honesty: this is a LINT-grade graph, not a type checker.  It
resolves (a) plain-name calls/references through the lexical chain
(nested defs -> module top level -> imports), (b) ``self.method`` inside
a class — including methods BOUND via ``self.<attr> = <callable>``
assignments (the engine's ``self._fn = _impl`` pattern dropped edges in
v1, silently shrinking host-sync reachability), (c) ``Class.method``
references by class name, and (d) ``alias.func`` where ``alias`` is an
imported module that is part of the analyzed file set — ``import x.y as
z`` and ``from x import y as z`` forms included (``functools.partial``
under an alias is resolved too).  Dynamic dispatch, inheritance and
higher-order returns are over/under-approximated; rules built on it
(host-sync, blocking-in-handler, recompile-hazard) pair with the
baseline/suppression workflow for the residue.

Trace entries — where XLA tracing starts and host syncs become hidden
recompiles/transfers:

- calls of the jit family (``jax.jit``/``pjit``/``vmap``/``pmap``/
  ``grad``/``value_and_grad``/``checkpoint``/``remat``/``eval_shape``,
  ``jax.lax.scan/while_loop/cond/fori_loop/switch/map``,
  ``shard_map``): every argument that resolves to
  a known function becomes an entry;
- functions decorated with any of the above, incl. through
  ``functools.partial(jax.jit, ...)``.

A function REFERENCED (not just called) inside a traced function is
itself treated as traced — that is exactly the engine's
``builder``/``attn_fn`` closure-callback pattern.
"""
from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field

JIT_DOTTED_LAST = {
    "jit", "pjit", "vmap", "pmap", "grad", "value_and_grad",
    "checkpoint", "remat", "eval_shape", "make_jaxpr",
    "scan", "while_loop", "cond", "fori_loop", "switch", "map",
    "shard_map",
}
# bare names that are unambiguous even without a jax-rooted dotted path
JIT_BARE = {"pjit", "shard_map"}


def dotted_name(node):
    """'jax.lax.scan' for Attribute chains rooted at a Name, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def iter_body_nodes(func_node):
    """Walk a function body WITHOUT descending into nested function/class
    definitions (each nested def is its own call-graph node)."""
    stack = list(ast.iter_child_nodes(func_node))
    while stack:
        n = stack.pop()
        yield n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(n))


@dataclass
class FuncInfo:
    key: tuple              # (rel, qualname)
    node: object            # ast.FunctionDef
    rel: str
    qualname: str
    class_name: str = None  # immediate enclosing class, if a method
    parent: "FuncInfo" = None   # lexically enclosing function
    locals_: dict = field(default_factory=dict)   # name -> FuncInfo (nested)


class ModuleIndex:
    """Per-module symbol + import tables."""

    def __init__(self, ctx, dotted):
        self.rel = ctx.rel
        self.dotted = dotted           # e.g. 'paddle_tpu.serving.engine'
        self.top = {}                  # name -> FuncInfo (module level)
        self.classes = {}              # class name -> {meth name -> FuncInfo}
        self.class_attrs = {}          # class name -> {attr -> FuncInfo}
        #                                (self.<attr> = <callable> bindings)
        self.mod_alias = {}            # local name -> dotted module
        self.sym_import = {}           # local name -> (dotted module, symbol)

    def package(self):
        """Dotted package for resolving relative imports."""
        if self.rel.endswith("__init__.py"):
            return self.dotted
        return self.dotted.rsplit(".", 1)[0] if "." in self.dotted else ""


def _module_dotted(rel):
    parts = rel[:-3].split("/")        # strip .py
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


class CallGraph:
    def __init__(self, contexts):
        self.functions = {}            # key -> FuncInfo
        self._by_node = {}             # id(ast node) -> FuncInfo
        self.indexes = {}              # rel -> ModuleIndex
        self._dotted_to_rel = {}
        self.entries = {}              # key -> reason str
        self.traced = {}               # key -> origin entry description

        ctxs = [c for c in contexts if c.tree is not None]
        for c in ctxs:
            self._dotted_to_rel[_module_dotted(c.rel)] = c.rel
        for c in ctxs:
            self._index_module(c)
        for c in ctxs:
            self._resolve_imports(c)
        for c in ctxs:
            self._index_class_attrs(c)
        self._edges = {}               # key -> set of keys
        for c in ctxs:
            self._collect_edges_and_entries(c)
        self._propagate()
        self._redges = None            # reverse edges, built lazily

    # -- indexing ----------------------------------------------------------

    def _index_module(self, ctx):
        idx = ModuleIndex(ctx, _module_dotted(ctx.rel))
        self.indexes[ctx.rel] = idx

        def visit(node, qual, class_name, parent):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    q = f"{qual}.{child.name}" if qual else child.name
                    fi = FuncInfo((ctx.rel, q), child, ctx.rel, q,
                                  class_name=class_name, parent=parent)
                    self.functions[fi.key] = fi
                    self._by_node[id(child)] = fi
                    if parent is not None:
                        parent.locals_[child.name] = fi
                    elif class_name is not None:
                        idx.classes.setdefault(class_name,
                                               {})[child.name] = fi
                    else:
                        idx.top[child.name] = fi
                    visit(child, q, None, fi)
                elif isinstance(child, ast.ClassDef):
                    q = f"{qual}.{child.name}" if qual else child.name
                    visit(child, q, child.name, None)
                else:
                    visit(child, qual, class_name, parent)

        visit(ctx.tree, "", None, None)

    def _resolve_imports(self, ctx):
        idx = self.indexes[ctx.rel]
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    # `import x.y` binds `x`; `import x.y as z` binds z->x.y
                    local = a.asname or a.name.split(".")[0]
                    idx.mod_alias[local] = (a.name if a.asname
                                            else a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom):
                base = node.module or ""
                if node.level:
                    pkg_parts = idx.package().split(".") if idx.package() \
                        else []
                    cut = len(pkg_parts) - (node.level - 1)
                    base_parts = pkg_parts[:max(cut, 0)]
                    if node.module:
                        base_parts.append(node.module)
                    base = ".".join(base_parts)
                for a in node.names:
                    if a.name == "*":
                        continue
                    local = a.asname or a.name
                    sub = f"{base}.{a.name}" if base else a.name
                    if sub in self._dotted_to_rel:
                        idx.mod_alias[local] = sub       # submodule import
                    else:
                        idx.sym_import[local] = (base, a.name)

    def _index_class_attrs(self, ctx):
        """``self.<attr> = <callable>`` bindings inside a class's methods
        bind the attribute to that callable for every ``self.<attr>(...)``
        call site in the class (v1 dropped these edges).  Runs AFTER
        import resolution so the assigned value can be a module function,
        an imported symbol, or a sibling method."""
        idx = self.indexes[ctx.rel]
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            attrs = idx.class_attrs.setdefault(node.name, {})
            for meth in node.body:
                if not isinstance(meth, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                fi = self._by_node.get(id(meth))
                for n in iter_body_nodes(meth):
                    if not isinstance(n, ast.Assign):
                        continue
                    for t in n.targets:
                        if (isinstance(t, ast.Attribute)
                                and isinstance(t.value, ast.Name)
                                and t.value.id == "self"):
                            tgt = self.resolve(n.value, idx, fi)
                            if tgt is not None:
                                attrs.setdefault(t.attr, tgt)

    # -- resolution --------------------------------------------------------

    def resolve(self, expr, idx, func=None):
        """Resolve a reference expression to a FuncInfo, or None."""
        if isinstance(expr, ast.Name):
            f = func
            while f is not None:
                if expr.id in f.locals_:
                    return f.locals_[expr.id]
                f = f.parent
            if expr.id in idx.top:
                return idx.top[expr.id]
            if expr.id in idx.sym_import:
                mod, sym = idx.sym_import[expr.id]
                rel = self._dotted_to_rel.get(mod)
                if rel is not None:
                    return self.indexes[rel].top.get(sym)
            return None
        if isinstance(expr, ast.Attribute):
            v = expr.value
            if isinstance(v, ast.Name):
                if v.id == "self" and func is not None:
                    cls = func.class_name
                    f = func
                    while cls is None and f.parent is not None:
                        f = f.parent
                        cls = f.class_name
                    if cls is not None:
                        hit = idx.classes.get(cls, {}).get(expr.attr)
                        if hit is None:   # self.<attr> = <callable>
                            hit = idx.class_attrs.get(cls,
                                                      {}).get(expr.attr)
                        return hit
                if v.id in idx.classes:   # Class.method reference
                    return idx.classes[v.id].get(expr.attr)
                mod = self._local_module(v.id, idx)
                if mod is not None:
                    rel = self._dotted_to_rel.get(mod)
                    if rel is not None:
                        return self.indexes[rel].top.get(expr.attr)
        return None

    def _local_module(self, name, idx):
        return idx.mod_alias.get(name)

    def is_jit_entry_callable(self, func_expr, idx):
        """Does this call expression start a trace?"""
        dn = dotted_name(func_expr)
        if dn:
            last = dn.rsplit(".", 1)[-1]
            root = dn.split(".", 1)[0]
            root_mod = idx.mod_alias.get(root, root)
            if last in JIT_DOTTED_LAST and (
                    root_mod == "jax" or root_mod.startswith("jax.")):
                return True
            if last in JIT_BARE:
                return True
            if dn in idx.sym_import:
                mod, sym = idx.sym_import[dn]
                if sym in JIT_DOTTED_LAST and mod.startswith("jax"):
                    return True
                if sym in JIT_BARE:
                    return True
        return False

    def _is_partial_of_jit(self, call, idx):
        """functools.partial(jax.jit, ...) (decorator form) — incl.
        ``from functools import partial as P`` aliases (a v1 gap: the
        aliased form dropped the entry, shrinking host-sync scope)."""
        dn = dotted_name(call.func)
        if dn is None:
            return False
        if dn.rsplit(".", 1)[-1] != "partial" and dn != "partial":
            # aliased symbol import: resolve the local name back to
            # ('functools', 'partial')
            if "." in dn or idx.sym_import.get(dn) != ("functools",
                                                       "partial"):
                return False
        return bool(call.args) and self.is_jit_entry_callable(call.args[0],
                                                              idx)

    # -- edges + entries ---------------------------------------------------

    def _collect_edges_and_entries(self, ctx):
        idx = self.indexes[ctx.rel]
        file_funcs = [fi for fi in self.functions.values()
                      if fi.rel == ctx.rel]
        for fi in file_funcs:
            # decorator-declared entries
            for dec in fi.node.decorator_list:
                if (self.is_jit_entry_callable(dec, idx)
                        or (isinstance(dec, ast.Call)
                            and (self.is_jit_entry_callable(dec.func, idx)
                                 or self._is_partial_of_jit(dec, idx)))):
                    self.entries.setdefault(
                        fi.key, f"decorated at {ctx.rel}:{dec.lineno}")
            edges = self._edges.setdefault(fi.key, set())
            for n in iter_body_nodes(fi.node):
                if isinstance(n, ast.Call) and \
                        self.is_jit_entry_callable(n.func, idx):
                    for arg in list(n.args) + [k.value for k in n.keywords]:
                        tgt = self.resolve(arg, idx, fi)
                        if tgt is not None:
                            self.entries.setdefault(
                                tgt.key,
                                f"passed to {dotted_name(n.func)} at "
                                f"{ctx.rel}:{n.lineno}")
                elif isinstance(n, (ast.Name, ast.Attribute)) and \
                        isinstance(getattr(n, "ctx", None), ast.Load):
                    tgt = self.resolve(n, idx, fi)
                    if tgt is not None and tgt.key != fi.key:
                        edges.add(tgt.key)
        # module-level jit calls (g = jax.jit(f) at top level)
        for n in iter_body_nodes(ctx.tree):
            if isinstance(n, ast.Call) and \
                    self.is_jit_entry_callable(n.func, idx):
                for arg in list(n.args) + [k.value for k in n.keywords]:
                    tgt = self.resolve(arg, idx, None)
                    if tgt is not None:
                        self.entries.setdefault(
                            tgt.key,
                            f"passed to {dotted_name(n.func)} at "
                            f"{ctx.rel}:{n.lineno}")

    def _propagate(self):
        work = list(self.entries)
        for k in work:
            self.traced[k] = self.entries[k]
        while work:
            k = work.pop()
            origin = self.traced[k]
            for tgt in self._edges.get(k, ()):
                if tgt not in self.traced:
                    self.traced[tgt] = origin
                    work.append(tgt)

    # -- rule-facing API ---------------------------------------------------

    def traced_functions_in(self, rel):
        out = []
        for key, origin in self.traced.items():
            if key[0] == rel:
                out.append((self.functions[key], origin))
        out.sort(key=lambda p: p[0].node.lineno)
        return out

    def index_of(self, rel):
        return self.indexes.get(rel)

    def reachable_from(self, seeds):
        """{key: origin description} for every function reachable from
        the seed set ({key: origin}) through call/reference edges —
        the generic BFS the handler-context and --changed analyses ride
        (the jit-entry propagation is the same walk with its own seeds)."""
        out = dict(seeds)
        work = list(seeds)
        while work:
            k = work.pop()
            origin = out[k]
            for tgt in self._edges.get(k, ()):
                if tgt not in out:
                    out[tgt] = origin
                    work.append(tgt)
        return out

    def _reverse_edges(self):
        if self._redges is None:
            self._redges = {}
            for src, tgts in self._edges.items():
                for t in tgts:
                    self._redges.setdefault(t, set()).add(src)
        return self._redges

    def file_closure(self, rels):
        """Transitive file-level closure of `rels` in BOTH directions:
        files whose functions call into `rels` (their findings may change
        when a callee changes — e.g. a helper gaining a host sync) AND
        files `rels`' functions reach (a changed caller can put a new
        jit entry above an unchanged callee).  The --changed target set."""
        want = set(rels)
        seeds = [k for k in self.functions if k[0] in want]
        for graph in (self._edges, self._reverse_edges()):
            work = list(seeds)
            seen = set(seeds)
            while work:
                k = work.pop()
                want.add(k[0])
                for nxt in graph.get(k, ()):
                    if nxt not in seen:
                        seen.add(nxt)
                        work.append(nxt)
        return want
