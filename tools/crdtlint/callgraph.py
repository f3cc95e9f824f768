"""Project-wide, import-aware call graph (round 16 tentpole core).

One graph is built per lint run and memoized in ``ctx.shared`` (every
CL7xx/CL8xx/CL9xx checker walks it; building it once keeps the
whole-tree pass inside the round-11 <10 s budget). Nodes are function
definitions — module-level defs, methods (``Class.meth``), and nested
defs (``outer.<locals>.inner``). Edges carry a **confidence**:

- ``strong`` — the callee was resolved the way the donate checker
  resolves donating defs (:func:`tools.crdtlint.astutil.
  make_module_resolver`): same-module def, explicit import, or
  module-attribute spelling matched on the receiver module; plus
  ``self.meth(...)`` within the enclosing class and calls to nested
  defs. Strong edges are what the lock-discipline checker propagates
  lock/blocking closures through — a guessed edge must never lend a
  function someone else's blocking call.
- ``weak`` — attribute calls on unresolvable receivers
  (``ph.timed(...)``, ``get_tracer().span(...)``) matched by METHOD
  NAME across every class in the project, linking to ALL candidates.
  Weak edges over-approximate, which is exactly right for
  thread-REACHABILITY (CL803's thread-shared-class discovery must not
  miss a class because a receiver was a local variable) and exactly
  wrong for closures. Name collisions (several classes defining the
  method) are counted in ``stats()``, which shows how much of the
  graph is guessed.

Thread roots: ``threading.Thread(target=f)`` keywords,
``executor.submit(f, ...)`` / ``executor.map(f, ...)`` first
arguments. ``thread_reachable`` is the closure over strong+weak edges
from those roots — the set CL803 calls "reachable from a Thread /
ThreadPoolExecutor target".
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Set, Tuple

from tools.crdtlint.astutil import dotted, make_module_resolver

STRONG = "strong"
WEAK = "weak"


@dataclass
class FuncInfo:
    module: str                  # repo-relative defining module path
    qual: str                    # "f", "Class.meth", "f.<locals>.g"
    name: str                    # bare name
    cls: Optional[str]           # enclosing class name (methods only)
    node: object                 # ast.FunctionDef / AsyncFunctionDef
    lineno: int = 0

    @property
    def key(self) -> str:
        return f"{self.module}:{self.qual}"


@dataclass
class CallSite:
    caller: str                  # FuncInfo.key
    callee: str                  # FuncInfo.key
    lineno: int
    confidence: str              # STRONG | WEAK


@dataclass
class CallGraph:
    funcs: Dict[str, FuncInfo] = field(default_factory=dict)
    edges: Dict[str, List[CallSite]] = field(default_factory=dict)
    thread_roots: Set[str] = field(default_factory=set)
    thread_reachable: Set[str] = field(default_factory=set)
    collisions: int = 0          # weak edges fanned over >1 candidate

    def callees(self, key: str, *,
                strong_only: bool = False) -> Iterable[CallSite]:
        for cs in self.edges.get(key, ()):
            if strong_only and cs.confidence != STRONG:
                continue
            yield cs

    def stats(self) -> Dict[str, int]:
        n_edges = sum(len(v) for v in self.edges.values())
        n_weak = sum(
            1 for v in self.edges.values()
            for cs in v if cs.confidence == WEAK
        )
        return {
            "functions": len(self.funcs),
            "edges": n_edges,
            "weak_edges": n_weak,
            "collisions": self.collisions,
            "thread_roots": len(self.thread_roots),
            "thread_reachable": len(self.thread_reachable),
        }


def get_callgraph(ctx) -> CallGraph:
    """The per-run memoized graph: first checker to ask builds it,
    the rest share it (ctx.shared rides one LintContext per run)."""
    cg = ctx.shared.get("callgraph")
    if cg is None:
        cg = build_callgraph(ctx.modules)
        ctx.shared["callgraph"] = cg
        ctx.shared["callgraph_stats"] = cg.stats()
    return cg


# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------


def _collect_funcs(modules) -> Tuple[
    Dict[str, FuncInfo],            # key -> info
    Dict[str, Dict[str, FuncInfo]],  # module -> {bare name: top-level def}
    Dict[str, List[FuncInfo]],      # method name -> defs across classes
]:
    funcs: Dict[str, FuncInfo] = {}
    module_defs: Dict[str, Dict[str, FuncInfo]] = {}
    methods: Dict[str, List[FuncInfo]] = {}

    def visit(node, mod, cls, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{child.name}"
                fi = FuncInfo(mod.path, qual, child.name, cls, child,
                              child.lineno)
                funcs[fi.key] = fi
                if not prefix:
                    module_defs[mod.path][child.name] = fi
                # direct methods of `cls` only (qual ends with
                # Class.name — covers nested classes too, whose qual
                # keeps the enclosing prefix so a nested `class A`
                # can never overwrite a top-level one in `funcs`)
                if cls is not None and qual.endswith(
                    f"{cls}.{child.name}"
                ):
                    methods.setdefault(child.name, []).append(fi)
                visit(child, mod, cls, f"{qual}.<locals>.")
            elif isinstance(child, ast.ClassDef):
                visit(child, mod, child.name,
                      f"{prefix}{child.name}.")
            else:
                visit(child, mod, cls, prefix)

    for mod in modules:
        if mod.tree is None:
            continue
        module_defs[mod.path] = {}
        visit(mod.tree, mod, None, "")
    return funcs, module_defs, methods


class _ByModule:
    """Adapter giving module-level defs the ``.module`` attribute
    shape :func:`make_module_resolver` candidates need — it already
    have it, so this is just the candidate index."""

    def __init__(self, module_defs: Dict[str, Dict[str, FuncInfo]]):
        self.by_name: Dict[str, List[FuncInfo]] = {}
        for defs in module_defs.values():
            for fi in defs.values():
                self.by_name.setdefault(fi.name, []).append(fi)


def _own_stmts(fn_node) -> Iterable[ast.AST]:
    """Walk a function's body WITHOUT descending into nested function
    or class definitions (those are their own call-graph nodes)."""
    work = list(ast.iter_child_nodes(fn_node))
    while work:
        node = work.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        work.extend(ast.iter_child_nodes(node))


def build_callgraph(modules) -> CallGraph:
    from tools.crdtlint.astutil import import_map

    cg = CallGraph()
    funcs, module_defs, methods = _collect_funcs(modules)
    cg.funcs = funcs
    cands = _ByModule(module_defs).by_name
    # one import map per module, shared by both resolver passes
    # (import_map walks the whole tree — recomputing it per resolver
    # pass was a measurable slice of the <10s budget)
    imaps = {
        m.path: import_map(m.tree)
        for m in modules if m.tree is not None
    }

    # per-module indexes, built once (per-function recomputation over
    # the whole func table is quadratic and blew the <10s budget)
    funcs_by_module: Dict[str, List[FuncInfo]] = {}
    for f in funcs.values():
        funcs_by_module.setdefault(f.module, []).append(f)

    for mod in modules:
        if mod.tree is None:
            continue
        local = set(module_defs.get(mod.path, ()))
        resolve_strong = make_module_resolver(
            mod.path, mod.tree, local, cands, fallback_first=False,
            imap=imaps[mod.path],
        )
        mod_funcs = funcs_by_module.get(mod.path, [])
        by_cls: Dict[Optional[str], Dict[str, FuncInfo]] = {}
        by_parent: Dict[str, Dict[str, FuncInfo]] = {}
        for f in mod_funcs:
            by_cls.setdefault(f.cls, {})[f.name] = f
            if ".<locals>." in f.qual:
                parent = f.qual.rsplit(".<locals>.", 1)[0]
                by_parent.setdefault(parent, {})[f.name] = f
        for fi in mod_funcs:
            self_methods = (by_cls.get(fi.cls, {})
                            if fi.cls is not None else {})
            nested = by_parent.get(fi.qual, {})
            out: List[CallSite] = []
            for node in _own_stmts(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted(node.func)
                targets, conf = _resolve_call(
                    node, name, fi, nested, self_methods,
                    resolve_strong, methods,
                )
                if len(targets) > 1:
                    cg.collisions += 1
                for t in targets:
                    out.append(CallSite(
                        fi.key, t.key, node.lineno, conf
                    ))
            if out:
                cg.edges[fi.key] = out

    _find_thread_roots(cg, modules, module_defs, funcs, methods,
                       imaps)
    cg.thread_reachable = _closure(cg, cg.thread_roots)
    return cg


def _resolve_call(call, name, fi, nested, self_methods,
                  resolve_strong, methods):
    """-> (targets, confidence). Resolution ladder mirrors the donate
    checker's (see module doc)."""
    if name:
        tail = name.rsplit(".", 1)[-1]
        if name == tail and tail in nested:
            return [nested[tail]], STRONG
        if name.startswith("self.") and "." not in name[5:]:
            m = self_methods.get(name[5:])
            if m is not None:
                return [m], STRONG
        hit = resolve_strong(name)
        if hit is not None:
            return [hit], STRONG
    # attribute call on an unresolvable receiver (or a call on a call
    # result): fan out by method name — weak
    if isinstance(call.func, ast.Attribute):
        cands = methods.get(call.func.attr, ())
        if cands:
            return list(cands), WEAK
    return [], WEAK


def _find_thread_roots(cg, modules, module_defs, funcs, methods,
                       imaps):
    """``Thread(target=f)`` / ``pool.submit(f, ...)`` /
    ``pool.map(f, it)`` — resolve ``f`` to its def and mark a root."""
    cands = _ByModule(module_defs).by_name
    for mod in modules:
        if mod.tree is None:
            continue
        local = module_defs.get(mod.path, {})
        resolve_strong = make_module_resolver(
            mod.path, mod.tree, set(local), cands,
            fallback_first=False, imap=imaps[mod.path],
        )
        # nested defs visible from each enclosing function
        nested_all = {
            f.name: f for f in funcs.values() if f.module == mod.path
        }

        def as_func(expr, resolve_strong=resolve_strong,
                    nested_all=nested_all,
                    mod_path=mod.path) -> Optional[FuncInfo]:
            d = dotted(expr)
            if not d:
                return None
            tail = d.rsplit(".", 1)[-1]
            hit = resolve_strong(d)
            if hit is not None:
                return hit
            if d == tail and tail in nested_all:
                return nested_all[tail]
            if d.startswith("self."):
                for m in methods.get(tail, ()):
                    if m.module == mod_path:
                        return m
            cands = methods.get(tail, ())
            return cands[0] if len(cands) == 1 else None

        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            cname = dotted(node.func) or ""
            tail = cname.rsplit(".", 1)[-1]
            fn = None
            if tail == "Thread":
                for k in node.keywords:
                    if k.arg == "target":
                        fn = as_func(k.value)
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("submit", "map")
                    and node.args):
                fn = as_func(node.args[0])
            if fn is not None:
                cg.thread_roots.add(fn.key)


def _closure(cg: CallGraph, roots: Set[str]) -> Set[str]:
    seen = set(roots)
    work = list(roots)
    while work:
        k = work.pop()
        for cs in cg.edges.get(k, ()):
            if cs.callee not in seen:
                seen.add(cs.callee)
                work.append(cs.callee)
    return seen


def reach_closure(cg: CallGraph, key: str, *, strong_only: bool,
                  memo: Dict[str, Set[str]]) -> Set[str]:
    """Transitive callee set of ``key`` (key excluded unless cyclic).
    The first query computes EVERY node's closure via SCC
    condensation and fills ``memo`` — a naive recursive memo poisons
    cycle members with the in-progress guard's incomplete set (A<->B
    with B->D memoized closure(A) without D), which would silently
    drop CL801/CL802 findings behind mutually recursive helpers."""
    if not memo:
        _fill_closures(cg, strong_only, memo)
    return memo.get(key, set())


def _fill_closures(cg: CallGraph, strong_only: bool,
                   memo: Dict[str, Set[str]]) -> None:
    adj: Dict[str, Set[str]] = {}
    for key in cg.funcs:
        adj[key] = {
            cs.callee for cs in cg.callees(key, strong_only=strong_only)
            if cs.callee in cg.funcs
        }
    comp_of, comps = _tarjan(adj)  # comps emitted callees-first
    comp_reach: List[Set[str]] = []
    for ci, members in enumerate(comps):
        cyclic = len(members) > 1 or any(
            m in adj.get(m, ()) for m in members
        )
        out: Set[str] = set(members) if cyclic else set()
        for m in members:
            for v in adj.get(m, ()):
                cj = comp_of[v]
                if cj != ci:
                    out.add(v)
                    out |= comp_reach[cj]
        comp_reach.append(out)
        for m in members:
            memo[m] = out
    if not memo:
        memo["<empty>"] = set()  # mark computed even for bare graphs


def _tarjan(adj: Dict[str, Set[str]]):
    """Iterative Tarjan SCC; components are emitted in reverse
    topological order of the condensation (every edge out of a
    component lands in an earlier-emitted one)."""
    index: Dict[str, int] = {}
    low: Dict[str, int] = {}
    on: Set[str] = set()
    stack: List[str] = []
    comp_of: Dict[str, int] = {}
    comps: List[List[str]] = []
    counter = [0]

    for root in adj:
        if root in index:
            continue
        work = [(root, iter(adj.get(root, ())))]
        index[root] = low[root] = counter[0]
        counter[0] += 1
        stack.append(root)
        on.add(root)
        while work:
            v, it = work[-1]
            advanced = False
            for w in it:
                if w not in index:
                    index[w] = low[w] = counter[0]
                    counter[0] += 1
                    stack.append(w)
                    on.add(w)
                    work.append((w, iter(adj.get(w, ()))))
                    advanced = True
                    break
                if w in on:
                    low[v] = min(low[v], index[w])
            if advanced:
                continue
            work.pop()
            if work:
                pv = work[-1][0]
                low[pv] = min(low[pv], low[v])
            if low[v] == index[v]:
                members = []
                while True:
                    w = stack.pop()
                    on.discard(w)
                    comp_of[w] = len(comps)
                    members.append(w)
                    if w == v:
                        break
                comps.append(members)
    return comp_of, comps
