"""CL801–CL803: lock discipline across the threaded surface (r16).

Rounds 6–15 grew a real threaded runtime — the streaming decode pool,
the background stager, the live-ingest ``serve()`` loop — and the
locks guarding it are scattered across eight modules. Three bug
classes survive any amount of per-function review because they are
*relations between* acquisition sites:

- **CL801 — lock-order cycle (potential deadlock).** Build a lock
  acquisition graph: an edge A→B whenever code acquires B while
  holding A — lexically nested ``with`` blocks, plus calls made under
  A whose STRONG call-graph closure acquires B. A cycle means two
  threads can each hold one lock of the cycle and wait on the next.
- **CL802 — blocking call under a lock.** Device dispatch *fetches*
  (``converge_fetch`` / ``xfer_fetch`` / ``block_until_ready``),
  native KV / socket IO (``kv_*`` / ``udp_*`` ABI calls,
  ``subprocess.run``), ``Future.result()`` / ``Thread.join()`` /
  ``queue.get`` / ``time.sleep`` — each can stall for milliseconds
  (or forever) while every other thread piles up on the lock. Checked through the same STRONG closure, so a with-block that
  calls a helper whose callee blocks is still caught.
- **CL803 — guarded-field inconsistency.** For thread-shared classes
  (any class with a method reachable from a ``Thread``/
  ``ThreadPoolExecutor`` target via the call graph, plus every class
  in the CL601 threaded-module scope) that own a lock: an instance
  attribute written both under ``with self.<lock>`` and outside it
  (``__init__`` exempt — the object is not shared yet) is a torn
  write waiting for a scheduler.

Lock identity: ``self.<attr>`` keys on the enclosing class,
module-level names on the defining module, anything else on the bare
name — and ``self._lock = other._lock`` aliasing UNIONs the two
identities (union-find), so an alias never manufactures a phantom
two-lock cycle.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from tools.crdtlint.astutil import MUTATOR_METHODS as _MUTATORS
from tools.crdtlint.astutil import call_name, dotted, import_map
from tools.crdtlint.callgraph import get_callgraph, reach_closure
from tools.crdtlint.checkers.threadshare import (
    THREADED_SUFFIXES,
    _is_lock_expr,
)
from tools.crdtlint.core import Checker, Finding, LintContext, Module

# blocking primitives by dotted-name tail. `.result()` / `.join()` /
# `.get()` are attribute-call-shape-gated below (str.join and
# dict.get must not fire).
_BLOCKING_TAILS = {
    "converge_fetch", "xfer_fetch", "block_until_ready",
    "device_get", "sleep", "wait",
}
_BLOCKING_DOTTED = {
    "subprocess.run", "subprocess.check_call",
    "subprocess.check_output", "subprocess.call",
}
_BLOCKING_PREFIXES = ("kv_", "udp_", "ct_")  # native ABI calls


class _Union:
    def __init__(self):
        self.p: Dict[object, object] = {}

    def find(self, x):
        self.p.setdefault(x, x)
        while self.p[x] != x:
            self.p[x] = self.p[self.p[x]]
            x = self.p[x]
        return x

    def union(self, a, b):
        self.p[self.find(a)] = self.find(b)


class LockDisciplineChecker(Checker):
    name = "lock-discipline"
    codes = {
        "CL801": "lock-order cycle across acquisition sites "
                 "(potential deadlock)",
        "CL802": "blocking call (device fetch / native IO / "
                 "Future.result / join / sleep) while holding a lock",
        "CL803": "instance attribute of a thread-shared class "
                 "written both under its lock and outside it",
    }
    explain = {
        "CL801": (
            "Two code paths that acquire the same pair of locks in "
            "opposite orders deadlock the moment two threads "
            "interleave between the acquisitions — and the threaded "
            "surface (streaming pool, stager, serve loop) provides "
            "the threads. The checker builds the acquisition graph "
            "(held lock -> lock acquired under it, lexically and "
            "through the strong call-graph closure) and reports "
            "every cycle.\n"
            "Fix: pick one global order (document it at the lock "
            "definitions) and acquire in that order everywhere, or "
            "collapse the pair into one lock."
        ),
        "CL802": (
            "A lock held across a blocking call (a device fetch "
            "waits on the device, a native build is seconds, "
            "Future.result can be forever) serializes every other thread behind "
            "IO they don't need. The classic outage shape: one slow "
            "dispatch, and the whole decode pool queues on a memo "
            "lock.\n"
            "Fix: move the blocking call out of the with-block — "
            "compute under the lock, IO outside (the "
            "fetch_packed_i32 pattern: wrap under the lock, compile "
            "at the unlocked call) — or baseline with a "
            "justification naming why the wait is bounded and "
            "intentional (e.g. the one-time native-build locks)."
        ),
        "CL803": (
            "An attribute written under `with self._lock` in one "
            "method and bare in another is only *sometimes* "
            "guarded: the unlocked write can interleave mid-"
            "read-modify-write of the locked one and tear the "
            "state. These surface as once-a-week corruption under "
            "production load and never in tests.\n"
            "Fix: take the lock at every write site (reads too, if "
            "compound), or document single-thread confinement by "
            "baselining with that justification. __init__ is exempt "
            "— the object is not shared yet."
        ),
    }

    # ---- lock node identity -------------------------------------------

    def prepare(self, ctx: LintContext) -> None:
        self._uf = _Union()
        self._module_globals: Dict[str, Set[str]] = {}
        for mod in ctx.modules:
            if mod.tree is None:
                continue
            g: Set[str] = set()
            for node in mod.tree.body:
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            g.add(t.id)
                elif isinstance(node, ast.AnnAssign) and isinstance(
                    node.target, ast.Name
                ):
                    g.add(node.target.id)
            self._module_globals[mod.path] = g
        # alias pass: `self.X = <recv>.Y` with both sides lock-like
        # unions ("a", cls, X) with the name-group ("n", Y) — shared
        # locks collapse to one node, so aliasing can only REMOVE
        # phantom cycles, never hide a real two-lock inversion
        cg = get_callgraph(ctx)
        for fi in cg.funcs.values():
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Assign):
                    continue
                tgt = node.targets[0] if node.targets else None
                td = dotted(tgt) if tgt is not None else None
                vd = dotted(node.value)
                if not td or not vd or "." not in vd:
                    continue
                if td.startswith("self.") and fi.cls:
                    tattr, vattr = td[5:], vd.rsplit(".", 1)[-1]
                    if _lockish(tattr) and _lockish(vattr):
                        self._uf.union(
                            ("a", f"{fi.module}:{fi.cls}", tattr),
                            ("n", vattr),
                        )

    def _lock_node(self, expr, mod: Module, cls: Optional[str],
                   imap: Dict[str, str]):
        d = dotted(expr)
        if d is None:
            return None  # `with threading.Lock():` — anonymous
        if d.startswith("self.") and cls:
            return self._uf.find(("a", f"{mod.path}:{cls}", d[5:]))
        if "." not in d:
            if d in self._module_globals.get(mod.path, ()):
                return self._uf.find(("g", mod.path, d))
            return self._uf.find(("n", d))
        head, tail = d.split(".", 1)[0], d.rsplit(".", 1)[-1]
        qual = imap.get(head)
        if qual:
            return self._uf.find(("g", qual, tail))
        return self._uf.find(("n", tail))

    # ---- per-run analysis (finalize: needs every module's sites) ------

    def _scan_function(self, fi, mod: Module,
                       imap: Dict[str, str], acq: Set[object],
                       held_calls: List, pair_edges: Dict) -> None:
        """Pass 1 for one function: record every lock acquisition,
        every lexically nested acquisition as a CL801 edge, and every
        call made while a lock is held."""

        def visit(node, held: Tuple[object, ...]):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                new = list(held)
                for item in node.items:
                    if _is_lock_expr(item.context_expr):
                        ln = self._lock_node(
                            item.context_expr, mod, fi.cls, imap
                        )
                        if ln is not None:
                            acq.add(ln)
                            for h in held:
                                pair_edges.setdefault(
                                    (h, ln),
                                    (fi.module, node.lineno, fi.qual),
                                )
                            new.append(ln)
                held = tuple(new)
            elif isinstance(node, ast.Call) and held:
                held_calls.append((held, node, fi.qual))
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef,
                                   ast.ClassDef)):
                return  # nested defs analyzed as their own nodes
            for child in ast.iter_child_nodes(node):
                visit(child, held)

        for child in ast.iter_child_nodes(fi.node):
            visit(child, ())

    def finalize(self, ctx: LintContext) -> Iterable[Finding]:
        cg = get_callgraph(ctx)
        findings: List[Finding] = []

        # pass 1: per function — direct acquisitions, and (held locks,
        # statement) pairs for every call made under a lock
        acquires: Dict[str, Set[object]] = {}
        under: Dict[str, List[Tuple[object, ast.Call, str]]] = {}
        # (held, acquired) -> first site, for CL801 edge provenance
        pair_edges: Dict[Tuple[object, object],
                         Tuple[str, int, str]] = {}
        mod_by_path = {m.path: m for m in ctx.modules}
        # one import map per MODULE (import_map walks the whole tree;
        # per-function recomputation blew the <10s budget)
        imaps: Dict[str, Dict[str, str]] = {
            m.path: import_map(m.tree)
            for m in ctx.modules if m.tree is not None
        }
        for fi in cg.funcs.values():
            mod = mod_by_path.get(fi.module)
            if mod is None or mod.tree is None:
                continue
            acq: Set[object] = set()
            held_calls: List[Tuple[object, ast.Call, str]] = []
            self._scan_function(
                fi, mod, imaps[fi.module], acq, held_calls,
                pair_edges,
            )
            acquires[fi.key] = acq
            under[fi.key] = held_calls

        # pass 2: interprocedural closures over STRONG edges
        memo: Dict[str, Set[str]] = {}
        acq_closure: Dict[str, Set[object]] = {}
        blocking: Dict[str, List[Tuple[str, str]]] = {}
        from tools.crdtlint.callgraph import _own_stmts

        for key in cg.funcs:
            mod = mod_by_path.get(cg.funcs[key].module)
            blk: List[Tuple[str, str]] = []
            if mod is not None and mod.tree is not None:
                # own statements only: nested defs are their own
                # call-graph nodes (walking whole subtrees per
                # ancestor re-scans every nested body)
                for node in _own_stmts(cg.funcs[key].node):
                    if isinstance(node, ast.Call):
                        prim = _blocking_primitive(node)
                        if prim:
                            blk.append((prim, key))
            blocking[key] = blk
        for key in cg.funcs:
            reach = reach_closure(cg, key, strong_only=True,
                                  memo=memo)
            clo = set(acquires.get(key, ()))
            for r in reach:
                clo |= acquires.get(r, set())
            acq_closure[key] = clo

        # CL802 + interprocedural CL801 edges
        edge_site: Dict[Tuple[object, object],
                        Tuple[str, int, str]] = dict(pair_edges)
        ordinals: Dict[str, int] = {}
        for key, calls in under.items():
            fi = cg.funcs[key]
            memo_local: Dict[str, Set[str]] = memo
            for held, call, qual in calls:
                prim = _blocking_primitive(call)
                via = ""
                if prim is None:
                    # does a strong-resolved callee block?
                    for cs in cg.callees(key, strong_only=True):
                        if cs.lineno != call.lineno:
                            continue
                        reach = {cs.callee} | reach_closure(
                            cg, cs.callee, strong_only=True,
                            memo=memo_local,
                        )
                        for r in reach:
                            if blocking.get(r):
                                prim = blocking[r][0][0]
                                via = cs.callee.rsplit(":", 1)[-1]
                                break
                        # CL801: locks acquired by the callee while
                        # we hold `held`
                        for ln2 in acq_closure.get(cs.callee, ()):
                            for h in held:
                                edge_site.setdefault(
                                    (h, ln2),
                                    (fi.module, call.lineno, qual),
                                )
                        if prim:
                            break
                if prim:
                    # ordinal scoped per (module, function, primitive)
                    # so the baseline fingerprint survives unrelated
                    # findings elsewhere in the tree
                    okey = f"{fi.module}|{qual}:{prim}"
                    ordinals[okey] = ordinals.get(okey, 0) + 1
                    msg_via = f" (via `{via}`)" if via else ""
                    findings.append(Finding(
                        fi.module, call.lineno, "CL802",
                        f"blocking call `{prim}`{msg_via} while "
                        f"holding a lock in `{qual}` — every other "
                        f"thread queues on the lock for the full "
                        f"wait; move the IO outside the with-block",
                        symbol=f"{qual}:{prim}:{ordinals[okey]}",
                    ))

        # CL801: cycles among the union-find representatives
        graph: Dict[object, Set[object]] = {}
        for (a, b), site in edge_site.items():
            a, b = self._uf.find(a), self._uf.find(b)
            if a == b:
                continue
            graph.setdefault(a, set()).add(b)
        for cyc in _cycles(graph):
            names = sorted(_lock_label(n) for n in cyc)
            anchor = None
            for (a, b), site in sorted(edge_site.items(),
                                       key=lambda kv: kv[1][:2]):
                if self._uf.find(a) in cyc and self._uf.find(b) in cyc:
                    anchor = site
                    break
            path, line, qual = anchor or ("<unknown>", 1, "<unknown>")
            findings.append(Finding(
                path, line, "CL801",
                f"lock-order cycle {' -> '.join(names)} -> "
                f"{names[0]} (potential deadlock): two threads "
                f"taking the cycle from different entry points "
                f"wedge; pick one global order",
                symbol="cycle:" + "|".join(names),
            ))

        findings.extend(self._guarded_fields(ctx, cg))
        return findings

    # ---- CL803 ---------------------------------------------------------

    def _guarded_fields(self, ctx: LintContext,
                        cg) -> Iterable[Finding]:
        findings: List[Finding] = []
        # thread-shared classes: a method reachable from a thread
        # root (weak edges included — reachability must not miss), or
        # defined in a CL601 threaded module
        shared: Set[Tuple[str, str]] = set()
        for key in cg.thread_reachable:
            fi = cg.funcs.get(key)
            if fi is not None and fi.cls:
                shared.add((fi.module, fi.cls))
        for fi in cg.funcs.values():
            if fi.cls and any(fi.module.endswith(s)
                              for s in THREADED_SUFFIXES):
                shared.add((fi.module, fi.cls))

        for (mod_path, cls) in sorted(shared):
            members = [f for f in cg.funcs.values()
                       if f.module == mod_path and f.cls == cls
                       and "<locals>" not in f.qual]
            lock_attrs = self._class_lock_attrs(members)
            if not lock_attrs:
                continue
            locked_writes: Dict[str, List] = {}
            bare_writes: Dict[str, List] = {}
            for fi in members:
                if fi.name == "__init__":
                    continue
                self._method_writes(fi, lock_attrs, locked_writes,
                                    bare_writes)
            for attr, bare in sorted(bare_writes.items()):
                if attr not in locked_writes or attr in lock_attrs:
                    continue
                for (line, qual) in bare:
                    findings.append(Finding(
                        mod_path, line, "CL803",
                        f"`self.{attr}` written without the lock in "
                        f"`{qual}` but under `with self."
                        f"{sorted(lock_attrs)[0]}` elsewhere in "
                        f"`{cls}` — a torn write on the "
                        f"thread-shared instance",
                        symbol=f"{cls}.{attr}:{qual}",
                    ))
        return findings

    @staticmethod
    def _class_lock_attrs(members) -> Set[str]:
        attrs: Set[str] = set()
        for fi in members:
            for node in ast.walk(fi.node):
                if isinstance(node, (ast.With, ast.AsyncWith)):
                    for item in node.items:
                        d = dotted(item.context_expr)
                        if (d and d.startswith("self.")
                                and _is_lock_expr(item.context_expr)):
                            attrs.add(d[5:])
                elif (isinstance(node, ast.Assign)
                      and fi.name == "__init__"):
                    for t in node.targets:
                        d = dotted(t)
                        if d and d.startswith("self.") and _lockish(
                            d[5:]
                        ):
                            attrs.add(d[5:])
        return attrs

    @staticmethod
    def _method_writes(fi, lock_attrs, locked_writes, bare_writes):
        # statements lexically inside a `with self.<lock>` block
        locked_ids: Set[int] = set()

        def mark(node, locked):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                if any(
                    (dotted(i.context_expr) or "").startswith("self.")
                    and (dotted(i.context_expr) or "")[5:] in lock_attrs
                    for i in node.items
                ):
                    locked = True
            for child in ast.iter_child_nodes(node):
                if locked:
                    locked_ids.add(id(child))
                mark(child, locked)

        mark(fi.node, False)

        def note(attr, node):
            bucket = (locked_writes if id(node) in locked_ids
                      else bare_writes)
            bucket.setdefault(attr, []).append(
                (node.lineno, fi.qual)
            )

        for node in ast.walk(fi.node):
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (node.targets
                           if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    base = t
                    if isinstance(t, ast.Subscript):
                        base = t.value
                    d = dotted(base)
                    if d and d.startswith("self.") and d.count(".") == 1:
                        note(d[5:], node)
            elif (isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Attribute)
                  and node.func.attr in _MUTATORS):
                d = dotted(node.func.value)
                if d and d.startswith("self.") and d.count(".") == 1:
                    note(d[5:], node)


def _lockish(name: str) -> bool:
    return any(s in name.lower() for s in
               ("lock", "rlock", "mutex", "semaphore"))


def _blocking_primitive(call: ast.Call) -> Optional[str]:
    name = call_name(call) or ""
    tail = name.rsplit(".", 1)[-1]
    attr = call.func.attr if isinstance(call.func, ast.Attribute) \
        else ""
    if name in _BLOCKING_DOTTED:
        return name
    if tail in _BLOCKING_TAILS:
        return name or tail
    if any(tail.startswith(p) for p in _BLOCKING_PREFIXES) and attr:
        return name or tail
    # shape-gated attribute calls: Future.result() / Thread.join()
    # take no positional args; str.join / dict.get take one
    if attr in ("result", "join") and not call.args:
        return f"{dotted(call.func.value) or '<recv>'}.{attr}"
    if attr == "get" and call.args and (
        dotted(call.func.value) or ""
    ).split(".")[-1] in ("q", "queue", "inbox"):
        return f"{dotted(call.func.value)}.get"
    return None


def _lock_label(node) -> str:
    node = node if isinstance(node, tuple) else (str(node),)
    if node[0] == "a":
        return f"{node[1]}.{node[2]}"
    if node[0] == "g":
        return f"{node[1]}:{node[2]}"
    return str(node[-1])


def _cycles(graph: Dict[object, Set[object]]) -> List[Set[object]]:
    """Strongly connected components with >1 node, via the shared
    iterative Tarjan (:func:`tools.crdtlint.callgraph._tarjan` — one
    SCC implementation in the suite, no recursion-limit exposure)."""
    from tools.crdtlint.callgraph import _tarjan

    adj: Dict[object, Set[object]] = dict(graph)
    for succs in graph.values():
        for v in succs:
            adj.setdefault(v, set())
    _, comps = _tarjan(adj)
    return [set(c) for c in comps if len(c) > 1]
