"""RA001 — lock discipline for the hetero serving stack.

The stack's threads (S-worker driver, R-worker threads, timer-delayed
sink posts, fleet hooks) share a handful of lock-owning classes
(``CompletionSink``, ``HostTier``, ``MetricsRegistry``,
``FaultPlan``).  Correctness rests on two properties nothing else
checks statically:

1. **A global lock order exists.**  Build the static lock-order graph:
   node = one lock attribute of one class, edge A -> B = somewhere the
   code can acquire B while holding A (lexically nested ``with``/
   ``acquire``, or a call made under A to a function whose transitive
   summary acquires B).  Any cycle — including a self-edge on a
   non-reentrant ``Lock`` — is a potential deadlock and is flagged.
   The discovered graph is deposited in ``artifacts["lock_graph"]`` so
   the runtime witness (``repro.analysis.lockwitness``) and the docs
   can be checked against it.

2. **Guarded state stays guarded.**  Within a lock-owning class, any
   ``self.<attr>`` that is ever mutated under the class lock is
   inferred to be lock-guarded shared state; a mutation of it outside
   the lock (and outside ``__init__``) is flagged.  A helper method
   whose every intra-class call site holds the lock counts as
   lock-held (the ``CompletionSink._buffer`` idiom: "caller holds
   self._lock").  Mutations of another object's guarded attribute
   (``sink._bufs[...] = ...`` from a worker) are flagged wherever they
   appear.

Lock creation is recognized as ``threading.Lock()`` / ``RLock()``,
the repo's instrumented factory ``make_lock(name, reentrant=...)``,
or assignment of a parameter whose name contains ``lock`` (the
``MetricsRegistry`` -> ``Counter`` shared-lock idiom; such aliases get
their own graph node annotated as an alias).
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from repro.analysis.core import Checker, Finding, Project, SourceFile

_MUTATING_METHODS = {
    "append", "extend", "insert", "remove", "pop", "popitem", "clear",
    "update", "setdefault", "add", "discard", "appendleft", "popleft",
    "move_to_end", "sort", "reverse",
}
_LOCK_FACTORIES = {"threading.Lock": "Lock", "threading.RLock": "RLock",
                   "Lock": "Lock", "RLock": "RLock"}
_MAKE_LOCK_NAMES = {"make_lock", "lockwitness.make_lock", "LW.make_lock"}

# Method names that also belong to builtin containers / stdlib sync
# primitives.  A cross-object call ``x.get(...)`` is far more likely a
# dict read than HostTier.get, so these never resolve cross-class —
# receiver types are outside static reach and a wrong resolution here
# fabricates lock-order edges (ctx.get -> HostTier.get was the very
# first false cycle this checker reported on its own codebase).
_GENERIC_METHODS = (
    {m for t in (dict, list, set, str, tuple, frozenset, bytes)
     for m in dir(t) if not m.startswith("_")}
    | {"put", "put_nowait", "get_nowait", "qsize", "task_done",
       "acquire", "release", "start", "join", "cancel", "close",
       "flush", "read", "write", "set", "is_set", "is_alive", "wait",
       "notify", "notify_all", "submit", "run", "send", "fileno"})


@dataclass
class LockDef:
    """One lock node: ``<module-stem>.<Class>.<attr>``."""
    cls: str                    # "HostTier"
    attr: str                   # "_lock"
    kind: str                   # "Lock" | "RLock" | "alias"
    file: str
    line: int

    @property
    def node_id(self) -> str:
        return f"{self.cls}.{self.attr}"


@dataclass
class ClassInfo:
    name: str
    sf: SourceFile
    node: ast.ClassDef
    locks: Dict[str, LockDef] = field(default_factory=dict)  # attr -> def
    methods: Dict[str, ast.FunctionDef] = field(default_factory=dict)


class _FuncSummary:
    """Locks a function acquires directly + calls it makes (for the
    transitive fixpoint)."""

    def __init__(self):
        self.acquires: Set[str] = set()          # lock node ids
        self.calls: Set[Tuple[str, str]] = set()  # (kind, name)
        #   kind: "self" (self.method()) | "name" (bare/dotted method name)


def _is_lock_creation(value: ast.AST) -> Optional[str]:
    """'Lock'/'RLock' if ``value`` constructs a lock, else None."""
    if not isinstance(value, ast.Call):
        return None
    name = Checker.dotted(value.func)
    if name in _LOCK_FACTORIES:
        return _LOCK_FACTORIES[name]
    if name in _MAKE_LOCK_NAMES or (name or "").endswith(".make_lock"):
        for kw in value.keywords:
            if kw.arg == "reentrant" and isinstance(kw.value, ast.Constant):
                return "RLock" if kw.value.value else "Lock"
        if len(value.args) >= 2 and isinstance(value.args[1], ast.Constant):
            return "RLock" if value.args[1].value else "Lock"
        return "Lock"
    return None


class LockDiscipline(Checker):
    code = "RA001"
    name = "lock-discipline"
    describe = ("static lock-order graph must be acyclic; lock-guarded "
                "attributes must not be mutated lock-free")

    def run(self, project: Project) -> List[Finding]:
        findings: List[Finding] = []
        classes = self._collect_classes(project)
        lock_owners = {c.name: c for c in classes.values() if c.locks}

        # per-method summaries + per-class lock-held method inference
        summaries: Dict[Tuple[str, str], _FuncSummary] = {}
        held_only_methods: Dict[str, Set[str]] = {}
        for cname, ci in lock_owners.items():
            held_only_methods[cname] = self._lock_held_helpers(ci)
        for cname, ci in classes.items():
            for mname, fn in ci.methods.items():
                summaries[(cname, mname)] = self._summarize(ci, fn)

        resolvable = self._resolvable(classes, lock_owners)
        acquires_trans = self._fixpoint(summaries, resolvable)

        # -- 1. lock-order graph --------------------------------------------
        edges: Dict[Tuple[str, str], List[str]] = {}
        for cname, ci in classes.items():
            for mname, fn in ci.methods.items():
                body_held: Set[str] = set()
                if cname in held_only_methods \
                        and mname in held_only_methods[cname]:
                    body_held = {ld.node_id for ld in ci.locks.values()}
                self._walk_held(ci, fn, body_held, edges,
                                acquires_trans, resolvable)

        graph = sorted({a for a, _ in edges} | {b for _, b in edges}
                       | {ld.node_id for c in lock_owners.values()
                          for ld in c.locks.values()})
        self.artifacts["lock_graph"] = {
            "nodes": graph,
            "edges": [{"from": a, "to": b, "sites": sorted(set(sites))}
                      for (a, b), sites in sorted(edges.items())],
        }
        lock_kinds = {ld.node_id: ld.kind
                      for c in lock_owners.values()
                      for ld in c.locks.values()}
        for (a, b), sites in sorted(edges.items()):
            if a == b and lock_kinds.get(a) != "RLock":
                findings.append(self._edge_finding(
                    sites, f"self-acquisition of non-reentrant lock "
                           f"{a} — deadlock"))
        for cyc in self._cycles(edges):
            sites = []
            for a, b in zip(cyc, cyc[1:] + cyc[:1]):
                sites.extend(edges.get((a, b), []))
            if len(cyc) > 1:
                findings.append(self._edge_finding(
                    sites, "lock-order cycle "
                    + " -> ".join(cyc + [cyc[0]])
                    + " — acquisition-order inversion can deadlock"))

        # -- 2. guarded-attribute discipline ---------------------------------
        guarded: Dict[str, Set[str]] = {}
        for cname, ci in lock_owners.items():
            findings.extend(self._guarded_mutations(
                ci, held_only_methods[cname], guarded))
        self._external_mutations(project, classes, guarded, findings)
        return findings

    # -- collection ----------------------------------------------------------
    def _collect_classes(self, project: Project) -> Dict[str, ClassInfo]:
        classes: Dict[str, ClassInfo] = {}
        for sf in project.src_files:
            if sf.tree is None:
                continue
            for node in ast.walk(sf.tree):
                if not isinstance(node, ast.ClassDef):
                    continue
                ci = ClassInfo(node.name, sf, node)
                for item in node.body:
                    if isinstance(item, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        ci.methods[item.name] = item
                for fn in ci.methods.values():
                    params = {a.arg for a in fn.args.args}
                    for st in ast.walk(fn):
                        if not isinstance(st, ast.Assign):
                            continue
                        for tgt in st.targets:
                            attr = self._self_attr(tgt)
                            if attr is None:
                                continue
                            kind = _is_lock_creation(st.value)
                            if kind is None and fn.name == "__init__" \
                                    and isinstance(st.value, ast.Name) \
                                    and "lock" in st.value.id.lower() \
                                    and st.value.id in params:
                                kind = "alias"
                            if kind is not None:
                                ci.locks[attr] = LockDef(
                                    ci.name, attr, kind, sf.rel, st.lineno)
                # later class with the same name would shadow — keep the
                # first and let findings name the file anyway
                classes.setdefault(ci.name, ci)
        return classes

    @staticmethod
    def _self_attr(node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name) \
                and node.value.id == "self":
            return node.attr
        return None

    # -- summaries + fixpoint -------------------------------------------------
    def _lock_expr(self, ci: ClassInfo, expr: ast.AST) -> Optional[str]:
        """Lock node id when ``expr`` denotes a known lock."""
        attr = self._self_attr(expr)
        if attr is not None and attr in ci.locks:
            return ci.locks[attr].node_id
        return None

    def _summarize(self, ci: ClassInfo, fn: ast.FunctionDef
                   ) -> _FuncSummary:
        s = _FuncSummary()
        for node in ast.walk(fn):
            if isinstance(node, ast.With):
                for item in node.items:
                    lid = self._lock_expr(ci, item.context_expr)
                    if lid:
                        s.acquires.add(lid)
            elif isinstance(node, ast.Call):
                name = Checker.dotted(node.func)
                if name is None:
                    continue
                if name.endswith(".acquire"):
                    lid = self._lock_expr(
                        ci, node.func.value)  # type: ignore[attr-defined]
                    if lid:
                        s.acquires.add(lid)
                elif name.startswith("self."):
                    parts = name.split(".")
                    if len(parts) == 2:
                        s.calls.add(("self", parts[1]))
                    else:
                        s.calls.add(("name", parts[-1]))
                else:
                    s.calls.add(("name", name.split(".")[-1]))
        return s

    @staticmethod
    def _resolvable(classes: Dict[str, ClassInfo],
                    lock_owners: Dict[str, ClassInfo]
                    ) -> Dict[str, List[Tuple[str, str]]]:
        """Method names a cross-object call may resolve to.

        ``x.m(...)`` resolves to ``C.m`` only when every class in the
        project defining ``m`` owns a lock and ``m`` is not a builtin-
        container/sync-primitive name (see ``_GENERIC_METHODS``).
        Ambiguous lock-owning candidates are unioned — a deliberate
        over-approximation (a missed edge hides a deadlock; a spurious
        one costs a review)."""
        defined_in: Dict[str, Set[str]] = {}
        for cname, ci in classes.items():
            for mname in ci.methods:
                defined_in.setdefault(mname, set()).add(cname)
        out: Dict[str, List[Tuple[str, str]]] = {}
        for mname, owners in defined_in.items():
            if mname in _GENERIC_METHODS or mname.startswith("__"):
                continue
            if owners and all(c in lock_owners for c in owners):
                out[mname] = [(c, mname) for c in sorted(owners)]
        return out

    def _fixpoint(self, summaries: Dict[Tuple[str, str], _FuncSummary],
                  by_name: Dict[str, List[Tuple[str, str]]]
                  ) -> Dict[Tuple[str, str], Set[str]]:
        """Transitive acquires per (class, method)."""
        trans = {k: set(s.acquires) for k, s in summaries.items()}
        changed = True
        while changed:
            changed = False
            for key, s in summaries.items():
                cname, _ = key
                acc = trans[key]
                before = len(acc)
                for kind, callee in s.calls:
                    if kind == "self":
                        acc |= trans.get((cname, callee), set())
                    else:
                        for tgt in by_name.get(callee, ()):
                            if tgt[0] != cname:
                                acc |= trans.get(tgt, set())
                if len(acc) != before:
                    changed = True
        return trans

    # -- nesting walk ---------------------------------------------------------
    def _walk_held(self, ci: ClassInfo, fn: ast.FunctionDef,
                   base_held: Set[str],
                   edges: Dict[Tuple[str, str], List[str]],
                   acquires_trans: Dict[Tuple[str, str], Set[str]],
                   by_name: Dict[str, List[Tuple[str, str]]]) -> None:
        site = f"{ci.sf.rel}:{fn.lineno} {ci.name}.{fn.name}"

        def visit(node: ast.AST, held: Set[str]) -> None:
            if isinstance(node, ast.With):
                inner = set(held)
                for item in node.items:
                    lid = self._lock_expr(ci, item.context_expr)
                    if lid:
                        for h in held:
                            edges.setdefault((h, lid), []).append(
                                f"{ci.sf.rel}:{node.lineno}")
                        inner.add(lid)
                for st in node.body:
                    visit(st, inner)
                return
            if isinstance(node, ast.Call) and held:
                name = Checker.dotted(node.func)
                callee_acq: Set[str] = set()
                if name and name.startswith("self."):
                    parts = name.split(".")
                    if len(parts) == 2:
                        callee_acq = acquires_trans.get(
                            (ci.name, parts[1]), set())
                    else:
                        for tgt in by_name.get(parts[-1], ()):
                            callee_acq |= acquires_trans.get(tgt, set())
                elif name:
                    if name.endswith(".acquire"):
                        lid = self._lock_expr(ci, node.func.value)
                        if lid:
                            callee_acq = {lid}
                    else:
                        for tgt in by_name.get(name.split(".")[-1], ()):
                            if tgt[0] != ci.name:
                                callee_acq |= acquires_trans.get(tgt, set())
                for lid in callee_acq:
                    for h in held:
                        edges.setdefault((h, lid), []).append(
                            f"{ci.sf.rel}:{node.lineno} (via {site})")
            for child in ast.iter_child_nodes(node):
                visit(child, held)

        for st in fn.body:
            visit(st, set(base_held))

    def _edge_finding(self, sites: List[str], msg: str) -> Finding:
        path, line = "<lock-graph>", 0
        if sites:
            loc = sites[0].split(" ")[0]
            if ":" in loc:
                path, _, ln = loc.rpartition(":")
                line = int(ln) if ln.isdigit() else 0
        return Finding(self.code, path, line, 0,
                       msg + f" [sites: {', '.join(sorted(set(sites))[:4])}]")

    @staticmethod
    def _cycles(edges: Dict[Tuple[str, str], List[str]]) -> List[List[str]]:
        """Elementary cycles via SCC (Tarjan, iterative; graphs here are
        tiny).  Returns each multi-node SCC as a node list."""
        adj: Dict[str, Set[str]] = {}
        for a, b in edges:
            adj.setdefault(a, set()).add(b)
            adj.setdefault(b, set())
        index: Dict[str, int] = {}
        low: Dict[str, int] = {}
        on_stack: Set[str] = set()
        stack: List[str] = []
        sccs: List[List[str]] = []
        counter = [0]

        def strongconnect(v: str) -> None:
            work = [(v, iter(sorted(adj[v])))]
            index[v] = low[v] = counter[0]
            counter[0] += 1
            stack.append(v)
            on_stack.add(v)
            while work:
                node, it = work[-1]
                advanced = False
                for w in it:
                    if w not in index:
                        index[w] = low[w] = counter[0]
                        counter[0] += 1
                        stack.append(w)
                        on_stack.add(w)
                        work.append((w, iter(sorted(adj[w]))))
                        advanced = True
                        break
                    elif w in on_stack:
                        low[node] = min(low[node], index[w])
                if advanced:
                    continue
                work.pop()
                if low[node] == index[node]:
                    comp = []
                    while True:
                        w = stack.pop()
                        on_stack.discard(w)
                        comp.append(w)
                        if w == node:
                            break
                    if len(comp) > 1:
                        sccs.append(sorted(comp))
                if work:
                    low[work[-1][0]] = min(low[work[-1][0]], low[node])

        for v in sorted(adj):
            if v not in index:
                strongconnect(v)
        return sccs

    # -- guarded-attribute analysis -------------------------------------------
    def _lock_held_helpers(self, ci: ClassInfo) -> Set[str]:
        """Methods whose every intra-class call site is lexically under
        the class lock — their bodies count as lock-held."""
        call_sites: Dict[str, List[bool]] = {}

        def visit(node: ast.AST, held: bool) -> None:
            if isinstance(node, ast.With):
                inner = held or any(
                    self._lock_expr(ci, item.context_expr)
                    for item in node.items)
                for st in node.body:
                    visit(st, inner)
                return
            if isinstance(node, ast.Call):
                name = Checker.dotted(node.func)
                if name and name.startswith("self.") \
                        and name.count(".") == 1:
                    call_sites.setdefault(name[5:], []).append(held)
            for child in ast.iter_child_nodes(node):
                visit(child, held)

        for fn in ci.methods.values():
            for st in fn.body:
                visit(st, False)
        return {m for m, sites in call_sites.items()
                if sites and all(sites) and m in ci.methods}

    def _mutations(self, ci: ClassInfo, fn: ast.FunctionDef,
                   base_held: bool):
        """Yield (attr, lineno, col, held) for every ``self.<attr>``
        mutation in ``fn``."""
        out: List[Tuple[str, int, int, bool]] = []

        def root_attr(node: ast.AST) -> Optional[str]:
            # self.X, self.X[...], self.X.anything -> "X"
            while isinstance(node, (ast.Subscript, ast.Attribute)):
                parent = node.value
                if isinstance(node, ast.Attribute) \
                        and isinstance(parent, ast.Name) \
                        and parent.id == "self":
                    return node.attr
                node = parent
            return None

        def visit(node: ast.AST, held: bool) -> None:
            if isinstance(node, ast.With):
                inner = held or any(
                    self._lock_expr(ci, item.context_expr)
                    for item in node.items)
                for st in node.body:
                    visit(st, inner)
                return
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.Delete)):
                targets = node.targets if isinstance(
                    node, (ast.Assign, ast.Delete)) else [node.target]
                for tgt in targets:
                    attr = root_attr(tgt)
                    if attr is not None:
                        out.append((attr, tgt.lineno,
                                    tgt.col_offset, held))
            elif isinstance(node, ast.Call):
                name = Checker.dotted(node.func)
                if name and name.startswith("self.") \
                        and name.split(".")[-1] in _MUTATING_METHODS \
                        and name.count(".") >= 2:
                    attr = name.split(".")[1]
                    out.append((attr, node.lineno, node.col_offset, held))
            for child in ast.iter_child_nodes(node):
                visit(child, held)

        for st in fn.body:
            visit(st, base_held)
        return out

    def _guarded_mutations(self, ci: ClassInfo, held_helpers: Set[str],
                           guarded_out: Dict[str, Set[str]]
                           ) -> List[Finding]:
        muts: List[Tuple[str, str, int, int, bool]] = []  # + method name
        for mname, fn in ci.methods.items():
            base_held = mname in held_helpers
            for attr, line, col, held in self._mutations(ci, fn, base_held):
                muts.append((mname, attr, line, col, held))
        lock_attrs = set(ci.locks)
        guarded = {attr for mname, attr, _, _, held in muts
                   if held and mname != "__init__"
                   and attr not in lock_attrs}
        guarded_out[ci.name] = guarded
        out: List[Finding] = []
        for mname, attr, line, col, held in muts:
            if attr in guarded and not held and mname != "__init__":
                out.append(Finding(
                    self.code, ci.sf.rel, line, col,
                    f"{ci.name}.{mname} mutates lock-guarded "
                    f"'self.{attr}' without holding "
                    f"{sorted(ld.node_id for ld in ci.locks.values())} "
                    f"(attribute is mutated under the lock elsewhere)"))
        return out

    def _external_mutations(self, project: Project,
                            classes: Dict[str, ClassInfo],
                            guarded: Dict[str, Set[str]],
                            findings: List[Finding]) -> None:
        """Mutation of another object's guarded attr (``x._bufs[...]=``)
        outside the owning class.  Only attr names unique to ONE
        lock-owning class are matched, so unrelated same-named attrs
        never false-positive."""
        owner_of: Dict[str, str] = {}
        ambiguous: Set[str] = set()
        all_attrs: Dict[str, int] = {}
        for ci in classes.values():
            for fn in ci.methods.values():
                for st in ast.walk(fn):
                    if isinstance(st, ast.Assign):
                        for tgt in st.targets:
                            a = self._self_attr(tgt)
                            if a:
                                all_attrs[a] = all_attrs.get(a, 0) + 1
        for cname, attrs in guarded.items():
            for a in attrs:
                if a in owner_of:
                    ambiguous.add(a)
                owner_of[a] = cname
        watch = {a: c for a, c in owner_of.items() if a not in ambiguous}
        if not watch:
            return
        for sf in project.src_files:
            if sf.tree is None:
                continue
            for node in ast.walk(sf.tree):
                tgt = None
                if isinstance(node, (ast.Assign, ast.AugAssign)):
                    tgts = node.targets if isinstance(node, ast.Assign) \
                        else [node.target]
                    for t in tgts:
                        # x.attr[...] = / x.attr = where x is NOT self
                        inner = t
                        while isinstance(inner, ast.Subscript):
                            inner = inner.value
                        if isinstance(inner, ast.Attribute) \
                                and inner.attr in watch \
                                and not (isinstance(inner.value, ast.Name)
                                         and inner.value.id == "self"):
                            tgt = (inner.attr, t.lineno, t.col_offset)
                if tgt is None:
                    continue
                attr, line, col = tgt
                owner = watch[attr]
                oci = classes[owner]
                if sf.rel == oci.sf.rel and oci.node.lineno <= line \
                        <= (oci.node.end_lineno or 10**9):
                    continue                     # inside the owning class
                findings.append(Finding(
                    self.code, sf.rel, line, col,
                    f"mutation of {owner}.{attr} from outside the owning "
                    f"class — that attribute is guarded by "
                    f"{[ld.node_id for ld in oci.locks.values()]}"))
