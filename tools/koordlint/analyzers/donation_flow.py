"""donation-flow: the double-buffer hand-off, verified interprocedurally.

PR 11 split the round into device/host halves with a donation-based
hand-off: the dispatched solve DONATES ``snapshot.state``'s buffers and
the snapshot must be re-pointed at the returned in-flight arrays before
anything else reads it — the *blessed swap*.  The existing
donation-safety rule polices single-function idioms only; this rule
runs the specflow dataflow over the whole call graph:

- **binding resolution through the kit.**  Donating jit bindings are
  found not just at ``self._x = jax.jit(...)`` sites but through typed
  attributes (``self.kit = SolverKit(...)`` ⇒ ``self.kit.pass1(...)``
  and the alias ``self._rsv_solve = self.kit.rsv_solve`` resolve in
  SolverKit), donating-parameter summaries (a function that hands its
  parameter N to a donating position donates its parameter N — the
  kit's one-entry-per-stage methods, which pick between two jitted
  programs, donate ``state`` either way), and factory summaries (a
  function whose return value is a donating jit — tenancy's
  ``_batched_fn`` — makes ``fn = self._batched_fn(key); fn(state,
  ...)`` a donating call).
- **⊥ after dispatch.**  A donated argument path's abstract value
  becomes ⊥ (dead) at the call; a *store* to the same path (the blessed
  swap) revives it.  Any load of a dead path — directly, or through a
  **stash alias** captured before the dispatch (``old =
  self.snapshot.state`` … ``dispatch()`` … ``old.sum()``) — is a
  finding.  A stash stays dead even after the swap: the name still
  points at the consumed buffer.
- **interprocedural summaries.**  Each function summarizes which
  ``self.*`` paths it kills (donates without re-storing before exit)
  and which it reads before storing; a caller that invokes a killing
  method and then a reading method (or reads directly) is a finding at
  the reading site.  Summaries reach a fixpoint in a few passes over
  the call graph.

Source-order linearization (like donation-safety): exception edges and
loop-carried reads are out of scope; ``.shape``-class metadata reads
survive donation and are exempt.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Optional

from ..callgraph import FunctionInfo, ModuleIndex, extract_jit_sites, get_index
from ..core import Analyzer, Finding, Project
from .donation_safety import dotted_path
from .jit_host_sync import HOST_SAFE_ATTRS

#: attribute probes that are ABOUT deadness (the recovery path's
#: `leaf.is_deleted()` check) — reading them is not consuming the buffer
_DEADNESS_PROBES = {"is_deleted"}

_FIXPOINT_PASSES = 4


@dataclasses.dataclass
class Summary:
    """Per-function donation facts over canonical ``self.*`` paths."""

    kills: frozenset[str] = frozenset()        # dead at exit
    reads_first: frozenset[str] = frozenset()  # read before any store
    stores_first: frozenset[str] = frozenset()  # stored before any read
    #: own parameters (``self`` not counted) handed on at a donating
    #: position: a call of this function donates those arguments
    donates: tuple[int, ...] = ()


class DonationFlowAnalyzer(Analyzer):
    name = "donation-flow"
    description = ("interprocedural double-buffer verification: a "
                   "donated buffer is dead until the blessed swap; "
                   "stashes and cross-function reads are findings")

    def __init__(self, package: str = "koordinator_tpu"):
        self.package = package

    # -- binding discovery ----------------------------------------------------

    def _attr_classes(self, index: ModuleIndex) -> dict[tuple[str, str], str]:
        """``(module.Class, attr) -> attribute's class fq`` from
        ``self.X = ClassName(...)`` in ``__init__`` (ternary arms
        included) — the typed-attribute resolution lock-discipline
        already uses, rebuilt here for donation bindings."""
        out: dict[tuple[str, str], str] = {}
        for fq, fn in index.functions.items():
            if not fq.endswith(".__init__"):
                continue
            cls = fq[: -len(".__init__")]
            for node in ast.walk(fn.node):
                if not (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Attribute)
                        and isinstance(node.targets[0].value, ast.Name)
                        and node.targets[0].value.id == "self"):
                    continue
                attr = node.targets[0].attr
                values = [node.value]
                if isinstance(node.value, ast.IfExp):
                    values = [node.value.body, node.value.orelse]
                for v in values:
                    if isinstance(v, ast.Call):
                        target = index.resolve(fn.module, v.func)
                        if target in index.classes:
                            out[(cls, attr)] = target
        return out

    def _collect_bindings(self, index: ModuleIndex):
        """(class_bindings, name_bindings, factory_returns): donated
        positions per binding, plus functions returning donating jits."""
        class_bindings: dict[tuple[str, str], tuple[int, ...]] = {}
        name_bindings: dict[str, tuple[int, ...]] = {}
        for s in extract_jit_sites(index):
            if not s.donate_argnums:
                continue
            if s.binding and s.binding_class:
                key = (f"{s.module}.{s.binding_class}", s.binding)
                class_bindings[key] = tuple(sorted(
                    set(class_bindings.get(key, ()) + s.donate_argnums)))
            elif s.binding:
                name_bindings[f"{s.module}.{s.binding}"] = s.donate_argnums

        attr_cls = self._attr_classes(index)
        # attribute-to-attribute aliases: self._pass1 = self.kit.pass1
        # (two passes so a chain through one alias level resolves)
        for _ in range(2):
            for fq, fn in index.functions.items():
                if not fq.endswith(".__init__"):
                    continue
                cls = fq[: -len(".__init__")]
                for node in ast.walk(fn.node):
                    if not (isinstance(node, ast.Assign)
                            and len(node.targets) == 1
                            and isinstance(node.targets[0], ast.Attribute)
                            and isinstance(node.targets[0].value, ast.Name)
                            and node.targets[0].value.id == "self"
                            and isinstance(node.value, ast.Attribute)
                            and isinstance(node.value.value,
                                           ast.Attribute)
                            and isinstance(node.value.value.value,
                                           ast.Name)
                            and node.value.value.value.id == "self"):
                        continue
                    via = attr_cls.get((cls, node.value.value.attr))
                    if via is None:
                        continue
                    donated = class_bindings.get((via, node.value.attr))
                    if donated:
                        key = (cls, node.targets[0].attr)
                        class_bindings[key] = tuple(sorted(
                            set(class_bindings.get(key, ()) + donated)))

        # factory summaries: `fn = jax.jit(..., donate_argnums=...)` +
        # `return fn` makes the function a donating-callable factory
        factory: dict[str, tuple[int, ...]] = {}
        for fq, fn in index.functions.items():
            local_jits: dict[str, tuple[int, ...]] = {}
            for node in ast.walk(fn.node):
                if (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)):
                    d = self._jit_donate(index, fn.module, node.value)
                    if d:
                        local_jits[node.targets[0].id] = d
            if not local_jits:
                continue
            for node in ast.walk(fn.node):
                if (isinstance(node, ast.Return)
                        and isinstance(node.value, ast.Name)
                        and node.value.id in local_jits):
                    factory[fq] = tuple(sorted(set(
                        factory.get(fq, ())
                        + local_jits[node.value.id])))
        return class_bindings, name_bindings, factory, attr_cls

    def _jit_donate(self, index, mod, node) -> tuple[int, ...]:
        """donate_argnums of a (possibly wrapped) jax.jit expression."""
        for call in ast.walk(node) if isinstance(node, ast.AST) else []:
            if isinstance(call, ast.Call) and (
                    index.resolve(mod, call.func) == "jax.jit"):
                for kw in call.keywords:
                    if kw.arg == "donate_argnums":
                        if isinstance(kw.value, ast.Constant) \
                                and isinstance(kw.value.value, int):
                            return (kw.value.value,)
                        if isinstance(kw.value, (ast.Tuple, ast.List)):
                            return tuple(
                                e.value for e in kw.value.elts
                                if isinstance(e, ast.Constant)
                                and isinstance(e.value, int))
        return ()

    # -- the analysis ---------------------------------------------------------

    def run(self, project: Project) -> list[Finding]:
        index = get_index(project, self.package)
        (self._class_b, self._name_b, self._factory,
         self._attr_cls) = self._collect_bindings(index)
        if not (self._class_b or self._name_b or self._factory):
            return []
        summaries: dict[str, Summary] = {}
        findings: list[Finding] = []
        for i in range(_FIXPOINT_PASSES):
            new: dict[str, Summary] = {}
            last = i == _FIXPOINT_PASSES - 1
            out = findings if last else []
            for fq, fn in sorted(index.functions.items()):
                new[fq] = self._scan(index, fn, summaries,
                                     out if last else None)
            if new == summaries:
                if not last:
                    # stable early: one reporting pass and stop
                    for fq, fn in sorted(index.functions.items()):
                        self._scan(index, fn, summaries, findings)
                break
            summaries = new
        dedup: dict[tuple, Finding] = {}
        for f in findings:
            dedup.setdefault((f.path, f.line, f.message), f)
        return sorted(dedup.values(), key=lambda f: (f.path, f.line))

    def _donated_positions(self, index, fn, cls, call, local_callables,
                           summaries) -> tuple[int, ...]:
        f = call.func
        if isinstance(f, ast.Name) and f.id in local_callables:
            return local_callables[f.id]
        if isinstance(f, ast.Attribute) and cls:
            # self.x(...) in the own class; self.kit.x(...) in the class
            # of the typed attribute
            owner = None
            if isinstance(f.value, ast.Name) and f.value.id == "self":
                owner = f"{fn.module}.{cls}"
            elif (isinstance(f.value, ast.Attribute)
                  and isinstance(f.value.value, ast.Name)
                  and f.value.value.id == "self"):
                owner = self._attr_cls.get(
                    (f"{fn.module}.{cls}", f.value.attr))
            if owner:
                method = summaries.get(f"{owner}.{f.attr}")
                return (self._class_b.get((owner, f.attr))
                        or (method.donates if method else ()))
        resolved = index.resolve(fn.module, f)
        if resolved:
            if "." not in resolved:
                resolved = f"{fn.module}.{resolved}"
            target = summaries.get(resolved)
            return (self._name_b.get(resolved)
                    or (target.donates if target else ()))
        return ()

    def _callee_fq(self, index, fn, cls, call) -> Optional[str]:
        f = call.func
        if (isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name)
                and f.value.id == "self" and cls):
            return f"{fn.module}.{cls}.{f.attr}"
        resolved = index.resolve(fn.module, f)
        target = index.find_function(resolved)
        return target.fq if target is not None else None

    def _scan(self, index: ModuleIndex, fn: FunctionInfo,
              summaries: dict[str, Summary],
              findings: Optional[list[Finding]]) -> Summary:
        """One source-order pass over a function: tracks dead paths,
        stash aliases and local donating callables; emits findings when
        a report list is given; returns the function's summary."""
        cls = fn.qualname.rsplit(".", 1)[0] if "." in fn.qualname else None
        prefix_alias: dict[str, str] = {}   # snap -> self.snapshot
        stash_alias: dict[str, str] = {}    # old -> self.snapshot.state
        local_callables: dict[str, tuple[int, ...]] = {}
        dead: dict[str, int] = {}           # path -> donating line
        dead_names: set[str] = set()
        first_event: dict[str, str] = {}    # path -> "read" | "store"
        params = [a.arg for a in
                  fn.node.args.posonlyargs + fn.node.args.args]
        if cls and params[:1] in (["self"], ["cls"]):
            params = params[1:]
        donates: set[int] = set()

        def canon(path: Optional[str]) -> Optional[str]:
            if path is None:
                return None
            head, _, rest = path.partition(".")
            if head in prefix_alias:
                return prefix_alias[head] + ("." + rest if rest else "")
            return path

        def note(path: str, kind: str) -> None:
            if path.startswith("self.") and path not in first_event:
                first_event[path] = kind

        # collect statements in source order; nested defs excluded (a
        # closure's execution point is its CALL, which we cannot place)
        nested: set[int] = set()
        for sub in ast.walk(fn.node):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)) and sub is not fn.node:
                for inner in ast.walk(sub):
                    nested.add(id(inner))
        events: list[tuple[int, int, str, object]] = []
        order = 0
        for node in ast.walk(fn.node):
            if id(node) in nested:
                continue
            if isinstance(node, ast.Assign):
                events.append((node.lineno, order, "assign", node))
            elif isinstance(node, ast.Call):
                events.append((node.lineno, order, "call", node))
            elif isinstance(node, ast.Name) and isinstance(
                    node.ctx, ast.Load):
                events.append((node.lineno, order, "load_name", node))
            elif isinstance(node, ast.Attribute):
                events.append((node.lineno, order, "attr", node))
            order += 1
        events.sort(key=lambda e: (e[0], e[1]))

        parents = {c: p for p in ast.walk(fn.node)
                   for c in ast.iter_child_nodes(p)}

        def rebinds(call: ast.Call, path: str) -> bool:
            node: ast.AST = call
            while node in parents:
                node = parents[node]
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        ts = (t.elts if isinstance(
                            t, (ast.Tuple, ast.List)) else [t])
                        if any(canon(dotted_path(x)) == path
                               for x in ts):
                            return True
                    return False
                if isinstance(node, (ast.stmt,)):
                    return False
            return False

        def own_param(call: ast.Call, arg: ast.AST) -> Optional[int]:
            """Index of the own parameter ``arg`` still names at
            ``call``: a bare parameter name not assigned in an earlier
            statement (``state = f(state)`` itself is the hand-on)."""
            if not (isinstance(arg, ast.Name) and arg.id in params):
                return None
            stmt: ast.AST = call
            while stmt in parents and not isinstance(stmt, ast.stmt):
                stmt = parents[stmt]
            for line, _, kind, node in events:
                if line >= stmt.lineno:
                    break
                if kind == "assign" and any(
                        isinstance(t, ast.Name) and t.id == arg.id
                        for tgt in node.targets for t in ast.walk(tgt)):
                    return None
            return params.index(arg.id)

        def report(line: int, msg: str, hint: str) -> None:
            if findings is not None:
                findings.append(Finding(self.name, fn.sf.path, line,
                                        msg, hint))

        for line, _, kind, node in events:
            if kind == "assign":
                self._handle_assign(index, fn, node, prefix_alias,
                                    stash_alias, local_callables,
                                    dead, dead_names, first_event,
                                    canon, note)
            elif kind == "call":
                end = getattr(node, "end_lineno", line)
                donated = self._donated_positions(
                    index, fn, cls, node, local_callables, summaries)
                if donated:
                    for pos in donated:
                        if pos >= len(node.args):
                            continue
                        own = own_param(node, node.args[pos])
                        if own is not None:
                            donates.add(own)
                        p = canon(dotted_path(node.args[pos]))
                        if p is None:
                            continue
                        note(p, "read")
                        if not rebinds(node, p):
                            dead[p] = end
                        # a PRE-dispatch stash dies with the buffer
                        # whether or not the path itself is rebound
                        for n, tgt in stash_alias.items():
                            if tgt == p:
                                dead_names.add(n)
                # a method call ON the object owning a dead path may BE
                # the blessed swap (`self.snapshot.adopt_state(new)`
                # re-points .state inside): conservatively revive paths
                # under an ATTRIBUTE receiver.  Bare-self methods stay
                # precise through the summaries below.
                if isinstance(node.func, ast.Attribute):
                    recv = canon(dotted_path(node.func.value))
                    if recv is not None and "." in recv:
                        for p in [p for p in dead
                                  if p.startswith(recv + ".")]:
                            dead.pop(p, None)
                # interprocedural: same-class callee summaries
                callee = self._callee_fq(index, fn, cls, node)
                summ = summaries.get(callee) if callee else None
                if summ is not None:
                    hit = sorted(p for p in set(summ.reads_first) & set(dead)
                                 if line > dead[p])
                    if hit:
                        report(
                            line,
                            f"{callee.rsplit('.', 1)[-1]}() reads "
                            f"{hit[0]!r}, which a donating dispatch "
                            "left dead (no blessed swap re-pointed it "
                            "before this call)",
                            "store the solve's returned state back to "
                            "the path before running host-half work")
                    for p in summ.kills:
                        dead[p] = end
                        note(p, "read")
                        for n, tgt in stash_alias.items():
                            if tgt == p:
                                dead_names.add(n)
                    for p in summ.stores_first:
                        dead.pop(p, None)
            elif kind == "load_name":
                if node.id in dead_names:
                    par = parents.get(node)
                    if (isinstance(par, ast.Attribute)
                            and par.attr in (HOST_SAFE_ATTRS
                                             | _DEADNESS_PROBES)):
                        continue
                    report(
                        line,
                        f"{node.id!r} stashes a buffer that was later "
                        f"donated ({stash_alias.get(node.id)!r}): the "
                        "stash points at the consumed buffer even "
                        "after the blessed swap",
                        "drop the stash, or capture what you need "
                        "(shapes, copies) before the dispatch")
            elif kind == "attr":
                p = canon(dotted_path(node))
                if p is None:
                    continue
                if isinstance(node.ctx, ast.Store):
                    dead.pop(p, None)
                    if p.startswith("self."):
                        first_event.setdefault(p, "store")
                    continue
                par = parents.get(node)
                if (isinstance(par, ast.Attribute)
                        and par.attr in (HOST_SAFE_ATTRS
                                         | _DEADNESS_PROBES)):
                    continue   # metadata survives donation; not a read
                note(p, "read")
                if p in dead and line > dead[p]:
                    report(
                        line,
                        f"{p!r} read after its buffers were donated: "
                        "the value is dead until the blessed swap "
                        "re-points it at the solve's returned state",
                        "rebind the result first "
                        "(path = solve(path, ...)), or move the read "
                        "before the dispatch")
        return Summary(
            kills=frozenset(p for p in dead if p.startswith("self.")),
            reads_first=frozenset(p for p, k in first_event.items()
                                  if k == "read"),
            stores_first=frozenset(p for p, k in first_event.items()
                                   if k == "store"),
            donates=tuple(sorted(donates)))

    def _handle_assign(self, index, fn, node, prefix_alias, stash_alias,
                       local_callables, dead, dead_names, first_event,
                       canon, note) -> None:
        if len(node.targets) != 1:
            return
        target = node.targets[0]
        # donating-callable locals: jax.jit directly, a self-binding, or
        # a factory call
        if isinstance(target, ast.Name):
            d = self._local_callable(index, fn, node.value)
            if d:
                local_callables[target.id] = d
                prefix_alias.pop(target.id, None)
                stash_alias.pop(target.id, None)
                dead_names.discard(target.id)
                return
            # `snap = self.snapshot` is BOTH an object-prefix alias
            # (so `snap.state` canonicalizes to the real path) and a
            # stash (reading `snap` after `self.snapshot` itself is
            # donated reads the dead buffer)
            src = canon(dotted_path(node.value))
            if src is not None and "." in src:
                prefix_alias[target.id] = src
                stash_alias[target.id] = src
                dead_names.discard(target.id)
                if src in dead:
                    dead_names.add(target.id)
                return
            dead_names.discard(target.id)
        targets = (target.elts if isinstance(target,
                                             (ast.Tuple, ast.List))
                   else [target])
        for t in targets:
            # a rebound name no longer aliases the old self.* path —
            # reads AND stores through it must stop canonicalizing
            if isinstance(t, ast.Name):
                dead_names.discard(t.id)
                stash_alias.pop(t.id, None)
                prefix_alias.pop(t.id, None)

    def _local_callable(self, index, fn, value) -> tuple[int, ...]:
        cls = fn.qualname.rsplit(".", 1)[0] if "." in fn.qualname else None

        def of(node) -> tuple[int, ...]:
            if (isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "self" and cls):
                return self._class_b.get(
                    (f"{fn.module}.{cls}", node.attr), ())
            if isinstance(node, ast.Call):
                d = self._jit_donate(index, fn.module, node)
                if d:
                    return d
                callee = self._callee_fq_simple(index, fn, cls, node)
                if callee in self._factory:
                    return self._factory[callee]
            return ()

        return of(value)

    def _callee_fq_simple(self, index, fn, cls, call) -> Optional[str]:
        f = call.func
        if (isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name)
                and f.value.id == "self" and cls):
            return f"{fn.module}.{cls}.{f.attr}"
        target = index.find_function(index.resolve(fn.module, f))
        return target.fq if target is not None else None
