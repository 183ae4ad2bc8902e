"""donation-safety: ``donate_argnums`` discipline, caught at parse time.

Buffer donation is how the solve path updates the (N, R) accounting in
place instead of reallocating it — and it is the sharpest knife in the
tree.  Two bug classes have already shipped here:

- **read-after-donate**: the caller passes a buffer at a donated
  position, XLA aliases the output into it, and any later host read of
  the SAME reference sees a deleted buffer (best case: a loud
  ``RuntimeError``; worst case on some backends: garbage).  Rule: after
  a call through a donating jit binding, the donated argument expression
  must not be READ again in that function before it is reassigned.
- **donation-aliasing** (the PR-1 ``ClusterState.zeros`` bug): one
  array bound to several fields of a donated pytree means XLA donates
  one buffer that five fields think they own — they die together.
  Rule: a local name holding a freshly-created array must not be passed
  to more than one field of a ``flax.struct.dataclass`` constructor,
  and the same expression must not appear at a donated position AND
  another position of one donating call.

Bindings are found through wrappers (``insp.instrument(jax.jit(...))``)
and matched at call sites by attribute name on the owning class
(``self._pass1(...)``) or module-level name.  The read-after scan is
linear in source order within the calling function — the bug class this
targets is sequential code; loop-carried reads are out of scope (see
docs/static_analysis.md).
"""

from __future__ import annotations

import ast
from typing import Optional

from ..callgraph import ModuleIndex, extract_jit_sites, get_index
from ..core import Analyzer, Finding, Project
from .jit_host_sync import HOST_SAFE_ATTRS

#: fresh-array constructors whose result aliased across pytree fields
#: reproduces the PR-1 bug
ARRAY_CREATORS = {"zeros", "ones", "full", "empty", "arange", "asarray",
                  "array", "zeros_like", "ones_like", "full_like"}


def dotted_path(node: ast.AST) -> Optional[str]:
    """'self.snapshot.state' for a pure Name/Attribute chain, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class DonationSafetyAnalyzer(Analyzer):
    name = "donation-safety"
    description = ("read-after-donate and donated-pytree aliasing around "
                   "donate_argnums jit sites")

    def __init__(self, package: str = "koordinator_tpu"):
        self.package = package
        #: per-function parent map / call->assign index, built once and
        #: reused across every donated argument of every call in it
        self._parents_cache: dict[int, dict] = {}
        self._assign_cache: dict[int, dict] = {}

    def _parents(self, fn) -> dict:
        cached = self._parents_cache.get(id(fn.node))
        if cached is None:
            cached = {c: p for p in ast.walk(fn.node)
                      for c in ast.iter_child_nodes(p)}
            self._parents_cache[id(fn.node)] = cached
        return cached

    def _assign_of_call(self, fn) -> dict:
        """call node id -> enclosing ast.Assign (one walk per fn)."""
        cached = self._assign_cache.get(id(fn.node))
        if cached is None:
            cached = {}
            for node in ast.walk(fn.node):
                if isinstance(node, ast.Assign):
                    for c in ast.walk(node.value):
                        if isinstance(c, ast.Call):
                            cached[id(c)] = node
            self._assign_cache[id(fn.node)] = cached
        return cached

    def run(self, project: Project) -> list[Finding]:
        index = get_index(project, self.package)
        findings: list[Finding] = []
        sites = [s for s in extract_jit_sites(index) if s.donate_argnums]

        # binding -> donated positions, keyed two ways.  Module-level
        # bindings key by FULLY-QUALIFIED name — a same-named function
        # in another module must not match (and two same-named bindings
        # in different modules keep their own donated positions)
        class_bindings: dict[tuple[str, str], tuple[int, ...]] = {}
        name_bindings: dict[str, tuple[int, ...]] = {}
        for s in sites:
            if s.binding and s.binding_class:
                # module-qualified class key: a same-named class in
                # another module must not inherit donated positions
                key = (f"{s.module}.{s.binding_class}", s.binding)
                class_bindings[key] = tuple(
                    sorted(set(class_bindings.get(key, ()) +
                               s.donate_argnums)))
            elif s.binding:
                name_bindings[f"{s.module}.{s.binding}"] = s.donate_argnums

        struct_classes = self._struct_dataclasses(index)
        for fq, fn in sorted(index.functions.items()):
            cls = (fn.qualname.rsplit(".", 1)[0]
                   if "." in fn.qualname else None)
            for call in ast.walk(fn.node):
                if not isinstance(call, ast.Call):
                    continue
                donated = self._donated_positions(
                    index, fn.module, cls, call, class_bindings,
                    name_bindings)
                if donated:
                    findings += self._check_call(fn, call, donated)
            findings += self._check_alias_construction(
                index, fn, struct_classes)
        return sorted(findings, key=lambda f: (f.path, f.line))

    # -- binding / site matching ---------------------------------------------

    def _struct_dataclasses(self, index: ModuleIndex) -> set[str]:
        """Fully-qualified names of @flax.struct.dataclass classes (the
        donated-pytree universe), plus their bare class names for
        ``cls(...)`` resolution inside their own classmethods."""
        out: set[str] = set()
        for fq, node in index.classes.items():
            mod = fq.rsplit(".", 1)[0]
            for deco in node.decorator_list:
                r = index.resolve(mod, deco) or ""
                if r.endswith("struct.dataclass"):
                    out.add(fq)
        return out

    def _donated_positions(self, index, mod, cls, call,
                           class_bindings, name_bindings):
        f = call.func
        if (isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name)
                and f.value.id == "self" and cls):
            return class_bindings.get((f"{mod}.{cls}", f.attr), ())
        # module-level bindings: resolve the callee to a fully-qualified
        # name — a from-import lands on the binding module, a bare local
        # name lands on the caller's own module
        resolved = index.resolve(mod, f)
        if resolved:
            if "." not in resolved:
                resolved = f"{mod}.{resolved}"
            return name_bindings.get(resolved, ())
        return ()

    # -- rule: read-after-donate + same-call aliasing -------------------------

    def _check_call(self, fn, call: ast.Call,
                    donated: tuple[int, ...]) -> list[Finding]:
        findings: list[Finding] = []
        paths: dict[int, str] = {}
        for pos in donated:
            if pos < len(call.args):
                p = dotted_path(call.args[pos])
                if p:
                    paths[pos] = p
        # aliasing inside the call itself: the donated expression also
        # passed at another position
        all_paths = [dotted_path(a) for a in call.args]
        for pos, p in paths.items():
            for j, other in enumerate(all_paths):
                if j != pos and other == p:
                    findings.append(Finding(
                        "donation-safety", fn.sf.path, call.lineno,
                        f"argument {p!r} is donated (position {pos}) but "
                        f"also passed at position {j}: XLA would alias "
                        "one buffer to both",
                        "pass an independent copy, or drop the donation"))
        if self._returned(fn, call):
            # `return f(x, ...)`: nothing after it runs on this path (an
            # entry that picks one of two donating programs returns
            # from each arm)
            return findings
        end = getattr(call, "end_lineno", call.lineno)
        for pos, p in paths.items():
            if self._rebinds(fn, call, p):
                continue  # `x = f(x, ...)`: the donated name is dead and
                # immediately rebound to the result — the intended idiom
            findings += self._reads_after(fn, p, end, call.lineno)
        return findings

    def _returned(self, fn, call: ast.Call) -> bool:
        """Is the statement holding the donating call a ``return``?"""
        parents = self._parents(fn)
        node: ast.AST = call
        while node in parents and not isinstance(node, ast.stmt):
            node = parents[node]
        return isinstance(node, ast.Return)

    def _rebinds(self, fn, call: ast.Call, path: str) -> bool:
        """Does the statement holding the donating call assign the
        donated path among its own targets?"""
        node = self._assign_of_call(fn).get(id(call))
        if node is None:
            return False
        for t in node.targets:
            targets = (t.elts if isinstance(t, (ast.Tuple, ast.List))
                       else [t])
            if any(dotted_path(x) == path for x in targets):
                return True
        return False

    def _reads_after(self, fn, path: str, after_line: int,
                     call_line: int) -> list[Finding]:
        """Loads of ``path`` after the donating call and before any store
        to it, by source order within the calling function."""
        events: list[tuple[int, str]] = []  # (line, "load"|"store")
        parents = self._parents(fn)
        for node in ast.walk(fn.node):
            if dotted_path(node) != path:
                continue
            par = parents.get(node)
            ctx = getattr(node, "ctx", None)
            if isinstance(ctx, ast.Store):
                events.append((node.lineno, "store"))
            elif isinstance(ctx, ast.Load):
                # a parent Attribute means a LONGER chain rooted here
                # (path.<attr>): .shape/.dtype metadata reads survive
                # donation, anything else consumes the dead buffer
                if (isinstance(par, ast.Attribute)
                        and par.attr in HOST_SAFE_ATTRS):
                    continue
                events.append((node.lineno, "load"))
        findings = []
        for line, kind in sorted(events):
            if line <= after_line:
                continue
            if kind == "store":
                break
            findings.append(Finding(
                "donation-safety", fn.sf.path, line,
                f"{path!r} read after being donated at line {call_line}: "
                "the buffer is dead once the donating jit call starts",
                "rebind the result first (x = f(x, ...)), or read what "
                "you need before the call"))
        return findings

    # -- rule: aliased fields in a struct-dataclass construction -------------

    def _check_alias_construction(self, index, fn,
                                  struct_classes: set[str]) -> list[Finding]:
        findings: list[Finding] = []
        cls = fn.qualname.rsplit(".", 1)[0] if "." in fn.qualname else None
        fresh: set[str] = set()
        for node in ast.walk(fn.node):
            if (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)):
                r = index.resolve(fn.module, node.value.func) or ""
                if r.rsplit(".", 1)[-1] in ARRAY_CREATORS:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            fresh.add(t.id)
        if not fresh:
            return findings
        for node in ast.walk(fn.node):
            if not isinstance(node, ast.Call):
                continue
            target = index.resolve(fn.module, node.func)
            is_struct = target in struct_classes or (
                isinstance(node.func, ast.Name) and node.func.id == "cls"
                and cls and f"{fn.module}.{cls}" in struct_classes)
            if not is_struct:
                continue
            used: dict[str, list[str]] = {}
            for i, a in enumerate(node.args):
                if isinstance(a, ast.Name) and a.id in fresh:
                    used.setdefault(a.id, []).append(f"arg {i}")
            for k in node.keywords:
                if isinstance(k.value, ast.Name) and k.value.id in fresh:
                    used.setdefault(k.value.id, []).append(k.arg or "**")
            for name, slots in used.items():
                if len(slots) > 1:
                    findings.append(Finding(
                        "donation-safety", fn.sf.path, node.lineno,
                        f"array {name!r} aliased across pytree fields "
                        f"({', '.join(slots)}): if this pytree is ever "
                        "donated, one buffer backs them all and they die "
                        "together (the PR-1 ClusterState.zeros bug)",
                        "create one fresh array per field (factory "
                        "function or per-field constructor call)"))
        return findings
