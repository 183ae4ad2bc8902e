"""tenant-axis: the leading T axis must be reduced before per-tenant code.

The PR 11 batched cycle stacks every tenant's state on a leading tenant
axis (``self._stack(states)``), runs ONE vmapped program, and hands
each tenant its own slice back through ``round_adopt_batched``.  Every
output of the batched program carries the T axis; forgetting a
``_unstack`` hands tenant 0's scheduler a (T, N, R) tensor where its
snapshot expects (N, R) — rank drift that surfaces rounds later as a
shape error (or, worse, silently broadcasts one tenant's accounting
over another's).  specflow tracks the tenant axis as a taint:

- **introduced** by ``_stack``/``jnp.stack`` calls and by parameters
  whose ``# koordlint: shape[...]`` annotation declares T-leading dims;
- **propagated** through any call/expression consuming a stacked value
  (the batched jit program's outputs are stacked because its inputs
  are), tuple unpacking included;
- **eliminated** by ``_unstack``/indexing (``x[i]``) — the explicit
  per-tenant slice.

Findings fire when a stacked value reaches a per-tenant sink: the
configured sink names (``round_adopt_batched``), or a SolverKit entry
that carries a per-tenant ``shape`` annotation (dims not T-leading, on
the method's named parameter or a binding's ``argN``) — the kit's
compiled programs are per-tenant contracts,
and feeding them a stacked tensor solves every tenant with tenant 0's
capacity row.  Scoped to the tenancy front-end module(s).
"""

from __future__ import annotations

import ast

from ..callgraph import get_index
from ..core import Analyzer, Finding, Project
from ..specflow.engine import (
    call_tail as _tail,
    parse_shape_body,
    shape_seeds_for,
)

#: call tails that introduce / eliminate the tenant axis
_STACKERS = {"_stack"}
_STACK_FQS = {"jax.numpy.stack", "jnp.stack", "numpy.stack", "np.stack"}
_UNSTACKERS = {"_unstack"}
#: results of these never carry an array axis at all
_SCALAR_FNS = {"len", "int", "float", "bool", "str", "range", "print",
               "enumerate", "zip", "sorted", "list", "dict", "set",
               "tuple", "min", "max", "sum", "isinstance", "getattr",
               "perf_counter", "time"}


class TenantAxisAnalyzer(Analyzer):
    name = "tenant-axis"
    description = ("a leading tenant axis (vmap/stacked pytrees) must "
                   "be _unstack'd before reaching per-tenant sinks "
                   "(round_adopt_batched, annotated kit entries)")

    def __init__(self, package: str = "koordinator_tpu",
                 targets: tuple[str, ...] = (
                     "koordinator_tpu/scheduler/tenancy.py",),
                 sinks: tuple[str, ...] = ("round_adopt_batched",)):
        self.package = package
        self.targets = targets
        self.sinks = set(sinks)

    # -- per-tenant kit contracts from shape annotations ----------------------

    def _kit_contracts(self, index) -> dict[str, set[int]]:
        """``attr -> per-tenant arg positions`` from ``shape``
        annotations whose dims are NOT T-leading: ``argN`` on a
        ``self.<attr> = ...`` jit-binding assign, or a named parameter
        on a method (the SolverKit's one-entry-per-stage methods)."""
        out: dict[str, set[int]] = {}
        for fn in index.functions.values():
            if "." not in fn.qualname:
                continue
            params = [a.arg for a in fn.node.args.args][1:]   # less self
            for name, seed in shape_seeds_for(fn.sf, fn.node).items():
                if (name in params and seed.dims is not None
                        and seed.dims[0] != "T"):
                    out.setdefault(fn.node.name, set()).add(
                        params.index(name))
        for mod, sf in index.modules.items():
            if sf.tree is None or "koordlint: shape" not in sf.text:
                continue
            for node in ast.walk(sf.tree):
                if not (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Attribute)
                        and isinstance(node.targets[0].value, ast.Name)
                        and node.targets[0].value.id == "self"):
                    continue
                d = sf.directive_at(node.lineno, "shape")
                if d is None:
                    continue
                for name, seed in parse_shape_body(d.body).items():
                    if (name.startswith("arg") and name[3:].isdigit()
                            and seed.dims is not None
                            and seed.dims[0] != "T"):
                        out.setdefault(node.targets[0].attr,
                                       set()).add(int(name[3:]))
        return out

    # -- the analysis ---------------------------------------------------------

    def run(self, project: Project) -> list[Finding]:
        index = get_index(project, self.package)
        kit_contracts = self._kit_contracts(index)
        findings: list[Finding] = []
        for mod, sf in sorted(index.modules.items()):
            if sf.path not in self.targets or sf.tree is None:
                continue
            for fq, fn in sorted(index.functions.items()):
                if fn.sf is sf:
                    findings.extend(self._scan(index, fn, kit_contracts))
        dedup: dict[tuple, Finding] = {}
        for f in findings:
            dedup.setdefault((f.path, f.line, f.message), f)
        return sorted(dedup.values(), key=lambda f: (f.path, f.line))

    def _scan(self, index, fn, kit_contracts) -> list[Finding]:
        findings: list[Finding] = []
        stacked: set[str] = set()

        def is_stacked(node: ast.expr) -> bool:
            """Does this expression carry the leading tenant axis?"""
            if isinstance(node, ast.Name):
                return node.id in stacked
            if isinstance(node, ast.Subscript):
                return False                  # x[i] slices the T axis off
            if isinstance(node, ast.IfExp):
                return is_stacked(node.body) or is_stacked(node.orelse)
            if isinstance(node, ast.Attribute):
                return is_stacked(node.value)
            if isinstance(node, ast.Call):
                tail = _tail(node.func)
                if tail in _UNSTACKERS:
                    return False
                if tail in _STACKERS or (
                        index.resolve(fn.module, node.func)
                        in _STACK_FQS):
                    return True
                if tail in _SCALAR_FNS:
                    return False
                return any(is_stacked(a) for a in node.args) or any(
                    is_stacked(k.value) for k in node.keywords)
            if isinstance(node, (ast.Tuple, ast.List)):
                return any(is_stacked(e) for e in node.elts)
            return False

        # seeds: parameters annotated with T-leading dims
        for name, seed in shape_seeds_for(fn.sf, fn.node).items():
            if seed.dims is not None and seed.dims and seed.dims[0] == "T":
                stacked.add(name)

        statements: list[ast.stmt] = []
        for node in ast.walk(fn.node):
            if isinstance(node, ast.stmt):
                statements.append(node)
        statements.sort(key=lambda s: (s.lineno, 0))

        # each call is checked only at its INNERMOST enclosing statement
        # so taint updates inside a compound statement's body land
        # before the sink calls that follow them in source order
        parents = {c: p for p in ast.walk(fn.node)
                   for c in ast.iter_child_nodes(p)}
        own_calls: dict[int, list[ast.Call]] = {}
        for node in ast.walk(fn.node):
            if not isinstance(node, ast.Call):
                continue
            holder: ast.AST = node
            while holder in parents and not isinstance(holder, ast.stmt):
                holder = parents[holder]
            own_calls.setdefault(id(holder), []).append(node)

        for stmt in statements:
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
                target = stmt.targets[0]
                hit = is_stacked(stmt.value)
                targets = (target.elts
                           if isinstance(target, (ast.Tuple, ast.List))
                           else [target])
                for t in targets:
                    if isinstance(t, ast.Name):
                        (stacked.add if hit else
                         stacked.discard)(t.id)
            for call in own_calls.get(id(stmt), []):
                tail = _tail(call.func)
                if tail in self.sinks:
                    for i, arg in enumerate(call.args):
                        if is_stacked(arg):
                            findings.append(Finding(
                                self.name, fn.sf.path, call.lineno,
                                f"argument {i} of per-tenant sink "
                                f"{tail}() still carries the leading "
                                "tenant axis: rank drift across the "
                                "batched cycle (the adopting scheduler "
                                "expects one tenant's slice)",
                                hint="slice the tenant first "
                                     "(self._unstack(x, i) / x[i])"))
                elif tail in kit_contracts:
                    for i in kit_contracts[tail]:
                        if i < len(call.args) and is_stacked(
                                call.args[i]):
                            findings.append(Finding(
                                self.name, fn.sf.path, call.lineno,
                                f"argument {i} of kit entry {tail}() "
                                "is tenant-stacked but the binding's "
                                "shape annotation declares a "
                                "per-tenant contract: one compiled "
                                "program would solve every tenant "
                                "with tenant 0's shapes",
                                hint="unstack per tenant, or use the "
                                     "tenant-axis batched program "
                                     "(_batched_fn) that declares the "
                                     "T axis"))
        return findings
