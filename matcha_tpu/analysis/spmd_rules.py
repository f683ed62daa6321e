"""graftverify SPMD-safety rules — GL101–GL104, the interprocedural family.

Each rule sits on the :mod:`dataflow` layer (module call graph, function
summaries, constant folding) and encodes an invariant no per-worker unit
test can see, because breaking it is only visible *between* workers:

========  ==================================================================
GL101     ``ppermute`` permutation tables must be permutations (statically
          evaluated where foldable, parametrically under ``bind`` hints; a
          one-sided send is silent corruption on ICI)
GL102     collectives under worker-divergent python control flow (the SPMD
          deadlock class: one worker enters the collective, its partner
          compiled a program that never issues it)
GL103     wire-dtype lattice: a tensor narrows through the wire exactly
          once per exchange (double quantization re-rounds someone else's
          rounding; a raw exchange next to a wire image bypasses the seam)
GL104     static retrace prediction: python branches on a traced argument's
          shape inside a compiled root — the static twin of the PR-5
          dynamic retrace guard
========  ==================================================================

Like the GL0xx family, the rules over-approximate on purpose: a flagged
site is either fixed, given a ``# graftverify: bind`` hint that lets the
analyzer verify it, or suppressed inline with a reason.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .dataflow import (
    COLLECTIVE_NAMES,
    DIVERGENT_CALLS,
    ModuleGraph,
    module_graph,
    NotFoldable,
    const_eval,
    dotted_name,
    expand_bindings,
    free_names,
    parse_bind_hints,
    static_params,
)
from .engine import LintSource, Rule, Violation

__all__ = ["SPMD_RULES"]

_NARROW_ATTRS = {
    "bfloat16", "float16", "half", "int8", "uint8",
    "float8_e4m3fn", "float8_e5m2", "float8_e4m3", "float8_e5m2fnuz",
}
_WIRE_SCOPE = ("matcha_tpu/parallel/", "matcha_tpu/communicator/")


def _in_wire_scope(source: LintSource) -> bool:
    return any(source.path.startswith(s) or f"/{s}" in source.path
               for s in _WIRE_SCOPE)


# =========================================================================
# GL101 — ppermute permutation-table verification
# =========================================================================

def _perm_arg(call: ast.Call) -> Optional[ast.AST]:
    """The ``perm`` argument of ``lax.ppermute(x, axis_name, perm)``."""
    for kw in call.keywords:
        if kw.arg == "perm":
            return kw.value
    if len(call.args) >= 3:
        return call.args[2]
    return None


def _involution_arg(call: ast.Call) -> Optional[ast.AST]:
    """The ``perms`` argument of ``gossip_mix(x, perms, weights, ...)`` /
    ``gossip_mix_skip`` — the static involution table stack the exchange's
    row gathers execute."""
    for kw in call.keywords:
        if kw.arg == "perms":
            return kw.value
    if len(call.args) >= 2:
        return call.args[1]
    return None


def _check_pairs(pairs) -> Optional[str]:
    """None if ``pairs`` is a valid (source, dest) permutation; else why not.

    Validity: every entry a distinct-source, distinct-dest int pair, and the
    sender set equals the receiver set — a device that sends but never
    receives (or vice versa) leaves someone's block silently zeroed, the
    one-sided-``sendrecv`` corruption class MPI would at least hang on.
    """
    try:
        entries = [(int(s), int(d)) for (s, d) in list(pairs)]
    except (TypeError, ValueError):
        return "does not evaluate to a list of (source, dest) int pairs"
    if not entries:
        return ("empty table — ppermute zeroes every receiver not named in "
                "perm, so an empty table replaces the whole block with zeros")
    srcs = [s for s, _ in entries]
    dsts = [d for _, d in entries]
    if len(set(srcs)) != len(srcs):
        return "a source index sends twice (duplicate source)"
    if len(set(dsts)) != len(dsts):
        return "a dest index receives twice (duplicate dest)"
    if set(srcs) != set(dsts):
        lonely = sorted(set(srcs) ^ set(dsts))
        return (f"one-sided: sender/receiver sets differ at {lonely} — the "
                f"unpaired side's block is silently zeroed")
    if any(s < 0 for s in srcs) or any(d < 0 for d in dsts):
        return "negative device index"
    return None


def _check_involutions(tables) -> Optional[str]:
    """None if ``tables`` is a valid ``[M, N]`` total-involution stack;
    else why not.

    Validity per row: every entry an in-range int and ``π[π[i]] == i`` for
    all i — a matching pairs slots symmetrically (fixed points map to
    self).  A non-involution gather does not error on the chip any more
    than a one-sided ppermute errors on ICI: the asymmetric row silently
    double- or zero-weights someone's state, the same corruption class.
    """
    try:
        rows = [[int(v) for v in row] for row in list(tables)]
    except (TypeError, ValueError):
        return "does not evaluate to a list of integer index rows"
    if not rows:
        return ("empty table stack — zero matchings compiles an identity "
                "kernel; build no kernel instead")
    n = len(rows[0])
    for j, row in enumerate(rows):
        if len(row) != n:
            return f"row {j} has length {len(row)} != {n} (ragged stack)"
        if n == 0:
            return f"row {j} is empty"
        if any(v < 0 or v >= n for v in row):
            bad = next(v for v in row if v < 0 or v >= n)
            return f"row {j}: partner index {bad} out of range [0, {n})"
        for i, v in enumerate(row):
            if row[v] != i:
                return (f"row {j} is not an involution: π(π({i})) = "
                        f"{row[v]} != {i} — the matching is one-sided")
    return None


class GL101PermutationTables(Rule):
    id = "GL101"
    title = "permutation/involution table unverified or invalid"
    invariant = (
        "Every lax.ppermute perm table must be a permutation (pairwise "
        "distinct sources, pairwise distinct dests, senders == receivers) "
        "and every gossip_mix / gossip_mix_skip involution stack must be "
        "total involutions (π∘π = id, in-range).  Neither errors at runtime — a "
        "one-sided ppermute entry zeroes the unmatched receiver's block on "
        "ICI, a non-involution gather double-weights someone's rows "
        "— and gossip silently averages against garbage either way.  "
        "Tables are verified by constant-folding the building expression; "
        "tables closing over runtime values carry a `# graftverify: bind "
        "NAME=lo..hi` hint and are verified for every binding in the "
        "hint's cross product; schedule-built involution stacks route "
        "through the `involution_tables` validator seam (the runtime half "
        "of the proof).  Genuinely dynamic tables suppress with a review "
        "reason."
    )

    #: what the row-gather exchanges' ``perms`` are held to
    _INVOLUTION_SITE = (_involution_arg, _check_involutions,
                        "involution table stack",
                        "is not a valid involution stack")
    #: call leaf name -> (table-arg extractor, folded-value checker,
    #: table label, failure phrase)
    _TABLE_SITES = {
        "ppermute": (_perm_arg, _check_pairs, "perm table",
                     "is not a permutation"),
        "gossip_mix": _INVOLUTION_SITE,
        "gossip_mix_skip": _INVOLUTION_SITE,
    }
    #: sanctioned runtime validator for involution stacks: a table bound
    #: from this call is checked at build time (raises on non-involution),
    #: so the static rule accepts the seam instead of demanding a fold
    _VALIDATOR = "involution_tables"

    def check(self, source: LintSource) -> List[Violation]:
        graph = module_graph(source)
        hints = parse_bind_hints(source.lines)
        out: List[Violation] = []
        for node in ast.walk(source.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = dotted_name(node.func)
            leaf = fn.split(".")[-1] if fn else None
            site = self._TABLE_SITES.get(leaf)
            if site is None:
                continue
            extract, checker, label, bad = site
            table = extract(node)
            if table is None:
                out.append(self.hit(
                    source, node, f"{leaf} call without a {label}"))
                continue
            out.extend(self._verify(source, graph, hints, node, table,
                                    checker, label, bad,
                                    seam=(site is self._INVOLUTION_SITE)))
        return out

    def _is_validator_call(self, expr: ast.AST) -> bool:
        if isinstance(expr, ast.Call):
            fn = dotted_name(expr.func)
            return fn is not None and fn.split(".")[-1] == self._VALIDATOR
        if isinstance(expr, ast.Subscript):  # involution_tables(p)[0]
            return self._is_validator_call(expr.value)
        return False

    def _routed_through_validator(self, graph: ModuleGraph, call: ast.Call,
                                  name: str) -> bool:
        """True when ``name`` is bound exactly once in the *outermost*
        enclosing scope, from an ``involution_tables(...)`` call (plain or
        tuple-unpacked: ``pi, pr = involution_tables(perms)``), and never
        mutated.  Outermost, not innermost: the exchange call typically
        sits inside a closure (``mix``) while the tables are built once in
        the backend factory around it; the single-binding + no-mutation
        requirement keeps the widened search conservative."""
        search: ast.AST = graph.source.tree
        line = getattr(call, "lineno", None)
        outer_lo = None
        for fn_nodes in graph.functions.values():
            for fn in fn_nodes:
                lo = getattr(fn, "lineno", None)
                hi = getattr(fn, "end_lineno", None)
                if lo is None or hi is None or line is None:
                    continue
                if lo <= line <= hi and (outer_lo is None or lo < outer_lo):
                    outer_lo, search = lo, fn
        bindings: List[ast.AST] = []
        for n in ast.walk(search):
            if isinstance(n, ast.Assign):
                for t in n.targets:
                    names = [e.id for e in ast.walk(t)
                             if isinstance(e, ast.Name)]
                    if name in names:
                        bindings.append(n.value)
            elif isinstance(n, ast.AugAssign) \
                    and isinstance(n.target, ast.Name) \
                    and n.target.id == name:
                return False
            elif isinstance(n, ast.Call) \
                    and isinstance(n.func, ast.Attribute) \
                    and n.func.attr in self._MUTATORS \
                    and isinstance(n.func.value, ast.Name) \
                    and n.func.value.id == name:
                return False
        return len(bindings) == 1 and self._is_validator_call(bindings[0])

    def _verify(self, source: LintSource, graph: ModuleGraph,
                hints: Dict[int, Dict[str, List[int]]],
                call: ast.Call, perm: ast.AST, checker, label: str,
                bad: str, seam: bool = False) -> List[Violation]:
        if seam and self._is_validator_call(perm):
            return []  # table built inline through the validator seam
        binds: Dict[str, List[int]] = dict(hints.get(call.lineno, {}))
        expr = perm
        if isinstance(perm, ast.Name):
            if seam and self._routed_through_validator(graph, call, perm.id):
                return []  # runtime-validated: involution_tables raises
            assign = self._single_assignment(graph, call, perm.id)
            if assign is not None:
                expr = assign.value
                binds.update(hints.get(assign.lineno, {}))
            else:
                fix = (f"route it through {self._VALIDATOR}(...) "
                       f"(runtime-validated seam), build it in one "
                       f"expression (with a bind hint if it closes over "
                       f"runtime values)" if seam else
                       "build the table in one expression (with a bind "
                       "hint if it closes over runtime values)")
                return [self.hit(
                    source, call,
                    f"{label} `{perm.id}` has no unique unmutated local "
                    f"assignment — not statically verifiable; {fix}, or "
                    f"suppress with a review reason")]
        missing = sorted(free_names(expr) - set(binds))
        if missing:
            return [self.hit(
                source, call,
                f"{label} depends on runtime value(s) {missing} — add "
                f"`# graftverify: bind {missing[0]}=lo..hi` (all free "
                f"symbols) so the table can be verified parametrically"
                + (f", route it through {self._VALIDATOR}(...)" if seam
                   else "")
                + ", or suppress with a review reason")]
        combos = expand_bindings(binds)
        if not combos:
            # a reversed range (`C=8..1`) or malformed value list expands to
            # nothing — looping over zero bindings would "verify" the table
            # vacuously, the exact silent pass the rule must never produce
            return [self.hit(
                source, call,
                f"bind hint for {sorted(binds)} expands to zero bindings — "
                f"nothing was verified; check the hint's ranges/values")]
        for binding in combos:
            try:
                tables = const_eval(expr, dict(binding))
            except NotFoldable as e:
                return [self.hit(
                    source, call,
                    f"{label} is outside the statically-evaluable subset "
                    f"({e}) — simplify the building expression or suppress "
                    f"with a review reason")]
            except ZeroDivisionError:
                return [self.hit(
                    source, call,
                    f"{label} evaluation divides by zero under binding "
                    f"{binding} — exclude 0 from the bind hint ranges")]
            except Exception as e:  # a broken expression/hint must report,
                # never abort the whole lint run (review finding, ISSUE 6)
                return [self.hit(
                    source, call,
                    f"{label} evaluation raised "
                    f"{type(e).__name__}: {e} under binding {binding} — "
                    f"fix the expression or the hint ranges")]
            why = checker(tables)
            if why is not None:
                where = f" under binding {binding}" if binding else ""
                return [self.hit(
                    source, call,
                    f"{label} {bad}{where}: {why}")]
        return []

    _MUTATORS = {"append", "extend", "insert", "remove", "pop", "clear",
                 "sort", "reverse", "setdefault", "update"}

    @staticmethod
    def _single_assignment(graph: ModuleGraph, call: ast.Call,
                           name: str) -> Optional[ast.Assign]:
        """The one assignment that defines ``name`` — or None when it is
        reassigned, augmented (`+=`), item-assigned, or mutated through a
        method (`pairs.append(...)`): folding the seed expression of a
        later-mutated table would 'verify' a value the ppermute never
        sees (review finding, ISSUE 6)."""
        scope = graph.enclosing_function(call)
        search = scope if scope is not None else graph.source.tree
        assigns: List[ast.Assign] = []
        for n in ast.walk(search):
            if isinstance(n, ast.Assign):
                for t in n.targets:
                    if isinstance(t, ast.Name) and t.id == name:
                        assigns.append(n)
                    elif isinstance(t, (ast.Subscript, ast.Tuple)) \
                            and any(isinstance(e, ast.Name) and e.id == name
                                    for e in ast.walk(t)):
                        return None  # pairs[i] = … / tuple-target rebind
            elif isinstance(n, ast.AugAssign) \
                    and isinstance(n.target, ast.Name) \
                    and n.target.id == name:
                return None  # pairs += …
            elif isinstance(n, ast.Call) \
                    and isinstance(n.func, ast.Attribute) \
                    and n.func.attr in GL101PermutationTables._MUTATORS \
                    and isinstance(n.func.value, ast.Name) \
                    and n.func.value.id == name:
                return None  # pairs.append(…) etc.
        return assigns[0] if len(assigns) == 1 else None


# =========================================================================
# GL102 — collectives under worker-divergent python control flow
# =========================================================================

class GL102DivergentCollectives(Rule):
    id = "GL102"
    title = "collective under worker-divergent python control flow"
    invariant = (
        "SPMD correctness is lockstep: every worker's compiled program "
        "issues the same collectives in the same order.  A python "
        "`if`/`while` conditioned on axis_index/process_index forks the "
        "*program*, not the data — the worker that skips the branch "
        "compiled a program with no matching ppermute/psum, and its "
        "partners deadlock (or worse, pair with the wrong collective).  "
        "Divergent data is fine (masks, jnp.where, weighted edges); "
        "divergent *program structure* is the bug.  Reachability is "
        "interprocedural: calling a helper that gossips, from inside a "
        "divergent branch, is the same deadlock."
    )

    def check(self, source: LintSource) -> List[Violation]:
        graph = module_graph(source)
        out: List[Violation] = []
        seen_fns: Set[int] = set()
        reported: Set[int] = set()
        for root, fn_node in graph.compiled_functions_cached():
            if id(fn_node) in seen_fns:
                continue
            seen_fns.add(id(fn_node))
            self._scan_function(source, graph, fn_node, root, out, reported)
        return out

    def _scan_function(self, source: LintSource, graph: ModuleGraph,
                       fn_node: ast.AST, root: str,
                       out: List[Violation], reported: Set[int]) -> None:
        summ = graph.summary(fn_node)
        div_names = set(summ.divergent_names)

        def expr_divergent(expr: ast.AST) -> bool:
            for n in ast.walk(expr):
                if isinstance(n, ast.Name) and n.id in div_names:
                    return True
                if isinstance(n, ast.Call):
                    f = dotted_name(n.func)
                    if f and f.split(".")[-1] in DIVERGENT_CALLS:
                        return True
            return False

        def flag_collectives(stmt: ast.AST) -> None:
            for n in ast.walk(stmt):
                if not isinstance(n, ast.Call) or id(n) in reported:
                    continue
                f = dotted_name(n.func)
                if f is None:
                    continue
                leaf = f.split(".")[-1]
                if leaf in COLLECTIVE_NAMES:
                    reported.add(id(n))
                    out.append(self.hit(
                        source, n,
                        f"`{f}` executes under worker-divergent python "
                        f"control flow [compiled via `{root}`] — the SPMD "
                        f"deadlock class: gate data with jnp.where/masks, "
                        f"never the collective itself"))
                else:
                    for defn in graph.resolve(f):
                        if defn is not fn_node \
                                and graph.issues_collective(defn):
                            reported.add(id(n))
                            out.append(self.hit(
                                source, n,
                                f"`{f}` (transitively issues collectives) "
                                f"called under worker-divergent python "
                                f"control flow [compiled via `{root}`]"))
                            break

        def visit(stmts: List[ast.stmt], divergent: bool) -> None:
            for st in stmts:
                if isinstance(st, (ast.If, ast.While)):
                    d = divergent or expr_divergent(st.test)
                    visit(st.body, d)
                    visit(st.orelse, d)
                elif isinstance(st, ast.For):
                    d = divergent or expr_divergent(st.iter)
                    visit(st.body, d)
                    visit(st.orelse, d)
                elif isinstance(st, (ast.With, ast.AsyncWith)):
                    visit(st.body, divergent)
                elif isinstance(st, ast.Try):
                    visit(st.body, divergent)
                    for h in st.handlers:
                        visit(h.body, divergent)
                    visit(st.orelse, divergent)
                    visit(st.finalbody, divergent)
                elif isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    visit(st.body, divergent)  # lexical: a def inside the
                    # branch only runs there
                else:
                    if divergent:
                        flag_collectives(st)

        body = getattr(fn_node, "body", None)
        if isinstance(body, list):
            visit(body, False)


# =========================================================================
# GL103 — wire-dtype lattice: quantize exactly once per exchange
# =========================================================================

def _is_wire_dtype_arg(arg: ast.AST, wire_names: Set[str]) -> bool:
    if isinstance(arg, ast.Name) and arg.id in wire_names:
        return True
    if isinstance(arg, ast.Attribute) and arg.attr in _NARROW_ATTRS:
        return True
    return False


class GL103WireLattice(Rule):
    id = "GL103"
    title = "wire narrowing applied zero or two times across an exchange"
    invariant = (
        "PR 4's mean-preservation proof needs each exchanged tensor "
        "quantized to the wire dtype *exactly once*: quantize-before-"
        "exchange, form the delta from the quantized image on both "
        "endpoints.  Quantizing twice re-rounds an already-rounded value "
        "(the second rounding differs between sender and receiver and "
        "edge-pairwise cancellation dies); exchanging the raw tensor while "
        "a wire image exists ships f32 bytes the wire knob claims were "
        "halved.  The lattice tracks `resolve_wire_dtype` results through "
        "astype/ppermute/copies per function, and across a Communicator's "
        "begin_mix/apply_mix pair via summaries."
    )

    def check(self, source: LintSource) -> List[Violation]:
        if not _in_wire_scope(source):
            return []
        wire_names = self._wire_names(source.tree)
        out: List[Violation] = []
        for node in ast.walk(source.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._scan_function(source, node, wire_names, out)
        self._scan_two_phase(source, wire_names, out)
        return out

    @staticmethod
    def _wire_names(tree: ast.AST) -> Set[str]:
        """Names anywhere in the file bound from ``resolve_wire_dtype`` —
        closures hand them down, so the set is file-scoped."""
        names: Set[str] = set()
        for n in ast.walk(tree):
            if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call):
                fn = dotted_name(n.value.func)
                if fn and fn.split(".")[-1] == "resolve_wire_dtype":
                    for t in n.targets:
                        if isinstance(t, ast.Name):
                            names.add(t.id)
        return names

    def _scan_function(self, source: LintSource, fn_node: ast.AST,
                       wire_names: Set[str], out: List[Violation]) -> None:
        # quantized: var name -> origin name (the raw tensor it images)
        def origin_of(name: str, q: Dict[str, str]) -> str:
            return q.get(name, name)

        def expr_state(e: ast.AST, q: Dict[str, str]) -> Optional[str]:
            """Origin name if ``e`` evaluates to a wire-quantized image."""
            if isinstance(e, ast.Name):
                return q.get(e.id)
            if isinstance(e, ast.IfExp):
                return expr_state(e.body, q) or expr_state(e.orelse, q)
            if isinstance(e, ast.Call):
                f = e.func
                if isinstance(f, ast.Attribute) and f.attr == "astype" \
                        and e.args:
                    wire_cast = _is_wire_dtype_arg(e.args[0], wire_names)
                    inner = expr_state(f.value, q)
                    if wire_cast:
                        if inner is not None:
                            out.append(self.hit(
                                source, e,
                                f"wire-quantizing an already-quantized "
                                f"image of `{inner}` — the second rounding "
                                f"breaks edge-pairwise cancellation "
                                f"(quantize exactly once per exchange)"))
                            return inner
                        if isinstance(f.value, ast.Name):
                            return f.value.id
                        return expr_state(f.value, q)
                    return inner  # back-cast keeps the rounded values
                fname = dotted_name(f)
                if fname and fname.split(".")[-1] == "ppermute" and e.args:
                    op = e.args[0]
                    st = expr_state(op, q)
                    if st is None and isinstance(op, ast.Name):
                        # raw operand: does a wire image of it exist?
                        if op.id in set(q.values()):
                            out.append(self.hit(
                                source, e,
                                f"ppermute moves raw `{op.id}` while its "
                                f"wire image exists — the exchange bypasses "
                                f"the quantization seam (full-width bytes "
                                f"on a wire the knob claims is narrowed)"))
                    return st
            return None

        def visit(stmts: List[ast.stmt], q: Dict[str, str]) -> None:
            for st in stmts:
                if isinstance(st, ast.Assign):
                    state = expr_state(st.value, q)  # also runs the checks
                    if len(st.targets) == 1 \
                            and isinstance(st.targets[0], ast.Name):
                        if state is not None:
                            q[st.targets[0].id] = state
                        else:
                            q.pop(st.targets[0].id, None)
                elif isinstance(st, (ast.If,)):
                    qa, qb = dict(q), dict(q)
                    expr_state(st.test, q)
                    visit(st.body, qa)
                    visit(st.orelse, qb)
                    # join: keep images both paths agree on, plus the
                    # pre-branch ones (sibling branches stay independent)
                    for k in list(q):
                        if qa.get(k) != q[k] and qb.get(k) != q[k]:
                            q.pop(k, None)
                elif isinstance(st, (ast.For, ast.While)):
                    visit(st.body, q)
                    visit(st.orelse, q)
                elif isinstance(st, (ast.With, ast.AsyncWith)):
                    visit(st.body, q)
                elif isinstance(st, ast.Try):
                    visit(st.body, q)
                    for h in st.handlers:
                        visit(h.body, dict(q))
                elif isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    visit(st.body, dict(q))
                else:
                    for n in ast.walk(st):
                        if isinstance(n, (ast.expr,)):
                            expr_state(n, q)
                            break  # expr_state recurses itself

        body = getattr(fn_node, "body", None)
        if isinstance(body, list):
            visit(body, {})

    def _scan_two_phase(self, source: LintSource, wire_names: Set[str],
                        out: List[Violation]) -> None:
        """Cross-phase summary check: a Communicator overriding both phases
        must quantize in at most one of them."""
        def quantizes(fn_node: ast.AST) -> bool:
            for n in ast.walk(fn_node):
                if isinstance(n, ast.Call) \
                        and isinstance(n.func, ast.Attribute) \
                        and n.func.attr == "astype" and n.args \
                        and _is_wire_dtype_arg(n.args[0], wire_names):
                    return True
            return False

        for node in ast.walk(source.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            phases = {
                n.name: n for n in node.body
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                and n.name in ("begin_mix", "apply_mix")
            }
            if len(phases) == 2 and all(quantizes(f)
                                        for f in phases.values()):
                out.append(self.hit(
                    source, phases["apply_mix"],
                    f"`{node.name}` quantizes the wire in both begin_mix "
                    f"and apply_mix — the exchanged tensor narrows twice "
                    f"per exchange (quantize at issue, apply is a pure "
                    f"add)"))


# =========================================================================
# GL104 — static retrace prediction
# =========================================================================

class GL104StaticRetrace(Rule):
    id = "GL104"
    title = "python branch on a traced argument's shape in a compiled root"
    invariant = (
        "The repo's compile-time contract (DESIGN.md §1) is one program: "
        "shapes are static, flags are trace-time constants.  A python "
        "`if`/`while` on a traced argument's shape/len inside a jit or "
        "shard_map root declares the opposite — the author expects shapes "
        "to vary, and every distinct shape silently compiles a fresh "
        "program (the throughput death the PR-5 dynamic retrace guard "
        "catches at runtime; this is its static twin).  Parameters pinned "
        "by static_argnames/static_argnums are exempt: recompiling per "
        "value there is declared behavior.  Shape *uses* (reshape, "
        "indexing, unrolled loops) stay legal — only branching program "
        "structure on shapes is flagged."
    )

    def check(self, source: LintSource) -> List[Violation]:
        graph = module_graph(source)
        out: List[Violation] = []
        reported: Set[int] = set()
        for root, fn_node in graph.roots:
            params = self._dynamic_params(fn_node)
            self._scan(source, graph, fn_node, root, params, out, reported,
                       depth=0, visited=set())
        return out

    @staticmethod
    def _dynamic_params(fn_node: ast.AST) -> Set[str]:
        args = getattr(fn_node, "args", None)
        if args is None:
            return set()
        names = {a.arg for a in args.posonlyargs + args.args
                 + args.kwonlyargs}
        return names - static_params(fn_node) - {"self"}

    def _scan(self, source: LintSource, graph: ModuleGraph,
              fn_node: ast.AST, root: str, traced: Set[str],
              out: List[Violation], reported: Set[int],
              depth: int, visited: Set[Tuple[int, frozenset]]) -> None:
        key = (id(fn_node), frozenset(traced))
        if depth > 8 or key in visited or not traced:
            return
        visited.add(key)

        def shape_read(expr: ast.AST) -> Optional[str]:
            for n in ast.walk(expr):
                if isinstance(n, ast.Attribute) \
                        and n.attr in ("shape", "ndim", "size") \
                        and isinstance(n.value, ast.Name) \
                        and n.value.id in traced:
                    return f"{n.value.id}.{n.attr}"
                if isinstance(n, ast.Call) \
                        and isinstance(n.func, ast.Name) \
                        and n.func.id == "len" and n.args \
                        and isinstance(n.args[0], ast.Name) \
                        and n.args[0].id in traced:
                    return f"len({n.args[0].id})"
            return None

        def is_validation_guard(n: ast.AST) -> bool:
            # `if x.shape != expected: raise ...` is the loud-failure idiom
            # (static validation), not shape polymorphism — the program
            # never forks, it refuses
            body = getattr(n, "body", [])
            orelse = getattr(n, "orelse", [])
            return not orelse and bool(body) \
                and all(isinstance(s, ast.Raise) for s in body)

        for n in ast.walk(fn_node):
            if isinstance(n, (ast.If, ast.While)) and id(n) not in reported:
                if is_validation_guard(n):
                    continue
                read = shape_read(n.test)
                if read is not None:
                    reported.add(id(n))
                    out.append(self.hit(
                        source, n,
                        f"python branch on `{read}` inside compiled "
                        f"`{root}` — every distinct shape of the traced "
                        f"argument compiles a fresh program; hoist the "
                        f"branch out of the root, pad to a static shape, "
                        f"or pin the argument with static_argnames"))
            elif isinstance(n, ast.Call):
                fn = dotted_name(n.func)
                if fn is None:
                    continue
                for defn in graph.resolve(fn):
                    if defn is fn_node:
                        continue
                    callee_traced = self._map_args(defn, n, traced)
                    if callee_traced:
                        self._scan(source, graph, defn, root, callee_traced,
                                   out, reported, depth + 1, visited)

    @staticmethod
    def _map_args(defn: ast.AST, call: ast.Call,
                  traced: Set[str]) -> Set[str]:
        """Callee parameters receiving a traced argument at this site."""
        args = getattr(defn, "args", None)
        if args is None:
            return set()
        names = [a.arg for a in args.posonlyargs + args.args]
        mapped: Set[str] = set()
        for i, a in enumerate(call.args):
            if isinstance(a, ast.Name) and a.id in traced and i < len(names):
                mapped.add(names[i])
        for kw in call.keywords:
            if kw.arg and isinstance(kw.value, ast.Name) \
                    and kw.value.id in traced and kw.arg in names:
                mapped.add(kw.arg)
        return mapped


SPMD_RULES: Tuple[Rule, ...] = (
    GL101PermutationTables(),
    GL102DivergentCollectives(),
    GL103WireLattice(),
    GL104StaticRetrace(),
)
