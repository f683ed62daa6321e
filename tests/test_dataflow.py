"""graftverify tests (ISSUE 6): the interprocedural dataflow layer, the
GL101–GL104 SPMD-safety rules, and planlint.

Mirrors the ISSUE-5 test structure in ``test_analysis.py``:

* **Constant-folding unit suite** — the ``const_eval`` mini-interpreter
  that verifies perm-table expressions, plus ``bind`` hint parsing.
* **Per-rule fixtures** — every GL1xx rule fires on a synthetic violation,
  stays silent on the compliant twin, and honors inline suppression.
* **The real tree is clean** — covered by ``test_analysis.py``'s
  ``test_shipped_tree_is_clean`` (ALL_RULES now includes GL1xx).
* **planlint** — every committed plan artifact verifies numerically, and a
  tampered artifact is caught by the check that owns the invariant.

Marker: ``analysis`` — run standalone with ``pytest -m analysis``.
"""

import copy
import json
import pathlib
import textwrap

import numpy as np
import pytest

from matcha_tpu.analysis import (
    ALL_RULES,
    PLAN_CHECKS,
    discover_plan_files,
    lint_plan_data,
    lint_plan_paths,
    lint_source,
    rules_by_id,
)
from matcha_tpu.analysis.dataflow import (
    ModuleGraph,
    NotFoldable,
    const_eval,
    expand_bindings,
    free_names,
    parse_bind_hints,
)
from matcha_tpu.analysis.engine import load_source

pytestmark = pytest.mark.analysis

REPO = pathlib.Path(__file__).resolve().parents[1]
SPMD = ["GL101", "GL102", "GL103", "GL104"]


def _lint(tmp_path, code, rules=None, filename="snippet.py"):
    f = tmp_path / filename
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(code))
    return lint_source(load_source(f, REPO), rules or rules_by_id(SPMD))


def _ids(violations):
    return sorted({v.rule for v in violations})


def _expr(code):
    import ast

    return ast.parse(code, mode="eval").body


# ============================================================ const folding

def test_const_eval_arithmetic_and_modulo():
    assert const_eval(_expr("(3 + 4) % 5 * 2")) == 4
    assert const_eval(_expr("C // 2 + C % 3"), {"C": 7}) == 4
    assert const_eval(_expr("-x ** 2"), {"x": 3}) == -9


def test_const_eval_ring_table():
    """The exact expression shape gossip_mix_folded builds its ppermute
    tables from — the thing GL101 folds."""
    expr = _expr("[((cc + d) % C, cc) for cc in range(C)]")
    assert const_eval(expr, {"C": 4, "d": 1}) == [(1, 0), (2, 1), (3, 2), (0, 3)]
    # offsets beyond C wrap through the modulus: still a permutation
    assert const_eval(expr, {"C": 2, "d": 7}) == [(1, 0), (0, 1)]


def test_const_eval_dotted_attribute_env():
    expr = _expr("[((cc + part.offset) % C, cc) for cc in range(C)]")
    pairs = const_eval(expr, {"C": 3, "part.offset": 2})
    assert pairs == [(2, 0), (0, 1), (1, 2)]


def test_const_eval_comprehension_machinery():
    assert const_eval(_expr("[i * j for i in range(3) for j in range(2) if j]")) \
        == [0, 1, 2]  # j only ever 1: the identity row of the product
    assert const_eval(_expr("[i for i in range(6) if i % 2]")) == [1, 3, 5]
    assert const_eval(_expr("[(a, b) for (a, b) in zip(range(2), range(2))]")) \
        == [(0, 0), (1, 1)]
    assert const_eval(_expr("sorted({5, 1, 3})")) == [1, 3, 5]
    assert const_eval(_expr("[x for _, x in enumerate(range(3))]")) == [0, 1, 2]


def test_const_eval_subscript_slice_ifexp():
    assert const_eval(_expr("[10, 20, 30][1]")) == 20
    assert const_eval(_expr("[10, 20, 30][1:]")) == [20, 30]
    assert const_eval(_expr("1 if C > 2 else 0"), {"C": 3}) == 1


def test_const_eval_not_foldable():
    with pytest.raises(NotFoldable, match="unbound name"):
        const_eval(_expr("C + 1"))
    with pytest.raises(NotFoldable, match="unbound attribute"):
        const_eval(_expr("plan.num_chips"))
    with pytest.raises(NotFoldable, match="call"):
        const_eval(_expr("np.arange(4)"))
    with pytest.raises(NotFoldable, match="call"):
        const_eval(_expr("x.tolist()"), {"x": 1})
    with pytest.raises(NotFoldable, match="budget"):
        const_eval(_expr("[i * j for i in range(100000) for j in range(100000)]"))


def test_free_names_dotted_and_bound():
    expr = _expr("[((cc + part.offset) % C, cc) for cc in range(C)]")
    assert free_names(expr) == {"part.offset", "C"}
    # builtin whitelist members are not free symbols
    assert free_names(_expr("sorted(range(n))")) == {"n"}


def test_bind_hint_parsing_and_attachment():
    lines = [
        "pairs = table(C)  # graftverify: bind C=2,4,8",
        "# graftverify: bind C=1..3 part.offset=0..2",
        "# (explanatory continuation comment)",
        "",
        "pairs2 = other(C)",
    ]
    hints = parse_bind_hints(lines)
    assert hints[1] == {"C": [2, 4, 8]}
    # standalone form binds the next *code* line, skipping comments/blanks
    assert hints[5] == {"C": [1, 2, 3], "part.offset": [0, 1, 2]}


def test_expand_bindings_cross_product_and_cap():
    combos = expand_bindings({"a": [1, 2], "b": [3, 4]})
    assert {(c["a"], c["b"]) for c in combos} == {(1, 3), (1, 4), (2, 3), (2, 4)}
    assert expand_bindings({}) == [{}]
    assert len(expand_bindings({"a": list(range(100)),
                                "b": list(range(100))})) == 512  # capped


# ============================================================= module graph

def test_module_graph_reaches_through_transforms_and_closures(tmp_path):
    src = load_source(_write(tmp_path, """
        import jax

        def leaf(x):
            return x

        def middle(x):
            def inner(y):
                return leaf(y)
            return jax.vmap(inner)(x)

        stepped = jax.jit(middle)
    """), REPO)
    graph = ModuleGraph(src)
    names = {getattr(fn, "name", "?") for _, fn in graph.compiled_functions()}
    assert {"middle", "inner", "leaf"} <= names


def test_module_graph_issues_collective_transitively(tmp_path):
    src = load_source(_write(tmp_path, """
        from jax import lax

        def a(x, axis):
            return b(x, axis)

        def b(x, axis):
            return lax.psum(x, axis)

        def pure(x):
            return x + 1

        def cyclic(x, axis):
            return cyclic(x, axis)
    """), REPO)
    graph = ModuleGraph(src)
    fns = {getattr(f, "name"): f
           for flist in graph.functions.values() for f in flist}
    assert graph.issues_collective(fns["b"])
    assert graph.issues_collective(fns["a"])  # through the call graph
    assert not graph.issues_collective(fns["pure"])
    assert not graph.issues_collective(fns["cyclic"])  # cycle-safe


def _write(tmp_path, code, filename="snippet.py"):
    f = tmp_path / filename
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(code))
    return f


# ===================================================================== GL101

def test_gl101_fires_on_one_sided_literal(tmp_path):
    vs = _lint(tmp_path, """
        from jax import lax

        def f(x, axis):
            return lax.ppermute(x, axis, [(0, 1)])
    """)
    assert _ids(vs) == ["GL101"]
    assert "one-sided" in vs[0].message


def test_gl101_fires_on_broken_table_under_binding(tmp_path):
    vs = _lint(tmp_path, """
        from jax import lax

        def f(x, axis, C):
            # graftverify: bind C=2..4
            pairs = [(cc, cc // 2) for cc in range(C)]
            return lax.ppermute(x, axis, pairs)
    """)
    assert _ids(vs) == ["GL101"]
    assert "not a permutation" in vs[0].message
    assert "binding" in vs[0].message  # names the instantiation that broke


def test_gl101_fires_on_unhinted_dynamic_table(tmp_path):
    vs = _lint(tmp_path, """
        from jax import lax

        def f(x, axis, C, d):
            pairs = [((cc + d) % C, cc) for cc in range(C)]
            return lax.ppermute(x, axis, pairs)
    """)
    assert _ids(vs) == ["GL101"]
    assert "bind" in vs[0].message  # the fix is a hint, and the message says so


def test_gl101_silent_on_hinted_ring_and_literal_exchange(tmp_path):
    vs = _lint(tmp_path, """
        from jax import lax

        def ring(x, axis, C, d):
            # graftverify: bind C=1..8 d=0..7
            pairs = [((cc + d) % C, cc) for cc in range(C)]
            return lax.ppermute(x, axis, pairs)

        def pairwise(x, axis):
            return lax.ppermute(x, axis, [(0, 1), (1, 0)])
    """)
    assert vs == []


def test_gl101_suppression(tmp_path):
    vs = _lint(tmp_path, """
        from jax import lax

        def f(x, axis, pairs):
            return lax.ppermute(x, axis, pairs)  # graftlint: disable=GL101 — table validated by build_folded_plan
    """)
    assert vs == []


# ============================================== GL101: involution tables
# (the row-gather exchanges' tables — gossip_mix / gossip_mix_skip — are the
# same silent-corruption class as a one-sided ppermute — verified statically
# where foldable, parametrically under bind hints, and accepted through the
# involution_tables runtime-validator seam otherwise.)

def test_gl101_fires_on_non_involution_literal(tmp_path):
    vs = _lint(tmp_path, """
        from matcha_tpu.parallel import gossip_mix

        def f(x, w, gate):
            return gossip_mix(x, [[1, 2, 0]], w, gate)
    """)
    assert _ids(vs) == ["GL101"]
    assert "not an involution" in vs[0].message  # names the asymmetry


def test_gl101_fires_on_broken_involution_under_binding(tmp_path):
    # π(i) = (i + d) % n is an involution only when 2·d ≡ 0 (mod n):
    # the d=1 binding must break the parametric proof and be named
    vs = _lint(tmp_path, """
        from matcha_tpu.parallel import gossip_mix

        def f(x, w, gate, n, d):
            # graftverify: bind n=4 d=1,2
            tables = [[(i + d) % n for i in range(n)]]
            return gossip_mix(x, tables, w, gate)
    """)
    assert _ids(vs) == ["GL101"]
    assert "involution" in vs[0].message
    assert "binding" in vs[0].message


def test_gl101_silent_on_hinted_involution_and_pair_swap(tmp_path):
    vs = _lint(tmp_path, """
        from matcha_tpu.parallel import gossip_mix

        def shifted(x, w, gate, n):
            # the n/2 shift pairs i with its antipode: a real involution
            # for every even binding
            # graftverify: bind n=2,4,8
            tables = [[(i + n // 2) % n for i in range(n)]]
            return gossip_mix(x, tables, w, gate)

        def literal(x, w, gate):
            return gossip_mix(x, [[1, 0, 3, 2], [0, 2, 1, 3]], w, gate)
    """)
    assert vs == []


def test_gl101_accepts_involution_tables_seam(tmp_path):
    # schedule-built tables are runtime values; routing them through the
    # involution_tables validator (which raises on a non-involution) is
    # the sanctioned seam — including tuple unpacking and closure use,
    # the shape the production backend factory has
    vs = _lint(tmp_path, """
        from matcha_tpu.parallel import gossip_mix_skip, involution_tables

        def make(schedule):
            pi, pr = involution_tables(schedule.perms)

            def mix(x, w):
                return gossip_mix_skip(x, pi, w)

            return mix
    """)
    assert vs == []


def test_gl101_fires_on_unvalidated_runtime_tables(tmp_path):
    vs = _lint(tmp_path, """
        import numpy as np
        from matcha_tpu.parallel import gossip_mix

        def f(x, w, gate, schedule):
            pi = np.asarray(schedule.perms, np.int32)
            return gossip_mix(x, perms=pi, weights=w, alive=gate)
    """)
    assert _ids(vs) == ["GL101"]
    assert "involution_tables" in vs[0].message  # the fix is the seam


def test_involution_tables_validator_rejects_non_involution():
    # the runtime half of the seam the static rule accepts: a 3-cycle
    # must raise, a pair-swap stack must normalize
    import numpy as np
    import pytest as _pytest

    from matcha_tpu.parallel import involution_tables

    pi, pr = involution_tables(np.asarray([[1, 0, 2], [0, 2, 1]]))
    assert pi.dtype == np.int32 and pr.dtype == np.float32
    assert pr.tolist() == [[1.0, 1.0, 0.0], [0.0, 1.0, 1.0]]
    with _pytest.raises(ValueError, match="not an involution"):
        involution_tables(np.asarray([[1, 2, 0]]))
    with _pytest.raises(ValueError, match="out of range"):
        involution_tables(np.asarray([[3, 0, 1]]))


# ===================================================================== GL102

def test_gl102_fires_on_collective_in_divergent_branch(tmp_path):
    vs = _lint(tmp_path, """
        from jax import lax

        def body(x, axis):
            c = lax.axis_index(axis)
            if c == 0:
                x = lax.psum(x, axis)
            return x

        f = shard_map(body, mesh=None, in_specs=(), out_specs=())
    """)
    assert _ids(vs) == ["GL102"]
    assert "deadlock" in vs[0].message


def test_gl102_fires_interprocedurally(tmp_path):
    vs = _lint(tmp_path, """
        from jax import lax

        def gossip(x, axis):
            return lax.psum(x, axis)

        def body(x, axis):
            if lax.axis_index(axis) == 0:
                x = gossip(x, axis)
            return x

        f = shard_map(body, mesh=None, in_specs=(), out_specs=())
    """)
    assert _ids(vs) == ["GL102"]
    assert "transitively" in vs[0].message


def test_gl102_silent_on_data_gating_and_indexing(tmp_path):
    # the legal patterns: divergence flows through *data* (where/masks,
    # row selection), the collective itself runs on every worker
    vs = _lint(tmp_path, """
        import jax.numpy as jnp
        from jax import lax

        def body(x, table, axis):
            c = lax.axis_index(axis)
            row = table[c]                       # divergent *indexing*: fine
            y = lax.psum(jnp.where(c == 0, x, 0.0), axis)
            return y + row
        f = shard_map(body, mesh=None, in_specs=(), out_specs=())
    """)
    assert vs == []


def test_gl102_suppression(tmp_path):
    vs = _lint(tmp_path, """
        from jax import lax

        def body(x, axis):
            if lax.axis_index(axis) == 0:
                # graftlint: disable=GL102 — single-host init path, never traced SPMD
                x = lax.psum(x, axis)
            return x
        f = shard_map(body, mesh=None, in_specs=(), out_specs=())
    """)
    assert vs == []


# ===================================================================== GL103

_WIRE_FILE = "matcha_tpu/parallel/fake_wire.py"


def test_gl103_fires_on_double_quantization(tmp_path):
    vs = _lint(tmp_path, """
        from jax import lax

        def exchange(x, axis, wire_dtype, pairs):
            wire = resolve_wire_dtype(wire_dtype)
            xw = x.astype(wire)
            xq = xw.astype(wire)  # second rounding
            return lax.ppermute(xq, axis, pairs)  # graftlint: disable=GL101 — fixture targets GL103
    """, filename=_WIRE_FILE)
    assert _ids(vs) == ["GL103"]
    assert "already-quantized" in vs[0].message


def test_gl103_fires_on_raw_exchange_bypassing_wire_image(tmp_path):
    vs = _lint(tmp_path, """
        from jax import lax

        def exchange(x, axis, wire_dtype, pairs):
            wire = resolve_wire_dtype(wire_dtype)
            xw = x.astype(wire)
            y = lax.ppermute(x, axis, pairs)  # graftlint: disable=GL101 — fixture targets GL103
            return y + xw
    """, filename=_WIRE_FILE)
    assert _ids(vs) == ["GL103"]
    assert "bypasses" in vs[0].message


def test_gl103_fires_on_two_phase_double_quantize(tmp_path):
    vs = _lint(tmp_path, """
        from matcha_tpu.communicator.base import Communicator

        class DoubleWire(Communicator):
            def begin_mix(self, flat, carry, flags_t, alive=None):
                wire = resolve_wire_dtype("bf16")
                return flat.astype(wire), carry

            def apply_mix(self, flat, delta):
                wire = resolve_wire_dtype("bf16")
                return flat + delta.astype(wire)
    """, filename="matcha_tpu/communicator/fake_comm.py")
    assert _ids(vs) == ["GL103"]
    assert "begin_mix" in vs[0].message and "apply_mix" in vs[0].message


def test_gl103_silent_on_the_shipped_exchange_shape_and_out_of_scope(tmp_path):
    # the exact quantize-once shape gossip_mix_folded ships
    vs = _lint(tmp_path, """
        from jax import lax

        def exchange(x_blk, axis, wire_dtype, pairs):
            wire = resolve_wire_dtype(wire_dtype)
            xw_wire = x_blk if wire is None else x_blk.astype(wire)
            xw = x_blk if wire is None else xw_wire.astype(x_blk.dtype)
            y = lax.ppermute(xw_wire, axis, pairs).astype(x_blk.dtype)  # graftlint: disable=GL101 — fixture targets GL103
            return y - xw
    """, filename=_WIRE_FILE)
    assert vs == []
    # identical double-cast outside parallel/+communicator/ is not GL103's
    # business
    vs = _lint(tmp_path, """
        def elsewhere(x, wire_dtype):
            wire = resolve_wire_dtype(wire_dtype)
            return x.astype(wire).astype(wire)
    """, filename="somewhere/else.py")
    assert vs == []


def test_gl103_suppression(tmp_path):
    vs = _lint(tmp_path, """
        def exchange(x, wire_dtype):
            wire = resolve_wire_dtype(wire_dtype)
            xw = x.astype(wire)
            # graftlint: disable=GL103 — stochastic-rounding probe, second pass intended
            return xw.astype(wire)
    """, filename=_WIRE_FILE)
    assert vs == []


# ===================================================================== GL104

def test_gl104_fires_on_shape_branch_in_jit_root(tmp_path):
    vs = _lint(tmp_path, """
        import jax

        @jax.jit
        def step(x):
            if x.shape[0] > 4:
                return x * 2
            return x
    """)
    assert _ids(vs) == ["GL104"]
    assert "x.shape" in vs[0].message


def test_gl104_fires_through_a_helper(tmp_path):
    vs = _lint(tmp_path, """
        import jax

        def helper(y):
            if len(y) > 4:
                return y * 2
            return y

        @jax.jit
        def step(x):
            return helper(x)
    """)
    assert _ids(vs) == ["GL104"]
    assert "len(y)" in vs[0].message


def test_gl104_silent_on_static_argnames_and_validation_guards(tmp_path):
    vs = _lint(tmp_path, """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("n",))
        def step(x, n):
            if x.shape[0] != 8:
                raise ValueError("bad worker fold")   # loud guard, no fork
            if n > 4:                                 # declared static: the
                return x * 2                          # cache key covers it
            return x

        def host_helper(x):
            if x.shape[0] > 4:                        # never compiled: fine
                return x * 2
            return x
    """)
    assert vs == []


def test_gl104_suppression(tmp_path):
    vs = _lint(tmp_path, """
        import jax

        @jax.jit
        def step(x):
            # graftlint: disable=GL104 — two shapes by design: full + tail batch
            if x.shape[0] > 4:
                return x * 2
            return x
    """)
    assert vs == []


# ================================================================= planlint

PLAN_DIR = REPO / "benchmarks"


def _committed_plan():
    # discover_plan_files also surfaces the measured_link_costs family
    # (ISSUE 11) — the tampering suite below wants a *plan*-format artifact
    plans = [json.loads(f.read_text()) for f in discover_plan_files([PLAN_DIR])]
    plans = [d for d in plans
             if str(d.get("format", "")).startswith("matcha_tpu.plan")]
    assert plans, "no committed plan artifact under benchmarks/ — ISSUE 6 " \
                  "ships benchmarks/plan_ring16.json"
    return plans[0]


def test_every_committed_plan_artifact_verifies():
    """The acceptance gate: lint-plan validates every committed artifact
    numerically (doubly stochastic draws, involutions, α window, re-derived
    predictions)."""
    violations, files = lint_plan_paths([PLAN_DIR])
    assert files, "no plan artifacts found under benchmarks/"
    assert violations == [], "\n".join(
        f"{v.path}: {v.rule} {v.message}" for v in violations)


def test_planlint_catches_tampering():
    base = _committed_plan()

    def tampered(mutate):
        d = copy.deepcopy(base)
        mutate(d)
        return {v.rule for v in lint_plan_data(d, "tampered.json")}

    # α pushed out of the spectral window: PL005 (plus the re-derivations
    # it breaks)
    assert "PL005" in tampered(
        lambda d: d["chosen"].__setitem__("alpha", d["chosen"]["alpha"] * 50))
    # ρ edited without touching its inputs: PL006
    assert "PL006" in tampered(
        lambda d: d["chosen"].__setitem__("rho", 0.5))
    # probabilities outside [0, 1] / over budget: PL007
    assert "PL007" in tampered(
        lambda d: d["chosen"].__setitem__(
            "probs", [1.5] * len(d["chosen"]["probs"])))
    # chosen replaced by a worse-ranked candidate: PL008
    assert "PL008" in tampered(
        lambda d: d.__setitem__("chosen", copy.deepcopy(d["candidates"][-1])))
    # solver outputs that do not belong to the stored topology: PL002
    assert "PL002" in tampered(
        lambda d: d["chosen"].__setitem__("num_workers", 15))
    # missing solver keys / foreign format: PL001
    assert "PL001" in tampered(lambda d: d["chosen"].pop("probs"))
    assert "PL001" in tampered(lambda d: d.__setitem__("format", "nope/9"))
    # non-finite alpha must not sail through NaN comparisons
    assert "PL005" in tampered(
        lambda d: d["chosen"].__setitem__("alpha", float("nan")))


def test_planlint_ignores_non_plan_json(tmp_path):
    (tmp_path / "not_a_plan.json").write_text(json.dumps({"cells": [1, 2]}))
    violations, files = lint_plan_paths([tmp_path])
    assert files == [] and violations == []


def test_plan_checks_documented():
    assert set(PLAN_CHECKS) == {f"PL{i:03d}" for i in range(1, 12)}
    for what in PLAN_CHECKS.values():
        assert what  # lint-plan --list-checks has substance


# ============================================================== CLI plumbing

def test_lint_plan_cli_clean_and_tampered(tmp_path, capsys):
    import lint_tpu

    assert lint_tpu.main(["lint-plan", str(PLAN_DIR)]) == 0
    out = capsys.readouterr().out
    # count dynamically: new per-round captures (e.g. a committed
    # measured_link_costs_r7.json) must not break the pin
    n = len(discover_plan_files([PLAN_DIR]))
    assert n >= 2  # plan_ring16.json + measured_link_costs_ring8.json
    assert "0 violation(s)" in out and f"{n} plan artifact" in out

    d = copy.deepcopy(_committed_plan())
    d["chosen"]["rho"] = 0.123
    bad = tmp_path / "tampered_plan.json"
    bad.write_text(json.dumps(d))
    assert lint_tpu.main(["lint-plan", str(bad)]) == 1
    assert "PL006" in capsys.readouterr().out

    assert lint_tpu.main(["lint-plan", str(tmp_path / "missing.json")]) == 2
    assert lint_tpu.main(["lint-plan", "--list-checks"]) == 0


def test_lint_plan_cli_json_format(tmp_path, capsys):
    import lint_tpu

    assert lint_tpu.main(["lint-plan", str(PLAN_DIR), "--format", "json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["clean"] is True
    assert len(out["artifacts_checked"]) >= 1


def test_changed_mode(capsys):
    import lint_tpu

    # vs HEAD: whatever is dirty right now must still lint clean (the tree
    # invariant), and an unknown ref is a usage error, not a crash
    assert lint_tpu.main(["--changed", "HEAD"]) == 0
    assert lint_tpu.main(["--changed", "no-such-ref-xyz"]) == 2
    assert "failed" in capsys.readouterr().err


def test_spmd_rules_listed_by_cli(capsys):
    import lint_tpu

    assert lint_tpu.main(["--list-rules", "--rules", "GL101,GL104"]) == 0
    out = capsys.readouterr().out
    assert "GL101" in out and "GL104" in out and "permutation" in out


# ==================================================== review-finding guards
# (ISSUE 6 code review: each of these was a demonstrated hole)

def test_gl101_fires_on_mutated_table(tmp_path):
    """Folding the seed of a later-mutated table would 'verify' a value the
    ppermute never sees — mutation must force the dynamic path."""
    vs = _lint(tmp_path, """
        from jax import lax

        def f(x, axis):
            pairs = []
            for i in range(4):
                pairs.append((0, i))   # duplicate sources, one-sided
            return lax.ppermute(x, axis, pairs)
    """)
    assert _ids(vs) == ["GL101"]
    assert "unmutated" in vs[0].message
    # += and item assignment count as mutation too
    vs = _lint(tmp_path, """
        from jax import lax

        def f(x, axis):
            pairs = [(0, 1), (1, 0)]
            pairs += [(0, 2)]
            return lax.ppermute(x, axis, pairs)
    """)
    assert _ids(vs) == ["GL101"]


def test_gl101_rejects_empty_table(tmp_path):
    vs = _lint(tmp_path, """
        from jax import lax

        def f(x, axis):
            return lax.ppermute(x, axis, [])
    """)
    assert _ids(vs) == ["GL101"]
    assert "empty table" in vs[0].message


def test_lint_plan_surfaces_tampered_format_on_explicit_path(tmp_path, capsys):
    """A wrong format tag must not make an explicitly-named artifact vanish
    from the scan (exit 0, '0 artifacts') — and a *drifted* plan-family
    version tag is scanned and fails PL001 even in directory mode."""
    import lint_tpu

    d = copy.deepcopy(_committed_plan())
    d["format"] = "nope/9"
    foreign = tmp_path / "foreign.json"
    foreign.write_text(json.dumps(d))
    assert lint_tpu.main(["lint-plan", str(foreign)]) == 1
    assert "PL001" in capsys.readouterr().out

    d["format"] = "matcha_tpu.plan/999"
    drifted = tmp_path / "drifted_plan.json"
    drifted.write_text(json.dumps(d))
    assert lint_tpu.main(["lint-plan", str(tmp_path)]) == 1  # directory scan
    assert "PL001" in capsys.readouterr().out


def test_changed_flag_guards(capsys):
    """--changed computes its own path set: explicit paths and
    --write-baseline (which would drop unchanged files' grandfathered
    entries) are refused loudly."""
    import lint_tpu

    assert lint_tpu.main(["matcha_tpu", "--changed", "HEAD"]) == 2
    assert "mutually exclusive" in capsys.readouterr().err
    assert lint_tpu.main(["--changed", "HEAD", "--write-baseline"]) == 2
    assert "refusing" in capsys.readouterr().err


def test_lint_plan_works_from_any_cwd(tmp_path, monkeypatch, capsys):
    import lint_tpu

    monkeypatch.chdir(tmp_path)
    assert lint_tpu.main(["lint-plan"]) == 0  # default benchmarks/ resolves
    n = len(discover_plan_files([PLAN_DIR]))
    assert f"{n} plan artifact" in capsys.readouterr().out


def test_gl101_empty_or_malformed_hint_is_a_violation_not_a_pass(tmp_path):
    """A reversed range or malformed value must not verify vacuously, and
    must never crash the lint run (round-2 review findings)."""
    broken_table = """
        from jax import lax

        def f(x, axis, C):
            # graftverify: bind C={spec}
            pairs = [(0, cc) for cc in range(C)]   # duplicate sources
            return lax.ppermute(x, axis, pairs)
    """
    for spec in ("8..1", "1.5"):
        vs = _lint(tmp_path, broken_table.replace("{spec}", spec))
        assert _ids(vs) == ["GL101"], spec
        assert "zero bindings" in vs[0].message


def test_gl101_fold_crash_reports_instead_of_aborting(tmp_path):
    """TypeError/IndexError inside const_eval under a binding must become a
    violation with context, not a traceback that kills ci/lint.sh."""
    vs = _lint(tmp_path, """
        from jax import lax

        def f(x, axis, C):
            # graftverify: bind C=2..3
            pairs = [((cc, cc) + C, cc) for cc in range(C)]
            return lax.ppermute(x, axis, pairs)
    """)
    assert _ids(vs) == ["GL101"]
    assert "TypeError" in vs[0].message and "binding" in vs[0].message
