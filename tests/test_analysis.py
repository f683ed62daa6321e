"""graftlint + trace-purity sanitizer tests (ISSUE 5).

Three layers, mirroring how ``tests/test_docs_artifacts.py`` machine-checks
doc claims:

* **Per-rule fixtures** — every rule (GL001–GL006) fires on a synthetic
  violation, stays silent on the compliant twin, and honors the inline
  ``# graftlint: disable=RULE`` suppression.
* **The real tree is clean** — the engine runs over ``matcha_tpu/`` and the
  three CLIs with the shipped (empty) baseline and must report nothing:
  the review-lore invariants are now enforced on every tier-1 run.
* **Retrace sanitizer e2e** — a 2-step MLP ring train compiles exactly one
  program; a deliberately shape-polymorphic step trips the guard.

Marker: ``analysis`` — run standalone with ``pytest -m analysis``.
"""

import json
import pathlib
import textwrap

import pytest

from matcha_tpu.analysis import (
    ALL_RULES,
    check_single_trace,
    lint_paths,
    lint_source,
    load_baseline,
    render_text,
    retrace_guard,
    rules_by_id,
)
from matcha_tpu.analysis.engine import load_source

pytestmark = pytest.mark.analysis

REPO = pathlib.Path(__file__).resolve().parents[1]
LINT_TARGETS = ["matcha_tpu", "train_tpu.py", "plan_tpu.py", "serve_tpu.py"]


def _lint(tmp_path, code, rules=None, filename="snippet.py"):
    f = tmp_path / filename
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(code))
    return lint_source(load_source(f, REPO), rules or ALL_RULES)


def _ids(violations):
    return sorted({v.rule for v in violations})


# ===================================================================== GL001

def test_gl001_fires_on_mask_value_multiply(tmp_path):
    vs = _lint(tmp_path, """
        def seal(x, alive):
            return alive * x  # the 0·NaN leak
    """)
    assert _ids(vs) == ["GL001"]
    assert vs[0].line == 3


def test_gl001_silent_on_where_and_mask_algebra(tmp_path):
    vs = _lint(tmp_path, """
        import jax.numpy as jnp

        def seal(x, alive, finite):
            ok = alive * finite                 # mask ∘ mask: finite 0/1
            comp = alive * (1.0 - finite)       # complement algebra
            cast = alive * finite.astype(x.dtype)
            return jnp.where(ok > 0, x, jnp.zeros_like(x)), comp, cast
    """)
    assert vs == []


def test_gl001_suppression_with_reason(tmp_path):
    vs = _lint(tmp_path, """
        def edge(delta, alive):
            return alive * delta  # graftlint: disable=GL001 — weights, not values
    """)
    assert vs == []


def test_gl001_standalone_suppression_above_the_line(tmp_path):
    vs = _lint(tmp_path, """
        def edge(delta, alive):
            # graftlint: disable=GL001 — weights, not values: the mask
            # scales finite edge weights (two-line annotation form)
            return alive * delta
    """)
    assert vs == []


# ===================================================================== GL002

def test_gl002_fires_on_impurity_inside_jit(tmp_path):
    vs = _lint(tmp_path, """
        import time
        import numpy as np
        import jax

        @jax.jit
        def step(x):
            t = time.time()
            noise = np.random.normal()
            return x + t + noise
    """)
    assert _ids(vs) == ["GL002"]
    assert len(vs) == 2  # time.time and np.random.normal


def test_gl002_reaches_through_the_call_graph(tmp_path):
    vs = _lint(tmp_path, """
        import jax

        def helper(x):
            print("leaks once, at trace time")
            return x

        def middle(x):
            return helper(x)

        @jax.jit
        def step(x):
            return middle(x)
    """)
    assert _ids(vs) == ["GL002"]
    assert "print" in vs[0].message and "step" in vs[0].message


def test_gl002_reaches_through_transforms_and_shard_map(tmp_path):
    vs = _lint(tmp_path, """
        import jax

        def per_worker(x):
            return float(x.sum())  # concretizes a tracer

        def body(x):
            return jax.vmap(per_worker)(x)

        sharded = shard_map(body, mesh=None, in_specs=(), out_specs=())
    """)
    assert _ids(vs) == ["GL002"]
    assert "float" in vs[0].message


def test_gl002_silent_on_host_code_and_pure_jit(tmp_path):
    vs = _lint(tmp_path, """
        import time
        import jax
        import jax.numpy as jnp

        def epoch_timer():
            return time.time()  # host-side: never traced

        @jax.jit
        def step(x, key):
            noise = jax.random.normal(key, x.shape)
            jax.debug.print("loss {}", x.sum())
            return x + noise
    """)
    assert vs == []


def test_gl002_suppression(tmp_path):
    vs = _lint(tmp_path, """
        import jax

        @jax.jit
        def step(x, n):
            # graftlint: disable=GL002 — n rides static_argnames (trace-time)
            k = int(n)
            return x * k
    """)
    assert vs == []


# ===================================================================== GL003

def test_gl003_fires_on_literal_axis_names(tmp_path):
    # scoped to GL003: the dynamic `pairs` parameter is GL101's business
    # (tests/test_dataflow.py) and would double-report here
    vs = _lint(tmp_path, """
        from jax import lax

        def exchange(x, pairs):
            y = lax.ppermute(x, "workers", pairs)
            return lax.psum(y, axis_name="workers")
    """, rules=rules_by_id(["GL003"]))
    assert _ids(vs) == ["GL003"]
    assert len(vs) == 2


def test_gl003_silent_on_threaded_axis_constant(tmp_path):
    vs = _lint(tmp_path, """
        from jax import lax
        from matcha_tpu.parallel.mesh import WORKER_AXIS

        def exchange(x, pairs, axis=WORKER_AXIS):
            y = lax.ppermute(x, axis, pairs)
            return lax.psum(y, axis_name=axis)
    """, rules=rules_by_id(["GL003"]))
    assert vs == []


def test_gl003_suppression(tmp_path):
    vs = _lint(tmp_path, """
        from jax import lax

        def exchange(x, pairs):
            return lax.ppermute(x, "workers", pairs)  # graftlint: disable=GL003 — single-axis test harness
    """, rules=rules_by_id(["GL003"]))
    assert vs == []


# ===================================================================== GL004

_EXCHANGE_FILE = "matcha_tpu/parallel/fake_exchange.py"


def test_gl004_fires_on_hardcoded_narrow_cast_in_exchange_layer(tmp_path):
    vs = _lint(tmp_path, """
        import jax.numpy as jnp

        def exchange(x):
            return x.astype(jnp.bfloat16)  # bypasses resolve_wire_dtype
    """, filename=_EXCHANGE_FILE)
    assert _ids(vs) == ["GL004"]


def test_gl004_silent_on_seam_threaded_dtype_and_out_of_scope(tmp_path):
    vs = _lint(tmp_path, """
        def exchange(x, wire):
            xw = x if wire is None else x.astype(wire)
            return xw.astype(x.dtype)
    """, filename=_EXCHANGE_FILE)
    assert vs == []
    # the identical hard cast OUTSIDE the exchange layer is not GL004's
    # business
    vs = _lint(tmp_path, """
        import jax.numpy as jnp

        def bench_state(x):
            return x.astype(jnp.bfloat16)
    """, filename="somewhere/else.py")
    assert vs == []


def test_gl004_suppression(tmp_path):
    vs = _lint(tmp_path, """
        import jax.numpy as jnp

        def exchange(x):
            # graftlint: disable=GL004 — kernel-internal scratch, never wired
            return x.astype(jnp.bfloat16)
    """, filename=_EXCHANGE_FILE)
    assert vs == []


# ===================================================================== GL005

def test_gl005_fires_on_one_sided_override(tmp_path):
    vs = _lint(tmp_path, """
        from matcha_tpu.communicator.base import Communicator

        class BeginOnly(Communicator):
            def begin_mix(self, flat, carry, flags_t, alive=None):
                return flat, carry

        class ApplyOnly(Communicator):
            def apply_mix(self, flat, delta):
                return flat
    """)
    assert _ids(vs) == ["GL005"]
    assert len(vs) == 2
    assert "BeginOnly" in vs[0].message and "ApplyOnly" in vs[1].message


def test_gl005_silent_on_paired_or_untouched_overrides(tmp_path):
    vs = _lint(tmp_path, """
        from matcha_tpu.communicator.base import Communicator

        class Paired(Communicator):
            def begin_mix(self, flat, carry, flags_t, alive=None):
                return flat, carry

            def apply_mix(self, flat, delta):
                return flat + delta

        class Untouched(Communicator):
            def extra(self):
                return None

        class NotAComm:
            def begin_mix(self):
                return None
    """)
    assert vs == []


def test_gl005_suppression(tmp_path):
    vs = _lint(tmp_path, """
        from matcha_tpu.communicator.base import Communicator

        # graftlint: disable=GL005 — inherits base apply_mix on purpose:
        # the delta form is unchanged, only issue-side bookkeeping differs
        class BeginOnly(Communicator):
            def begin_mix(self, flat, carry, flags_t, alive=None):
                return flat, carry
    """)
    assert vs == []


# ===================================================================== GL006

def test_gl006_fires_on_bare_and_swallowed(tmp_path):
    vs = _lint(tmp_path, """
        def recover(retry):
            try:
                retry()
            except:
                retry()
            try:
                retry()
            except Exception:
                pass
    """)
    assert _ids(vs) == ["GL006"]
    assert len(vs) == 2
    assert "bare" in vs[0].message and "swallowed" in vs[1].message


def test_gl006_silent_on_narrow_eafp_and_handled_broad(tmp_path):
    vs = _lint(tmp_path, """
        def recover(retry, log):
            try:
                retry()
            except ValueError:
                pass  # narrow EAFP: deliberate and legal
            try:
                retry()
            except Exception as e:
                log(e)
                raise
    """)
    assert vs == []


def test_gl006_suppression(tmp_path):
    vs = _lint(tmp_path, """
        def recover(retry):
            try:
                retry()
            # graftlint: disable=GL006 — best-effort telemetry, loss is safe
            except Exception:
                pass
    """)
    assert vs == []


# ============================================================ engine plumbing

def test_rules_by_id_filter_and_unknown():
    assert [r.id for r in rules_by_id(["GL003", "gl001"])] == ["GL001", "GL003"]
    with pytest.raises(KeyError):
        rules_by_id(["GL999"])


def test_duplicate_hits_collapse_per_line(tmp_path):
    # a * b * c nests two Mult nodes on one line — one report, not two
    vs = _lint(tmp_path, """
        def f(x, alive, mask):
            return alive * mask[0] * x
    """)
    assert len(vs) == 1


def test_baseline_grandfathers_old_but_not_new(tmp_path):
    import lint_tpu

    bad = tmp_path / "bad.py"
    bad.write_text("def f(x, alive):\n    return alive * x\n")
    baseline = tmp_path / "baseline.json"
    assert lint_tpu.main([str(bad), "--no-baseline"]) == 1
    assert lint_tpu.main([str(bad), "--baseline", str(baseline),
                          "--write-baseline"]) == 0
    assert lint_tpu.main([str(bad), "--baseline", str(baseline)]) == 0
    # a NEW violation in the same file is not grandfathered
    bad.write_text("def f(x, alive):\n    return alive * x\n"
                   "def g(x, mask):\n    return mask * x\n")
    assert lint_tpu.main([str(bad), "--baseline", str(baseline)]) == 1


def test_cli_names_its_errors(tmp_path, capsys):
    """Missing paths and unparseable files are usage errors (exit 2) with a
    one-line message — never a raw traceback."""
    import lint_tpu

    assert lint_tpu.main([str(tmp_path / "missing.py")]) == 2
    assert "no such file" in capsys.readouterr().err
    broken = tmp_path / "broken.py"
    broken.write_text("def f(:\n")
    assert lint_tpu.main([str(broken)]) == 2
    assert "cannot parse" in capsys.readouterr().err
    assert lint_tpu.main(["--rules", "GL999"]) == 2


def test_cli_json_format_is_parseable(tmp_path, capsys):
    import lint_tpu

    bad = tmp_path / "bad.py"
    bad.write_text("def f(x, alive):\n    return alive * x\n")
    assert lint_tpu.main([str(bad), "--no-baseline", "--format", "json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["clean"] is False
    assert out["violations"][0]["rule"] == "GL001"
    assert {r["id"] for r in out["rules"]} >= {"GL001", "GL006"}


# ========================================================== the real tree

def test_shipped_baseline_is_empty():
    assert load_baseline(REPO / "graftlint_baseline.json") == set()


def test_shipped_tree_is_clean():
    """The acceptance gate: zero non-suppressed violations over the package
    and all three CLIs, with the shipped (empty) baseline."""
    violations, sources = lint_paths(LINT_TARGETS, ALL_RULES,
                                     baseline=set(), repo_root=REPO)
    assert len(sources) > 50  # the walk actually covered the package
    assert not violations, "\n" + render_text(violations, sources, ALL_RULES)


def test_rules_cover_the_documented_set():
    # core syntactic family + the interprocedural SPMD family (ISSUE 6) +
    # the graftcontract family (ISSUE 15) + the graftdur family (ISSUE 20);
    # tests/test_dataflow.py exercises GL101–GL104,
    # tests/test_contracts.py GL201–GL203, tests/test_durability.py
    # GL301–GL304
    assert [r.id for r in ALL_RULES] == [
        "GL001", "GL002", "GL003", "GL004", "GL005", "GL006",
        "GL101", "GL102", "GL103", "GL104",
        "GL201", "GL202", "GL203",
        "GL301", "GL302", "GL303", "GL304"]
    for r in ALL_RULES:
        assert r.title and r.invariant  # lint_tpu --list-rules has substance


# ==================================================== retrace sanitizer e2e

def _tiny_train():
    """A real compiled train step: MLP, 8-worker ring, dense gossip."""
    from matcha_tpu import topology as tp
    from matcha_tpu.communicator import make_decen
    from matcha_tpu.data import synthetic_classification
    from matcha_tpu.models import select_model
    from matcha_tpu.schedule import matcha_schedule
    from matcha_tpu.train.lr import make_lr_schedule
    from matcha_tpu.train.state import (
        init_train_state,
        make_optimizer,
        make_train_step,
    )

    n = 8
    sched = matcha_schedule(tp.select_graph(5), n, iterations=8, budget=0.5,
                            seed=0)
    comm = make_decen(sched, backend="dense")
    ds = synthetic_classification(num_train=256, num_test=32, seed=0)
    model = select_model("mlp", "synthetic", num_classes=ds.num_classes)
    lr = make_lr_schedule(0.1, 4, warmup=False)
    opt = make_optimizer(lr, momentum=0.9, weight_decay=0.0, nesterov=False)
    state, flattener = init_train_state(model, ds.x_train.shape[1:], n, opt,
                                        comm, seed=0)
    step = make_train_step(model, opt, comm, flattener, sched.flags,
                           lr_schedule=lr)
    return state, step, ds, n


def _batches(ds, n_workers, batch, steps, offset=0):
    import jax.numpy as jnp

    out = []
    for t in range(steps):
        lo = offset + t * n_workers * batch
        hi = lo + n_workers * batch
        xb = jnp.asarray(ds.x_train[lo:hi]).reshape(
            (n_workers, batch) + ds.x_train.shape[1:])
        yb = jnp.asarray(ds.y_train[lo:hi]).reshape(n_workers, batch)
        out.append((xb, yb))
    return out


@pytest.fixture
def trace_sanitizer():
    """Wrap a compiled train step, run it over batches, and assert it
    compiled exactly one program — the dynamic half of graftlint."""
    import jax

    def run(step_fn, state, batches, label="train_step"):
        guarded, counter = retrace_guard(step_fn)
        rng = jax.random.PRNGKey(0)
        for xb, yb in batches:
            state, metrics = guarded(state, xb, yb, rng)
        jax.block_until_ready(state.params)
        check_single_trace(counter, label=label)
        return state, counter

    return run


def test_retrace_sanitizer_clean_on_static_train(trace_sanitizer):
    """2-step MLP ring train: one trace, end of story."""
    state, step, ds, n = _tiny_train()
    state, counter = trace_sanitizer(step, state, _batches(ds, n, 4, 2))
    assert counter.count == 1
    assert int(state.step) == 2  # the train actually ran


def test_retrace_sanitizer_trips_on_shape_polymorphism(trace_sanitizer):
    """Deliberately vary the batch shape step-to-step: the guard must fail
    loudly — this is the recompile-every-step failure mode it exists for."""
    state, step, ds, n = _tiny_train()
    polymorphic = _batches(ds, n, 4, 1) + _batches(ds, n, 6, 1, offset=64)
    with pytest.raises(AssertionError, match="retraced"):
        trace_sanitizer(step, state, polymorphic)


def test_retrace_guard_counts_distinct_programs():
    import jax.numpy as jnp

    calls = {"n": 0}

    def f(x):
        calls["n"] += 1
        return x * 2.0

    guarded, counter = retrace_guard(f)
    a = guarded(jnp.ones((3,)))
    b = guarded(jnp.ones((3,)))  # cache hit: python body must NOT rerun
    assert counter.count == 1 and calls["n"] == 1
    assert jnp.allclose(a, b) and float(a[0]) == 2.0
    guarded(jnp.ones((4,)))  # new shape ⇒ new program
    assert counter.count == 2
    with pytest.raises(AssertionError, match="retraced"):
        check_single_trace(counter)


def test_check_single_trace_requires_a_call():
    from matcha_tpu.analysis import TraceCount

    with pytest.raises(AssertionError, match="never traced"):
        check_single_trace(TraceCount())
