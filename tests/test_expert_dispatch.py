"""The expert layer's dispatch (``models/mellum2.py``: ``_experts``,
``_dispatched_bf16``, ``checkpointed``), shared by the four token models:
what a layer's checkpoint keeps by name gives the step that computes it
again, to the bit; the rows rounded before the gather are the operands the
products read before; a rematerialised layer gathers and lays out once; the
cotangent into the tokens is summed in float32; the journal's plan names what
is kept.  Since PR 43 a row that holds no slot is in no group: whatever a
product leaves on such a row (NaN, 1e30) reaches nothing the layer keeps; the
layer equals the layout that counted those rows with the last expert, to the
bit; ``moe_rows_multiplied`` counts the row tiles the products visit.  Small
sizes on the CPU; seconds are a case's own inside the driver's pool of six
workers."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from matcha_tpu.models import mellum2, select_model
from matcha_tpu.ops import grouped
from test_mellum2 import SEQ, rows, sizes_of  # beside this file


def _token_cases():
    import test_keye_vl2 as keye  # beside this file
    import test_qwen3_next as gdn
    import test_sdar as bd
    more = {"moe_rows_per_even_slot": 3}  # rows != tokens: shapes tell them
    return {"mellum2": (sizes_of(**more), rows("packed")),
            "keye_vl2": (keye.sizes_of(**more), keye.rows("packed")),
            "qwen3_next": (gdn.sizes_of(**more), gdn.rows()),
            "sdar": (bd.sizes_of(**more), bd.raw_rows())}


TOKEN_MODELS = ["mellum2", "keye_vl2", "qwen3_next", "sdar"]


def token_model(name, remat):
    """(model, seeded weights away from their initial values, raw rows
    ``(x, y)``) of a token model at its own test's sizes."""
    sizes, raw = _token_cases()[name]
    model = select_model(name, "tokens", sizes=sizes, remat=remat)
    params = model.init(jax.random.PRNGKey(1), model.dummy_input(()),
                        train=False)["params"]
    keys = jax.random.split(jax.random.PRNGKey(2), len(params))
    params = {k: v + (0.3 * jax.random.normal(key, v.shape)
                      if v.ndim > 1 else 0.0)
              for key, (k, v) in zip(keys, sorted(params.items()))}
    return model, params, raw


def step_of(model, params, raw):
    """(loss, every gradient leaf) of one batch, jitted."""
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: model.apply({"params": p}, *raw, method="batch_loss"),
        has_aux=True))(params)
    return loss, grads


def assert_same_bits(got, want):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b, err_msg=str(path))


@pytest.mark.parametrize("name", TOKEN_MODELS)
def test_a_step_that_keeps_the_dispatch_equals_one_that_computes_it_again(
        name, monkeypatch):
    """Float32 path, a whole step under ``remat``: with the gathered rows
    and the layout kept by name the loss and every gradient leaf are, to the
    bit, those of the checkpoints that keep nothing and dispatch a second
    time (the form until PR 42).  (13-31 s a case.)"""
    kept = step_of(*token_model(name, True))
    assert np.isfinite(float(kept[0]))
    monkeypatch.setattr(mellum2, "MOE_KEPT", ())
    assert_same_bits(kept, step_of(*token_model(name, True)))


def expert_layer_of(name):
    """(``f(p, h)``: a model's expert layer as its block calls it, one
    layer's weights, ``h``)."""
    from matcha_tpu.models import keye_vl2, qwen3_next, sdar

    model, params, raw = token_model(name, True)
    z = model.sizes
    p = {k[len("layer0_"):]: v for k, v in params.items()
         if k.startswith("layer0_")}
    positions = model.row_positions(raw[0].shape[1])
    h = jax.random.normal(jax.random.PRNGKey(8), (3, positions, z["hidden"]))
    masked = jax.random.bernoulli(jax.random.PRNGKey(9), 0.3, h.shape[:2])
    return {
        "mellum2": lambda p, h: mellum2._moe(p, mellum2._rms_norm(
            h, p["moe_norm"], z["rms_norm_eps"]), z)[0],
        "keye_vl2": lambda p, h: keye_vl2._experts_of(p, h, z)[0],
        "qwen3_next": lambda p, h: qwen3_next._experts_of(p, h, z)[0],
        "sdar": lambda p, h: sdar._experts_of(p, h, masked, z)[0]}[name], p, h


@pytest.mark.parametrize("name", TOKEN_MODELS + ["mellum2, a whole step"])
def test_under_remat_equals_without_to_the_bit(name):
    """Float32 path, with and without ``remat``: each model's expert layer
    as its block wraps it (output and the gradients by its weights and its
    input), and a whole step of the one model whose attention, too,
    recomputes to the bit on this backend (the other three differ in a last
    bit of the attention's leaves with and without ``remat``, at the parent
    as here).  (3-4 s a case, the step 12 s.)"""
    if "step" in name:
        with_, without = (step_of(*token_model("mellum2", remat))
                          for remat in (True, False))
    else:
        layer, p, h = expert_layer_of(name)
        probe = jax.random.normal(jax.random.PRNGKey(10), h.shape)
        with_, without = (jax.jit(jax.value_and_grad(
            lambda p, h: jnp.sum(mellum2.checkpointed(remat)(layer)(p, h)
                                 * probe), argnums=(0, 1)))(p, h)
            for remat in (True, False))
    assert np.isfinite(float(with_[0]))
    assert_same_bits(with_, without)


def experts_until_pr_42(p, x, w_held, took, sizes):
    """``mellum2._experts`` as it stood until PR 42: two scatters lay the
    slots out, float32 rows are gathered and each product rounds its own
    operands (``_grouped_bf16``); no name, nothing kept."""
    b, s, hidden = x.shape
    tokens, held = took.shape
    flat = x.reshape(tokens, hidden)
    rows = mellum2.moe_capacity(tokens, sizes)
    count = jnp.sum(took, axis=0)
    start = jnp.cumsum(count) - count
    place = start[None, :] + jnp.cumsum(took, axis=0) - 1
    fits = took & (place < rows)
    at = jnp.where(fits, place, rows)
    token = jnp.full((rows,), tokens, jnp.int32).at[at].set(
        jnp.arange(tokens, dtype=jnp.int32)[:, None], mode="drop")
    w_rows = jnp.zeros((rows,), w_held.dtype).at[at].set(w_held, mode="drop")
    ends = jnp.minimum(start + count, rows)
    groups = (ends - jnp.minimum(start, rows)).astype(jnp.int32)
    groups = groups.at[-1].add(rows - jnp.sum(groups))
    y_rows = mellum2._swiglu(
        flat[jnp.minimum(token, tokens - 1)], p,
        lambda lhs, weights: mellum2._grouped_product(lhs, weights, groups))
    y = jnp.zeros_like(flat).at[token].add(y_rows * w_rows[:, None],
                                          mode="drop")
    return y.reshape(b, s, hidden), jnp.float32(rows)


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "plain"])
def test_kept_rows_equal_the_form_until_pr_42_on_the_bf16_path(remat,
                                                               monkeypatch):
    """The TPU's branch forced on the CPU at widths that tile, so the
    products are ``ops/grouped.py``'s kernels under the interpreter: rows
    rounded before the gather, gathered once and read by gate and up are
    the operands the products read when each rounded a float32 gather for
    itself, so the layer's output and its gradients by every weight and by
    its input (the float32 cotangent into ``flat``) are the old form's to
    the bit.  (4-10 s a case.)"""
    monkeypatch.setattr(mellum2, "_one_bf16_pass", lambda: True)
    z = sizes_of(hidden=128, expert_width=128, experts_held=[0, 1, 2, 3])
    tokens = 4 * SEQ  # 256 rows: two tiles of 128
    assert mellum2.expert_products(z, tokens, 1, remat, 1)["kernel_sites"] == 6
    rng = jax.random.split(jax.random.PRNGKey(7), 6)
    p = {k: 0.3 * jax.random.normal(key, shape) for key, (k, shape) in zip(
        rng, {"router": (128, 8), "gate": (4, 128, 128), "up": (4, 128, 128),
              "down": (4, 128, 128)}.items())}
    x, probe = (jax.random.normal(key, (4, SEQ, 128)) for key in rng[4:])
    # the router's choice is given, so that the experts' is the one
    # cotangent that reaches ``x``: where the router's joins it the compiler
    # is free to add it before a token's second slot or after
    w, sel = mellum2._route(p, x.reshape(tokens, 128), z)
    chosen = sel[:, :, None] == jnp.arange(4)[None, None, :]
    w_held = jnp.sum(jnp.where(chosen, w[:, :, None], 0.0), axis=1)
    took = jnp.any(chosen, axis=1)
    assert int(jnp.max(jnp.sum(took, axis=1))) == 2  # tokens in two slots

    def layer_and_gradients():
        return jax.jit(jax.value_and_grad(lambda p, x, w_held: jnp.sum(
            mellum2.checkpointed(remat)(
                lambda *a: mellum2._experts(*a, took, z))(p, x, w_held)[0]
            * probe), argnums=(0, 1, 2)))(p, x, w_held)

    now = layer_and_gradients()
    assert float(jnp.max(jnp.abs(now[1][0]["router"]))) == 0.0
    monkeypatch.setattr(mellum2, "_experts", experts_until_pr_42)
    assert_same_bits(now, layer_and_gradients())


def shapes_of(jaxpr, primitive):
    """The output shape of every ``primitive`` equation of a program,
    sub-programs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            found.append(tuple(eqn.outvars[0].aval.shape))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += shapes_of(sub, primitive)
    return found


@pytest.mark.parametrize("name", TOKEN_MODELS)
def test_a_rematerialised_layer_dispatches_once(name, monkeypatch):
    """The program of a step's gradient under ``remat``, each model through
    its own blocks: an expert layer gathers ``[rows, hidden]`` twice (its
    rows forward, the cotangent's rows backward) and scatters one layout;
    where the checkpoints keep no name it gathers a third time and lays the
    slots out again, which is what the count reads where an edit drops the
    names or a model's policy.  (2-5 s a case: tracing, no compile.)"""
    def dispatches():
        model, params, raw = token_model(name, True)
        z, positions = model.sizes, model.row_positions(raw[0].shape[1])
        rows = mellum2.moe_capacity(raw[0].shape[0] * positions, z)
        assert rows != raw[0].shape[0] * positions
        program = jax.make_jaxpr(jax.grad(lambda p: model.apply(
            {"params": p}, *raw, method="batch_loss")[0]))(params).jaxpr
        return (shapes_of(program, "gather").count((rows, z["hidden"]))
                / model.expert_layers,
                shapes_of(program, "scatter").count((rows,))
                / model.expert_layers)

    assert dispatches() == (2, 1)
    monkeypatch.setattr(mellum2, "MOE_KEPT", ())
    assert dispatches() == (3, 2)


def test_cotangent_into_the_tokens_is_float32_and_summed_unrounded(
        monkeypatch):
    """A token in several slots: the data gradients of gate and up reach
    ``flat`` as float32 rows, summed and scatter-added in float32, equal to
    the bit to autodiff through a float32 gather into two
    ``_grouped_bf16``; an ``astype`` before the gather rounds each slot's
    cotangent to bfloat16 before the sum, and the test tells it."""
    monkeypatch.setattr(mellum2, "_one_bf16_pass", lambda: True)
    rng = jax.random.split(jax.random.PRNGKey(6), 5)
    flat = jax.random.normal(rng[0], (10, 16))
    p = {"gate": jax.random.normal(rng[1], (3, 16, 12)),
         "up": jax.random.normal(rng[2], (3, 16, 12))}
    # token 4 in four slots, token 7 in three; 10 (out of bounds) holds none
    token = jnp.asarray([4, 0, 7, 4, 1, 10, 10, 4, 7, 2, 3, 4, 7, 5, 6, 8,
                         9, 10, 10, 10, 10, 10, 10, 10], jnp.int32)
    # (a row that holds no token has weight 0 in the layer: no cotangent)
    gs = tuple(jnp.where((token < 10)[:, None],
                         jax.random.normal(k, (24, 12)), 0.0)
               for k in rng[3:])
    groups = jnp.asarray([7, 5, 12], jnp.int32)

    def until_pr_42(flat):
        rows = flat[jnp.minimum(token, 9)]
        return tuple(mellum2._grouped_bf16(rows, p[k], groups)
                     for k in ("gate", "up"))

    out, vjp = jax.vjp(lambda f: mellum2._gate_and_up(f, token, p, groups),
                       flat)
    want, want_vjp = jax.vjp(until_pr_42, flat)
    assert_same_bits(out, want)
    (got,), (ref,) = vjp(gs), want_vjp(gs)
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(got, ref)
    # each slot's cotangent rounded before the sum, as a bfloat16 gather's
    # transpose would hand it on
    slots = sum(mellum2.grouped_dot_transposed(
        g.astype(jnp.bfloat16), p[k].astype(jnp.bfloat16), groups)
        for g, k in zip(gs, ("gate", "up")))
    rounded = jnp.zeros_like(flat).at[token].add(
        slots.astype(jnp.bfloat16).astype(jnp.float32), mode="drop")
    assert np.max(np.abs(rounded - ref)) > 1e-2
    np.testing.assert_allclose(rounded, ref, rtol=0.02, atol=0.1)


@pytest.mark.parametrize("name", TOKEN_MODELS)
def test_plan_lists_what_the_checkpoints_keep(name):
    """The journal's ``fwd_bwd`` event: every token model names the expert
    layer's kept dispatch under ``remat`` (the linear-attention model its
    chunk inverse beside it) and nothing without."""
    from matcha_tpu.models import qwen3_next
    from matcha_tpu.train.state import fwd_bwd_plan

    sizes, _ = _token_cases()[name]
    keeps = fwd_bwd_plan(select_model(name, "tokens", sizes=sizes,
                                      remat=True), 2)["remat_keeps"]
    assert keeps[:2] == ["moe_rows", "moe_layout"] == list(mellum2.MOE_KEPT)
    assert keeps[2:] == ([qwen3_next.KEPT] if name == "qwen3_next" else [])
    assert "remat_keeps" not in fwd_bwd_plan(
        select_model(name, "tokens", sizes=sizes), 2)


# ------------------------------------------- rows that hold no slot (PR 43)

def _with_products(monkeypatch, change):
    """``mellum2``'s three grouped products, each behind ``change(product,
    form)``; returns how often each was traced."""
    calls = dict.fromkeys(("grouped_dot", "grouped_dot_transposed",
                           "grouped_outer"), 0)
    for name in calls:
        product = getattr(grouped, name)

        def counted(*args, name=name, product=product):
            calls[name] += 1
            return change(product, name)(*args)

        monkeypatch.setattr(mellum2, name, counted)
    return calls


#: what the poisoned products leave on a row in no group: the jitted
#: function's own argument, put here while it is traced, so that one
#: compiled program serves 0, NaN and 1e30
LEFT = {"value": 0.0}


def _poisoned(product, name):
    """The forward forms with every output row past the groups' sum set to
    ``LEFT["value"]``: what a kernel may leave there is anything (0 is what
    ``lax.ragged_dot`` leaves); the weight gradient as it is."""
    if name == "grouped_outer":
        return product

    def poisoned(lhs, weights, groups):
        out = product(lhs, weights, groups)
        in_a_group = jnp.arange(out.shape[0]) < jnp.sum(groups)
        return jnp.where(in_a_group[:, None], out, LEFT["value"])
    return poisoned


def _counted_as_until_pr_43(product, name):
    """``groups`` as ``_experts`` laid them out until PR 43: the rows past
    the last slot count with the last expert."""
    def padded(lhs, other, groups):
        rows = lhs.shape[0]
        groups = groups.at[-1].add(rows - jnp.sum(groups))
        return product(lhs, other, groups)
    return padded


BATCH = 6  # 192 tokens: 384 rows, three tiles of 128


def _layer_sizes(width):
    return sizes_of(hidden=128, expert_width=width, experts_held=[0, 1, 2, 3])


def _given_routing(routing, width=128):
    """An expert layer on the TPU's branch with its routing given (as in
    ``test_kept_rows_equal_the_form_until_pr_42_on_the_bf16_path``):
    (weights, ``x``, ``w_held``, ``took``, a probe).  ``router``: what the
    router chooses (about half the rows hold a slot); ``spill``: every token
    takes all four experts held, twice the rows there are, so the overflow
    branch runs; ``none``: no token chooses an expert held."""
    z = _layer_sizes(width)
    tokens = BATCH * SEQ
    rng = jax.random.split(jax.random.PRNGKey(43), 6)
    p = {k: 0.3 * jax.random.normal(key, shape) for key, (k, shape) in zip(
        rng, {"router": (128, 8), "gate": (4, 128, width),
              "up": (4, 128, width), "down": (4, width, 128)}.items())}
    x, probe = (jax.random.normal(key, (BATCH, SEQ, 128)) for key in rng[4:])
    w, sel = mellum2._route(p, x.reshape(tokens, 128), z)
    chosen = sel[:, :, None] == jnp.arange(4)[None, None, :]
    w_held = jnp.sum(jnp.where(chosen, w[:, :, None], 0.0), axis=1)
    took = jnp.any(chosen, axis=1)
    if routing == "spill":
        took, w_held = jnp.ones_like(took), jnp.full_like(w_held, 0.25)
    elif routing == "none":
        took, w_held = jnp.zeros_like(took), jnp.zeros_like(w_held)
    return p, x, w_held, took, probe


@functools.lru_cache(maxsize=None)
def _layer_and_gradients(width, products):
    """``f(weights, x, w_held, took, probe, left=0.0)`` -> ((the layer's
    output under ``remat``, the rows' counters), the probed output's
    gradients by every weight, by ``x`` and by ``w_held``), jitted once a
    width and a set of ``products`` (the name of what the test has
    ``mellum2`` call, in force when the first call traces it): the routing
    is an argument, and so is what ``_poisoned`` products leave."""
    z = _layer_sizes(width)

    def probed(p, x, w_held, took, probe, left=0.0):
        LEFT["value"] = left
        try:
            y, counters = mellum2.checkpointed(True)(
                lambda *a: mellum2._experts(*a, took, z))(p, x, w_held)
        finally:
            LEFT["value"] = 0.0
        return jnp.sum(y * probe), (y, counters)

    grad = jax.jit(jax.value_and_grad(probed, argnums=(0, 1, 2),
                                      has_aux=True))

    def layer_and_gradients(*layer):
        (_, out), grads = grad(*layer)
        return out, grads
    return layer_and_gradients


def _assert_finite(tree):
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        assert np.all(np.isfinite(leaf)), str(path)


@pytest.mark.parametrize("value", [np.nan, 1e30], ids=["nan", "1e30"])
def test_what_a_kernel_leaves_on_a_row_in_no_group_reaches_nothing_kept(
        value, monkeypatch):
    """The bfloat16 path at widths that tile (the kernels, under the
    interpreter, which itself leaves NaN on a tile no step visits): with
    every output row past the groups' sum of both forward forms set to NaN,
    or to 1e30, the layer's output and its gradients by every weight, by
    ``x`` and by ``w_held`` are finite and, to the bit, those with zeros
    there.  (6 s a case.)"""
    monkeypatch.setattr(mellum2, "_one_bf16_pass", lambda: True)
    layer = _given_routing("router")
    assert mellum2.expert_products(_layer_sizes(128), BATCH * SEQ, 1, True,
                                   1)["kernel_sites"] == 6
    calls = _with_products(monkeypatch, _poisoned)
    clean, poisoned = (_layer_and_gradients(128, "poisoned")(*layer, left)
                       for left in (0.0, value))
    # gate, up, down and the recomputed three; their data gradients (traced
    # by the first of the two cases)
    assert (calls["grouped_dot"], calls["grouped_dot_transposed"]) in (
        (6, 3), (0, 0))
    (_, counters), _ = clean
    assert counters["moe_rows_multiplied"] < counters["moe_rows_computed"]
    _assert_finite(clean)
    assert_same_bits(poisoned, clean)


@pytest.mark.parametrize("name", TOKEN_MODELS)
def test_a_token_models_layer_keeps_nothing_of_a_row_in_no_group(
        name, monkeypatch):
    """Each token model's expert layer as its block wraps it under
    ``remat``, on the bfloat16 path at the test's sizes (``lax.ragged_dot``:
    they do not tile): NaN on every output row past the groups' sum changes
    no bit of the output or of the gradients by the layer's weights (the
    router's, which ``w_held``'s reaches, among them) and its input.
    (4-8 s a case.)"""
    monkeypatch.setattr(mellum2, "_one_bf16_pass", lambda: True)
    layer, p, h = expert_layer_of(name)
    probe = jax.random.normal(jax.random.PRNGKey(10), h.shape)

    def probed(p, h, left):
        LEFT["value"] = left
        try:
            # (a function of its own: a checkpoint's trace is cached by the
            # function it wraps, and with it the products it called)
            y = mellum2.checkpointed(True)(lambda p, h: layer(p, h))(p, h)
        finally:
            LEFT["value"] = 0.0
        return jnp.sum(y * probe)

    calls = _with_products(monkeypatch, _poisoned)
    grad = jax.jit(jax.value_and_grad(probed, argnums=(0, 1)))
    clean, poisoned = grad(p, h, 0.0), grad(p, h, np.nan)
    assert calls["grouped_dot"] == 6 and calls["grouped_outer"] == 3
    _assert_finite(clean)
    assert float(jnp.max(jnp.abs(clean[1][0]["router"]))) > 0
    assert_same_bits(poisoned, clean)


@pytest.mark.parametrize("routing", ["router", "spill", "none"])
@pytest.mark.parametrize("path", ["kernels", "ragged_dot"])
def test_rows_in_no_group_equal_rows_counted_with_the_last_expert(
        path, routing, monkeypatch):
    """Routing given: the layer's output and its gradients by every weight,
    by ``x`` and by ``w_held`` are, to the bit, those of the layout until
    PR 43 (``_counted_as_until_pr_43``: the rows past the last slot with the
    last expert, read at weight 0), on the kernels and where the widths do
    not tile; also where the slots spill (every row holds one, and the
    overflow branch runs) and where no token chooses an expert held.  And
    the counters: the rows laid out, and of them those in a row tile a
    group touches (all of them where ``lax.ragged_dot`` multiplies).
    (3-8 s a case.)"""
    monkeypatch.setattr(mellum2, "_one_bf16_pass", lambda: True)
    width = 128 if path == "kernels" else 24
    layer = _given_routing(routing, width)
    z, took = _layer_sizes(width), layer[3]
    tokens, held = took.shape
    rows = mellum2.moe_capacity(tokens, z)
    assert rows == 2 * tokens == 3 * (tm := 128)
    plan = mellum2.expert_products(z, tokens, 1, True, 1)
    assert plan["kernel_sites"] == (6 if path == "kernels" else 0)
    assert plan["empty_rows"] == "in no group"
    now = _layer_and_gradients(width, "as they are")(*layer)
    (y, counters), _ = now
    slots = int(jnp.sum(took))
    past = held * tokens if slots > rows else 0
    assert (slots > rows) == (routing == "spill")
    assert counters["moe_rows_computed"] == rows + past
    in_tiles = -(-min(slots, rows) // tm) * tm
    assert counters["moe_rows_multiplied"] == past + (
        in_tiles if path == "kernels" else rows)
    if routing == "router":
        assert 0 < in_tiles < rows
    elif routing == "spill":  # every row holds a slot
        assert in_tiles == rows
    else:
        assert in_tiles == 0 and not np.any(np.asarray(y))
    _assert_finite(now)
    _with_products(monkeypatch, _counted_as_until_pr_43)
    assert_same_bits(now, _layer_and_gradients(width, "until PR 43")(*layer))
