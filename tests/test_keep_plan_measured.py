"""The room of a recomputed stack's budget from the compiler's account of the
step (PR 72, `models/layers.py:keep_plan` and `_measured_plan`).

The static plan stands wherever it declines nothing or no account can be
had (the CPU of these tests has none: an account is INJECTED here through
`assume_memory_limit(account=)`, and `_step_peak`, which makes the real one,
is driven on the CPU's own compiler once).  Where it declines a name, the
declined names are offered what the compiled step leaves of the limit, in
`KEPT_NAMES`' order and whole; the step keeping them is compiled too and has
to fit; the outcome is remembered beside the compiled programs.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import evabyte, layers, phi4flash
from ray_tpu.models.layers import KEPT_NAMES, named
from ray_tpu.ops.flash_attention import KEPT_RESIDUALS
from ray_tpu.util import tracing

E = 128
UNIT = 4 * 16 * E * 4
# what the toy stack marks over its three layers, in `KEPT_NAMES`' order
MARKED = {"attention/out": 3 * UNIT, "attention/qkv": 9 * UNIT,
          "ffn/hidden": 6 * UNIT}
LIMIT = 1000 * UNIT
BUDGET = int(LIMIT * (1 - layers._HEADROOM))
# the training state under which the toy stack's static sum (6 units kept
# already, 20 of reserve) has room for its first name alone
SOME = BUDGET - 31 * UNIT
COUNTERS = ("remat.room_measured", "remat.plan_recorded_hit",
            "remat.measured_peak_bytes", "remat.names_admitted")


def toy_stack(n_layer=3):
    """A layer that marks three names of 1, 3 and 2 units of (4, 16, E)
    float32 and a kernel-shaped residual; ``n_layer`` calls of it."""
    def layer(x, w):
        a = named(jnp.tanh(x @ w), "attention/out")
        b = named(jnp.concatenate([a * a, a + 1, a - 1], -1), "attention/qkv")
        c = named(b[..., :2 * E] * 2, "ffn/hidden")
        lse = checkpoint_name(jnp.sum(c, -1).reshape(-1, 16, 1),
                              KEPT_RESIDUALS[1])
        return x + a + b[..., :E] + c[..., :E] + lse.reshape(4, 16, 1), None

    return layer, [(jnp.ones((4, 16, E)), jnp.ones((E, E)))] * n_layer


class Account:
    """A compiler's account of the step, made up: the static plan's peak is
    ``base`` and a kept name costs its bytes times ``cost`` (1: a value
    costs what it holds); the compiler makes ``remade`` instructions again
    by itself whatever is kept, and ``squeezed[name]`` more where that name
    is.  Remembers what it was asked."""

    def __init__(self, base, cost=None, raises=(), remade=0, squeezed=None):
        self.base, self.cost, self.raises = base, cost or {}, raises
        self.remade, self.squeezed = remade, squeezed or {}
        self.asked = []

    def __call__(self, forced):
        (names,) = forced.values()
        self.asked.append(names)
        if set(names) & set(self.raises):
            raise RuntimeError("Used 18.60G of 15.75G hbm")
        return (self.base + sum(int(MARKED[n] * self.cost.get(n, 1))
                                for n in names),
                self.remade + sum(self.squeezed.get(n, 0) for n in names))


@pytest.fixture
def records(tmp_path, monkeypatch):
    """The plans' records under a directory of the test's own."""
    monkeypatch.setattr(layers, "compile_cache_dir", lambda: str(tmp_path))
    layers._on_trial.clear()
    yield tmp_path / "keep_plans"
    layers._on_trial.clear()


def plan_under(account, state=BUDGET, limit=LIMIT, stack=None):
    """The toy stack's plan with ``state`` bytes of training state told: as
    much as the budget by default, so the static plan declines every
    name."""
    layer, calls = stack or toy_stack()
    with layers.assume_memory_limit(limit, account=account), \
            layers._telling(state_bytes=state):
        return layers.keep_plan(layer, calls)


def greedy(room):
    """The names of `MARKED` that ``room`` bytes admit, whole and in
    order."""
    names = []
    for name in KEPT_NAMES:
        if 0 < MARKED.get(name, 0) <= room:
            names.append(name)
            room -= MARKED[name]
    return tuple(names)


@pytest.mark.parametrize("room", [
    -5 * UNIT, 0, 3 * UNIT - 1, 3 * UNIT, 6 * UNIT, 9 * UNIT - 1, 9 * UNIT,
    12 * UNIT, 18 * UNIT - 1, 18 * UNIT, 500 * UNIT])
def test_the_declined_names_are_offered_the_measured_room(room, records):
    """What the compiled step leaves of the budget admits the declined
    names in `KEPT_NAMES`' order, whole, one that does not fit skipped and
    the next tried: up to the room and never past it."""
    account = Account(base=BUDGET - room)
    plan = plan_under(account)
    assert plan["room"] < 0 and plan["measured"] and not plan["recorded"]
    assert plan["measured_room"] == room
    assert plan["names"] == plan["admitted"] == greedy(room)
    assert plan["bytes_kept"] == sum(MARKED[n] for n in plan["names"]) \
        <= max(room, 0)
    assert plan["declined"] == tuple(
        n for n in MARKED if n not in plan["names"])
    # the static plan's step, then the one keeping what was admitted
    assert account.asked == [(), plan["names"]][:1 + bool(plan["names"])]
    assert plan["peak"] == BUDGET - room + plan["bytes_kept"] <= max(
        BUDGET, BUDGET - room)


@pytest.mark.parametrize("cost, raises, squeezed, kept", [
    # attention/qkv costs twice its bytes: given back, the first stays
    ({"attention/qkv": 2}, (), {}, ("attention/out",)),
    # every name dearer than it looks: one after the other given back
    ({"attention/out": 5, "attention/qkv": 2}, (), {}, ()),
    # the compiler refuses the step keeping qkv
    ({}, ("attention/qkv",), {}, ("attention/out",)),
    ({}, ("attention/out",), {}, ()),
    # a name cheaper than its bytes changes nothing: one offer, one check
    ({"attention/qkv": 0.5}, (), {}, ("attention/out", "attention/qkv")),
    # under the budget only because the compiler made an instruction again
    # itself that it did not under the static plan: given back as well
    ({}, (), {"attention/qkv": 1}, ("attention/out",)),
    ({}, (), {"attention/out": 2}, ()),
])
def test_a_plan_that_reads_over_the_limit_gives_back_its_last_name(
        cost, raises, squeezed, kept, records):
    """Room for `attention/out` and `attention/qkv` by their bytes; the
    step keeping them has to read under the budget itself, and no more
    squeezed by the compiler than the static plan's (which it made 3
    instructions again for)."""
    account = Account(base=BUDGET - 12 * UNIT, cost=cost, raises=raises,
                      remade=3, squeezed=squeezed)
    plan = plan_under(account)
    assert plan["names"] == plan["admitted"] == kept
    assert plan["measured_room"] == 12 * UNIT
    assert plan["peak"] <= BUDGET
    tried = [(), ("attention/out", "attention/qkv"), ("attention/out",)]
    assert account.asked == tried[:len(account.asked)]
    assert len(account.asked) == 1 + 2 - len(kept) + bool(kept)
    # what is remembered is the plan that was seen to fit
    (record,) = records.iterdir()
    assert tuple(json.loads(record.read_text())["names"]) == kept


def _raising(forced):
    raise RuntimeError("no compiler here")


@pytest.mark.parametrize("account", [None, lambda forced: None, _raising],
                         ids=["no_account", "account_says_none", "raises"])
@pytest.mark.parametrize("state", [BUDGET, SOME],
                         ids=["declines_all", "declines_some"])
def test_without_an_account_the_static_plan_stands(account, state, records):
    """No account, one that says nothing and one that raises leave the
    static plan to the last key, and nothing is recorded."""
    layer, calls = toy_stack()
    plan = plan_under(account, state)
    static = layers.keep_plan(layer, calls, room=plan["room"])
    assert plan["declined"] and not plan["measured"]
    for key in ("names", "declined", "bytes_kept", "room", "already",
                "reserve", "marked"):
        assert plan[key] == static[key], key
    assert "measured_room" not in plan and plan["peak"] == 0
    assert not records.exists()


def test_a_static_plan_that_keeps_all_is_not_measured(records):
    """Nothing declined: nothing is compiled and nothing recorded."""
    account = Account(base=0)
    plan = plan_under(account, state=0)
    assert plan["names"] == tuple(MARKED) and plan["declined"] == ()
    assert not plan["measured"] and account.asked == []
    assert not records.exists()
    # a room given directly is that plan and no other, measured or not
    layer, calls = toy_stack()
    with layers.assume_memory_limit(LIMIT, account=account), \
            layers._telling(state_bytes=BUDGET):
        plan = layers.keep_plan(layer, calls, room=3 * UNIT)
    assert plan["names"] == ("attention/out",) and account.asked == []


def test_a_static_plan_that_keeps_some_keeps_them_under_the_account(records):
    """The static plan's names are never given back, and the admitted ones
    come behind them in `KEPT_NAMES`' order."""
    layer, calls = toy_stack()
    static = plan_under(None, SOME)
    assert static["names"] == ("attention/out",) and 3 * UNIT <= \
        static["room"] < 9 * UNIT
    # 7 units left with the static plan's 3 kept: qkv's 9 do not fit
    account = Account(base=BUDGET - 10 * UNIT)
    plan = plan_under(account, SOME)
    assert plan["measured_room"] == 7 * UNIT
    assert plan["names"] == ("attention/out", "ffn/hidden")
    assert plan["admitted"] == ("ffn/hidden",)
    assert account.asked == [("attention/out",),
                             ("attention/out", "ffn/hidden")]
    # over the budget under its own plan (a step the guess let through):
    # nothing is admitted, nothing is taken away
    account = Account(base=BUDGET + UNIT)
    plan = plan_under(account, SOME, limit=LIMIT + 1)
    assert plan["names"] == ("attention/out",) and plan["admitted"] == ()
    assert plan["measured"] and plan["measured_room"] < 0


# -- the record ---------------------------------------------------------------

def test_the_outcome_is_recorded_and_found(records):
    """Measured once: a second trace finds the record, compiles nothing and
    keeps the same names; the record holds the names and both peaks."""
    account = Account(base=BUDGET - 12 * UNIT)
    first = plan_under(account)
    (record,) = records.iterdir()
    assert json.loads(record.read_text()) == {
        "names": ["attention/out", "attention/qkv"],
        "static_peak_bytes": BUDGET - 12 * UNIT, "peak_bytes": BUDGET}
    again = Account(base=0)         # would admit everything, if asked
    found = plan_under(again)
    assert again.asked == [] and found["recorded"] and found["measured"]
    for key in ("names", "declined", "bytes_kept", "admitted", "peak",
                "measured_room"):
        assert found[key] == first[key], key
    assert list(records.iterdir()) == [record]


@pytest.mark.parametrize("changed", ["limit", "state", "depth", "sources",
                                     "versions", "mesh"])
def test_a_record_under_another_key_is_ignored(changed, records, monkeypatch):
    """What the outcome depends on is in the key: another limit, state,
    stack, source, compiler or mesh measures anew and leaves the first
    record where it is."""
    plan_under(Account(base=BUDGET - 12 * UNIT))
    (record,) = records.iterdir()
    kw = {}
    if changed == "limit":
        kw["limit"] = LIMIT + 64
    elif changed == "state":
        kw["state"] = BUDGET + 64
    elif changed == "depth":
        kw["stack"] = toy_stack(4)
    elif changed == "sources":
        monkeypatch.setattr(layers, "_sources_digest", lambda: "another")
    elif changed == "versions":
        monkeypatch.setattr(layers, "_versions", lambda: ["0", "0", "0"])
    account = Account(base=0)
    if changed == "mesh":
        from ray_tpu.parallel.context import use_mesh
        from ray_tpu.parallel.sharding import ShardingConfig
        with use_mesh(ShardingConfig().build_mesh(jax.devices()[:1])):
            plan = plan_under(account, **kw)
    else:
        plan = plan_under(account, **kw)
    assert account.asked and not plan["recorded"]
    assert len(list(records.iterdir())) == 2 and record.exists()


def _compile_reported(refused):
    """jax reports the end of a compile of the step, as
    `dispatch.log_elapsed_time` does: also of one that raised."""
    def report():
        jax.monitoring.record_event_duration_secs(
            layers._COMPILE_EVENT, 1.0, fun_name="jit(train_step)")

    if not refused:
        return report()
    try:
        raise RuntimeError("RESOURCE_EXHAUSTED: Used 16.1G of 15.75G hbm")
    except RuntimeError:
        report()


@pytest.mark.parametrize("refused", [False, True])
def test_a_record_whose_plan_the_compiler_refuses_is_deleted(refused,
                                                             records):
    """A record that served a plan is on trial until the step compiles: a
    compile that ends well bears it out, one that raises deletes it and the
    next trace measures anew."""
    plan_under(Account(base=BUDGET - 12 * UNIT))
    (record,) = records.iterdir()
    assert not layers._on_trial         # measured here: seen to fit
    assert plan_under(Account(base=0))["recorded"]
    assert layers._on_trial == {str(record)}
    # another function's compile says nothing of the step's
    jax.monitoring.record_event_duration_secs(
        layers._COMPILE_EVENT, 1.0, fun_name="jit(convert_element_type)")
    assert layers._on_trial == {str(record)}
    _compile_reported(refused)
    assert not layers._on_trial and record.exists() != refused
    account = Account(base=BUDGET - 3 * UNIT)
    plan = plan_under(account)
    assert plan["recorded"] != refused and bool(account.asked) == refused
    assert plan["names"] == (("attention/out",) if refused
                             else ("attention/out", "attention/qkv"))


@pytest.mark.parametrize("spoilt", [
    "not json", json.dumps({"names": ["attention/out"]}),
    json.dumps({"names": ["kda/proj"], "static_peak_bytes": 1,
                "peak_bytes": 2})], ids=["garbage", "no_peaks", "no_mark"])
def test_a_record_that_cannot_be_read_is_deleted(spoilt, records):
    plan_under(Account(base=BUDGET - 12 * UNIT))
    (record,) = records.iterdir()
    record.write_text(spoilt)
    account = Account(base=BUDGET - 3 * UNIT)
    plan = plan_under(account)
    assert account.asked and not plan["recorded"]
    assert plan["names"] == ("attention/out",)
    assert json.loads(record.read_text())["names"] == ["attention/out"]


def test_no_compile_cache_nothing_remembered(monkeypatch):
    """A process that keeps no compilation cache keeps no record either:
    it measures at every trace."""
    monkeypatch.setattr(layers, "compile_cache_dir", lambda: None)
    for _ in range(2):
        account = Account(base=BUDGET - 3 * UNIT)
        plan = plan_under(account)
        assert account.asked and plan["measured"] and not plan["recorded"]


# -- through `train_step`: the counters, the models' gradients ----------------

SMALL = {
    "phi4flash": (phi4flash, phi4flash.PHI4FLASH_TINY, 48),
    "evabyte": (evabyte, evabyte.EVABYTE_TINY, 64),
}


def traced_step(module, cfg, seq, account, limit=1 << 40):
    """The model's step traced under an injected account -> (the lowered
    step, its state and batch, the stacks' plans, the four counters)."""
    cfg = dataclasses.replace(cfg, remat=True, compute_dtype=jnp.float32)
    params = module.init_params(jax.random.PRNGKey(0), cfg)
    optimizer = optax.adamw(1e-3)
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (2, seq + 1), 0, cfg.vocab_size)}
    step = module.make_train_step(cfg, optimizer)
    plans = []
    with layers.assume_memory_limit(limit, plans, account), \
            tracing.timeline_span("train.fit", root=True):
        lowered = jax.jit(step).lower(params, optimizer.init(params), batch)
        counted = {name: tracing.counter(name) for name in COUNTERS}
        declined = tracing.counter("remat.names_declined")
    return lowered, (params, optimizer.init(params), batch), plans, \
        dict(counted, declined=declined)


@pytest.mark.parametrize("name", list(SMALL))
def test_a_models_step_under_the_measured_plan(name, records, monkeypatch):
    """A small phi4 and a small evabyte stack whose static plan has no room
    (a limit of a byte): the account admits every name they mark; the four
    counters count once a traced stack; a second trace is served by the
    record; and the step's result under the measured plan equals the one
    under the static plan to the last bit, parameters and loss: a kept
    value is the value the replay would have made again."""
    module, cfg, seq = SMALL[name]
    _, _, (static,), counted = traced_step(module, cfg, seq, None, limit=1)
    assert static["names"] == () and len(static["declined"]) >= 3
    assert counted == dict.fromkeys(COUNTERS, 0) | {
        "declined": len(static["declined"])}

    peak = 1 << 20
    budget = int((1 << 40) * (1 - layers._HEADROOM))

    def account(forced):
        account.asked.append(forced)
        return peak + len(forced[0]), 0

    account.asked = []
    # one limit for the static sum and the account: a training state as
    # large as the budget leaves the sum no room
    monkeypatch.setattr(layers, "state_bytes", lambda *a: budget)
    lowered, state, (plan,), counted = traced_step(module, cfg, seq, account)
    marked = tuple(n for n in KEPT_NAMES if n in plan["marked"])
    assert plan["names"] == plan["admitted"] == marked
    assert account.asked == [{0: ()}, {0: marked}]
    assert counted == {
        "remat.room_measured": 1, "remat.plan_recorded_hit": 0,
        "remat.measured_peak_bytes": peak + len(marked),
        "remat.names_admitted": len(marked), "declined": 0}
    again, _, (found,), counted = traced_step(module, cfg, seq, _raising)
    assert found["recorded"] and found["names"] == marked
    assert counted == {
        "remat.room_measured": 1, "remat.plan_recorded_hit": 1,
        "remat.measured_peak_bytes": peak + len(marked),
        "remat.names_admitted": len(marked), "declined": 0}
    assert again.as_text() == lowered.as_text()

    bare, state, _, _ = traced_step(module, cfg, seq, None)
    assert bare.as_text() != lowered.as_text()
    want = bare.compile()(*state)
    got = lowered.compile()(*state)
    assert np.isfinite(float(got[2]["loss"]))
    for (path, w), g in zip(jax.tree_util.tree_flatten_with_path(want)[0],
                            jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=jax.tree_util.keystr(path))


def test_a_step_of_two_stacks_measures_each_under_what_the_first_got(
        records, monkeypatch):
    """A step's stacks are numbered as they are traced: the second is
    measured with the first keeping what it ended with, and the counters
    count each stack once."""
    layer, calls = toy_stack()
    x, w = calls[0]

    def objective(params, batch):
        h = batch
        for stack in ("first", "second"):
            kept = layers.checkpoint_layer(
                layer, stack=[(h, params[stack])] * 3)
            for _ in range(3):
                h, _ = kept(h, params[stack])
        return jnp.sum(h), {}

    optimizer = optax.sgd(1e-3)
    params = {"first": w, "second": w}
    step = layers.train_step(objective, optimizer, jnp.float32)
    asked = []

    def account(forced):
        asked.append(dict(forced))
        return BUDGET - 12 * UNIT + sum(
            MARKED[n] for names in forced.values() for n in names), 0

    monkeypatch.setattr(layers, "state_bytes", lambda *a: BUDGET)
    plans = []
    with layers.assume_memory_limit(LIMIT, plans, account), \
            tracing.timeline_span("train.fit", root=True):
        jax.jit(step).lower(params, optimizer.init(params), x)
        counted = {name: tracing.counter(name) for name in COUNTERS}
    first, second = plans
    both = ("attention/out", "attention/qkv")
    assert first["names"] == both and second["names"] == ()
    assert asked == [{0: ()}, {0: both}, {0: both, 1: ()}]
    assert second["measured_room"] == 0 and second["peak"] == BUDGET
    assert counted == {
        "remat.room_measured": 2, "remat.plan_recorded_hit": 0,
        "remat.measured_peak_bytes": 2 * BUDGET, "remat.names_admitted": 2}


# -- the real account, on the CPU's own compiler ------------------------------

def test_the_compiled_account_compiles_the_step_under_the_names_told(
        records, monkeypatch):
    """`_step_peak` on XLA:CPU, whose numbers mean nothing for a chip but
    whose path is the chip's: the step is traced again under the names it
    is told (a trace of its own each time), nothing of that counts on the
    job timeline or among the plans, and the trace it was asked from goes
    on under the plan the account admitted."""
    module, cfg, seq = SMALL["evabyte"]
    cfg = dataclasses.replace(cfg, remat=True, compute_dtype=jnp.float32)
    params = module.init_params(jax.random.PRNGKey(0), cfg)
    optimizer = optax.adamw(1e-3)
    opt_state = optimizer.init(params)
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (2, seq + 1), 0, cfg.vocab_size)}
    step = module.make_train_step(cfg, optimizer)
    assert layers._compiled_account(step, params, opt_state, batch) is None
    asked = []

    def account(forced):
        peak = layers._step_peak(step, (params, opt_state, batch), forced)
        asked.append((forced, peak))
        return peak

    budget = int((1 << 40) * (1 - layers._HEADROOM))
    plans = []
    monkeypatch.setattr(layers, "state_bytes", lambda *a: budget)
    with layers.assume_memory_limit(1 << 40, plans, account), \
            tracing.timeline_span("train.fit", root=True):
        jax.jit(step).lower(params, opt_state, batch)
        counted = {name: tracing.counter(name) for name in COUNTERS}
        layers_kept = tracing.counter("remat.bytes_kept")
    (plan,) = plans                     # the measuring traces' are not told
    marked = tuple(n for n in KEPT_NAMES if n in plan["marked"])
    assert [forced for forced, _ in asked] == [{0: ()}, {0: marked}]
    (_, (bare, _)), (_, (full, remade)) = asked
    assert bare > 0 and bare != full    # another program: the names told
    assert remade == 0                  # XLA:CPU makes nothing again
    assert plan["names"] == marked and plan["peak"] == full
    assert counted["remat.room_measured"] == 1
    assert counted["remat.names_admitted"] == len(marked)
    assert counted["remat.measured_peak_bytes"] == full
    assert layers_kept == plan["bytes_kept"]    # once, not three times
    assert not layers._on_trial
