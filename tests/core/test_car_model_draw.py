"""The arriving car's model is drawn by compare-and-count over the fleet mix's
CDF and its tables are read by selects over the model rows, and both give the
same bits as ``jax.random.choice`` and ``table[model]`` did: every trajectory
stays what it was, with no gather or ``while`` left in either stage."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scenarios
from repro.core import ChargaxEnv, EnvConfig, FleetEnv
from repro.core import transition
from repro.core.transition import charge_rate, draw_car_model, select_car_row
from repro.envs import AutoReset, VmapWrapper
from repro.obs import enable_trace_annotations
from repro.utils import replace

jax.config.update("jax_platform_name", "cpu")

# the benchmark's paper16_shop configuration: the paper station over four
# shopping scenarios, nested scenario x env
SHOP = ("shopping_flat", "shopping_pv_tou", "shopping_fleet_drift", "real_nl_2024_shopping_tou")
N_KEYS = 1024
N_PORTS = 16
CAR_TABLES = ("car_capacity", "car_tau", "car_dc_kw", "car_ac_kw")


def _mixes(case: str) -> np.ndarray:
    """Two fleet mixes (one per scenario of the nested layout) for ``case``."""
    env = ChargaxEnv(EnvConfig())
    if case == "constant":
        base = np.asarray(env.default_params.car_probs)
        return np.stack([base, np.full(8, 1 / 8, np.float32)])
    if case == "drift":
        table = np.asarray(scenarios.make("shopping_fleet_drift").make_params(env).car_probs)
        return table[[17, 300]]
    if case == "padded":  # a region with fewer models than MAX_CAR_MODELS rows
        return np.array([[0.5, 0.3, 0.2, 0, 0, 0, 0, 0], [0.6, 0.4, 0, 0, 0, 0, 0, 0]], np.float32)
    assert case == "one_model"
    return np.ones((2, 1), np.float32)


def _draws(draw, mixes, layout: str):
    """``draw(key, probs)`` over N_KEYS keys: per port with one shared mix, or
    nested scenario x env x port with each scenario's own mix."""
    keys = jax.random.split(jax.random.key(7), N_KEYS)
    probs = jnp.asarray(mixes)
    if layout == "ports":
        keys = keys.reshape(-1, N_PORTS)
        per_port = jax.vmap(draw, in_axes=(0, None))
        return jnp.stack([jax.vmap(per_port, in_axes=(0, None))(keys, p) for p in probs])
    keys = keys.reshape(len(mixes), -1, N_PORTS)
    per_env = jax.vmap(jax.vmap(draw, in_axes=(0, None)), in_axes=(0, None))
    return jax.vmap(per_env, in_axes=(0, 0))(keys, probs)


@pytest.mark.parametrize("layout", ["ports", "nested"])
@pytest.mark.parametrize("case", ["constant", "drift", "padded", "one_model"])
def test_draw_equals_random_choice(case, layout):
    mixes = _mixes(case)
    want = jax.jit(lambda: _draws(lambda k, p: jax.random.choice(k, p.shape[0], p=p), mixes, layout))()
    got = jax.jit(lambda: _draws(lambda k, p: draw_car_model(k, jnp.cumsum(p)), mixes, layout))()
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    # every model with mass is drawn, none without
    drawn = np.unique(np.asarray(got))
    np.testing.assert_array_equal(drawn, np.flatnonzero(mixes.max(0) > 0))


# a mix with zero-mass rows first, inside and after: its CDF repeats values
TIED_MIX = np.array([0, 0.25, 0, 0.25, 0.5, 0, 0, 0], np.float32)


@pytest.mark.parametrize("u", [0.0, 0.25, 0.5, 0.75, 0.875, 0.9999999])
def test_draw_breaks_ties_as_random_choice(monkeypatch, u):
    """Uniforms that put the draw exactly on a CDF value (``1 - u`` of 1,
    0.75, 0.5, 0.25): random keys almost never land there, so the uniform is
    pinned and both draws see the same value."""
    from jax._src import random as jax_random

    pinned = lambda key, shape=(), dtype=float, *a, **k: jnp.full(shape, u, dtype)
    monkeypatch.setattr(jax_random, "uniform", pinned)  # what choice calls
    monkeypatch.setattr(jax.random, "uniform", pinned)
    key, p = jax.random.key(0), jnp.asarray(TIED_MIX)
    want = jax.random.choice(key, p.shape[0], p=p)
    got = draw_car_model(key, jnp.cumsum(p))
    assert got.dtype == want.dtype
    assert int(got) == int(want)
    assert TIED_MIX[int(got)] > 0


@pytest.fixture(scope="module")
def shop_params():
    env = ChargaxEnv(EnvConfig())
    return env, scenarios.stack_params([scenarios.make(n).make_params(env) for n in SHOP])


@pytest.mark.parametrize("nested", [False, True], ids=["flat", "nested"])
@pytest.mark.parametrize("name", CAR_TABLES)
def test_select_equals_gather(shop_params, name, nested):
    env, stacked = shop_params
    if nested:  # per-scenario tables, per-env per-port models
        table = getattr(stacked, name)
        model = jax.random.randint(jax.random.key(1), (table.shape[0], 64, N_PORTS), 0, table.shape[-1])
        look = lambda f: jax.vmap(jax.vmap(f, in_axes=(None, 0)), in_axes=(0, 0))(table, model)
    else:
        table = getattr(env.default_params, name)
        model = jnp.arange(N_PORTS) % table.shape[0]
        look = lambda f: f(table, model)
    got = jax.jit(lambda: look(select_car_row))()
    want = jax.jit(lambda: look(lambda t, m: t[m]))()
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


def _arrive_cars_gather(params, state, key, rate_extra=None):
    """``transition.arrive_cars`` as it was with ``jax.random.choice`` and
    per-port gathers, kept to hold the select-based form to the same bits."""
    n = state.occupied.shape[0]
    spd = params.arrival_rate.shape[0]
    k_m, k_port = jax.random.split(key)
    n_days = params.arrival_day_scale.shape[0]
    rate = params.arrival_rate[jnp.mod(state.t, spd)] * params.arrival_day_scale[jnp.mod(state.day, n_days)]
    if rate_extra is not None:
        rate = rate + rate_extra
    m = jax.random.poisson(k_m, rate).astype(jnp.int32)
    free = (state.occupied < 0.5) & (params.evse_mask > 0.5)
    n_free = jnp.sum(free.astype(jnp.int32))
    n_arrive = jnp.minimum(m, n_free)
    n_reject = jnp.maximum(m - n_free, 0)
    rank = jnp.cumsum(free.astype(jnp.int32))
    assign = free & (rank <= n_arrive)
    a = assign.astype(jnp.float32)
    probs = (
        params.car_probs
        if params.car_probs.ndim == 1
        else params.car_probs[jnp.mod(state.day, params.car_probs.shape[0])]
    )

    def draw_port(i):
        k_model, k_stay, k_soc0, k_tgt, k_u = jax.random.split(jax.random.fold_in(k_port, i), 5)
        model = jax.random.choice(k_model, probs.shape[0], p=probs)
        z_stay = jax.random.normal(k_stay, ())
        soc0 = jax.random.beta(k_soc0, params.soc0_a, params.soc0_b)
        z_tgt = jax.random.normal(k_tgt, ())
        bern = jax.random.bernoulli(k_u, params.p_time_sensitive)
        return model, z_stay, soc0, z_tgt, bern

    model, z_stay, soc0_raw, z_tgt, bern = jax.vmap(draw_port)(jnp.arange(n))
    cap = params.car_capacity[model]
    tau = params.car_tau[model]
    car_kw = jnp.where(params.evse_is_dc > 0.5, params.car_dc_kw[model], params.car_ac_kw[model])
    rbar = car_kw * 1000.0 / params.evse_voltage
    stay_h = jnp.exp(params.stay_mu_log + params.stay_sigma * z_stay)
    stay_steps = jnp.maximum((stay_h * (spd / 24.0)).astype(jnp.int32), 1)
    soc0 = jnp.clip(soc0_raw, 0.02, 0.95)
    target = jnp.clip(params.target_soc_mu + params.target_soc_std * z_tgt, soc0 + 0.05, 1.0)
    e_req = (target - soc0) * cap
    u = 1.0 - bern.astype(jnp.float32)
    new_state = replace(
        state,
        occupied=state.occupied * (1 - a) + a,
        soc=state.soc * (1 - a) + a * soc0,
        e_remain=state.e_remain * (1 - a) + a * e_req,
        v2g_debt=state.v2g_debt * (1 - a),
        t_remain=jnp.where(assign, stay_steps, state.t_remain),
        rhat=state.rhat * (1 - a) + a * charge_rate(soc0, rbar, tau),
        cap=state.cap * (1 - a) + a * cap,
        rbar=state.rbar * (1 - a) + a * rbar,
        tau=jnp.where(assign, tau, state.tau),
        user_type=state.user_type * (1 - a) + a * u,
        cars_served=state.cars_served + n_arrive.astype(jnp.float32),
        cars_rejected=state.cars_rejected + n_reject.astype(jnp.float32),
    )
    return transition.ArriveResult(new_state, n_arrive, n_reject)


def _day_mismatches(step, sample_action, state, arrive, steps: int) -> tuple[int, int]:
    """Step a batch through ``steps`` steps; before each, run both forms of
    ``arrive_cars`` (mapped over the batch by ``arrive(f, state, key)``) on
    the batch's state with a fresh key. Returns the number of (step, leaf)
    pairs that differed, and the number of cars the select form placed."""

    def body(carry, k):
        s, bad, placed = carry
        k_act, k_arr, k_step = jax.random.split(k, 3)
        got, want = (arrive(f, s, k_arr) for f in (transition.arrive_cars, _arrive_cars_gather))
        differ = jax.tree_util.tree_map(lambda x, y: jnp.any(x != y), got, want)
        bad = bad + sum(jax.tree_util.tree_leaves(differ))
        return (step(k_step, s, sample_action(k_act)), bad, placed + got.n_arrived.sum()), None

    keys = jax.random.split(jax.random.key(11), steps)
    (_, bad, placed), _ = jax.lax.scan(body, (state, 0, 0), keys)
    return bad, placed


@pytest.mark.parametrize("layout", ["nested_shop", "fleet_padded"])
def test_arrive_cars_matches_gather_form_over_a_day(shop_params, layout):
    env, stacked = shop_params
    key = jax.random.key(3)
    if layout == "nested_shop":  # 4 scenarios x 2 envs, nested as the benchmark runs them
        n_scen, per = len(SHOP), 2
        venv = AutoReset(VmapWrapper(env, n_scen * per, num_scenarios=n_scen))
        _, state = venv.reset(key, stacked)
        step = lambda k, s, a: venv.step(k, s, a, stacked).state
        sample_action = venv.sample_action

        def arrive(f, s, k):
            nest = lambda x: x.reshape((n_scen, per) + x.shape[1:])
            keys = jax.random.split(k, n_scen * per).reshape(n_scen, per)
            per_env = jax.vmap(f, in_axes=(None, 0, 0))
            return jax.vmap(per_env)(stacked, jax.tree_util.tree_map(nest, s), keys)

    else:  # a fleet whose smaller stations carry padded, masked lanes
        fleet = FleetEnv(["paper_16", "single_dc_8", "kiosk_ac_4", "deep_2x4"], EnvConfig())
        params = fleet.default_params
        assert float(params.evse_mask.min()) == 0.0
        _, state = fleet.reset(key, params)
        step = lambda k, s, a: fleet.step(k, s, a, params)[1]
        sample_action = fleet.sample_action

        def arrive(f, s, k):
            keys = jax.random.split(k, fleet.n_stations)
            return jax.vmap(f)(params, s, keys)

    run = jax.jit(lambda s: _day_mismatches(step, sample_action, s, arrive, env.config.episode_steps))
    bad, placed = run(state)
    assert int(placed) > 0
    assert int(bad) == 0


# -- compiled form -------------------------------------------------------------
LOOKUP_SCOPES = ("env/draw_model", "env/car_lookup")


def _scoped_ops(hlo: str, scopes) -> list[tuple[str, str]]:
    """(opcode, op_name) of each instruction of a compiled HLO text whose
    op_name holds one of ``scopes``."""
    out = []
    for line in hlo.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        op = re.search(r"= .*? ([a-z][a-z0-9-]*)\(", line)
        if name and op and any(s in name.group(1) for s in scopes):
            out.append((op.group(1), name.group(1)))
    return out


def _is_threefry_loop(op_name: str) -> bool:
    """The CPU lowers the threefry hash of ``jax.random.uniform`` as a rolled
    loop (the TPU unrolls it): random bits, not a search."""
    return re.search(r"jit\(_uniform\)/[^/]*/while$", op_name) is not None


def _step_hlo(venv, params) -> str:
    key = jax.random.key(0)
    _, state = venv.reset(key, params)
    step = jax.jit(lambda k, s, a: venv.step(k, s, a, params))
    prev = enable_trace_annotations(True)
    try:
        return step.lower(key, state, venv.sample_action(key)).compile().as_text()
    finally:
        enable_trace_annotations(prev)


@pytest.mark.parametrize("layout", ["flat", "nested_shop"])
def test_no_gather_or_search_loop_in_draw_or_lookup(shop_params, layout):
    env, stacked = shop_params
    if layout == "flat":  # the default station, one fleet mix
        hlo = _step_hlo(AutoReset(VmapWrapper(env, 2)), env.default_params)
    else:
        hlo = _step_hlo(AutoReset(VmapWrapper(env, 2 * len(SHOP), num_scenarios=len(SHOP))), stacked)
    ops = _scoped_ops(hlo, LOOKUP_SCOPES)
    assert {n for _, n in ops if "env/car_lookup" in n}, "no op carries env/car_lookup"
    assert [n for op, n in ops if op == "while" and not _is_threefry_loop(n)] == []
    gathers = [n for op, n in ops if op == "gather"]
    if layout == "flat":
        assert gathers == []
    else:
        # the scenarios' (365, models) drift tables: one row of the fleet mix
        # per env for the day, outside the per-port draw and lookup
        assert all(n.endswith("/env/draw_cars/env/draw_model/gather") for n in gathers), gathers
