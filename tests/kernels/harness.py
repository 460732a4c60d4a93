"""Kernel-parity test harness (ISSUE 10).

The ONE way kernel tests build environments, random mid-episode states and
action targets — and the ONE assertion that the fused hot path
(``EnvConfig.fused_step`` → ``kernels/chargax_step/ops.fused_transition``)
matches the staged lax pipeline:

    env = harness.make_env(action_mode="delta", allow_v2g=True, dt_minutes=15)
    state = harness.random_state(env, params, key, n_occupied=6)
    te, tb = harness.random_targets(params, key2)
    harness.assert_fused_matches_staged(env, params, state, te, tb)

Bitwise discipline: on the ``ref`` impl (the CPU hot-path default) parity is
EXACT — ``assert_array_equal``, no tolerances — because the fused request
stage runs the staged clips at their natural shapes and only the Eq. 5 load
reduction uses the kernel's padded matmul (0/1 membership, exact-zero
padding lanes).  ``pallas``/``interpret`` impls get fp32 op-reorder
tolerance via :func:`assert_fused_close`.

Hypothesis strategies (:func:`parity_cases`) sweep the four action modes,
dt ∈ {5, 15, 60} minutes, battery on/off and ragged EVSE counts across
station architectures.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

try:  # strategies need hypothesis; the deterministic harness does not
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised in minimal installs
    st = None
    HAVE_HYPOTHESIS = False

from repro.core import ChargaxEnv, EnvConfig, transition
from repro.core.transition import BIG
from repro.kernels.chargax_step import ops as fused_ops
from repro.kernels.chargax_step.ref import PARITY_ATOL, PARITY_RTOL
from repro.utils import replace

# the four canonical action modes of the acceptance criteria
ACTION_MODES: dict[str, dict] = {
    "direct": dict(),
    "delta": dict(action_mode="delta"),
    "v2g": dict(allow_v2g=True),
    "delta_v2g_nobatt": dict(action_mode="delta", allow_v2g=True, battery=False),
}
DT_MINUTES = (5.0, 15.0, 60.0)
# ragged EVSE counts: 16, 16 (two trees), 16 (4x4 nodes), 4
ARCHITECTURES = ("paper_16", "mixed_8_8", "deep_4x4", "kiosk_ac_4")


@functools.lru_cache(maxsize=None)
def make_env(
    mode: str = "direct",
    dt_minutes: float = 5.0,
    architecture: str = "paper_16",
    pad_evse: int = 0,
    pad_nodes: int = 0,
) -> ChargaxEnv:
    """Cached env for a (mode, dt, architecture, padding) cell."""
    return ChargaxEnv(
        EnvConfig(
            dt_minutes=dt_minutes,
            architecture=architecture,
            pad_evse=pad_evse,
            pad_nodes=pad_nodes,
            **ACTION_MODES[mode],
        )
    )


def random_state(env: ChargaxEnv, params, key, n_occupied: int | None = None):
    """Random mid-episode state: ``n_occupied`` plugged cars at random ports
    with random SoC/capacity/deadline/charge-curve and open V2G debt."""
    n = env.n_evse
    if n_occupied is None:
        n_occupied = n // 2
    ks = jax.random.split(key, 9)
    _, state = env.reset(ks[0])
    occ = (jax.random.permutation(ks[8], jnp.arange(n)) < n_occupied).astype(
        jnp.float32
    )
    return replace(
        state,
        occupied=occ,
        soc=jax.random.uniform(ks[1], (n,), minval=0.05, maxval=0.95) * occ,
        cap=(40.0 + 60.0 * jax.random.uniform(ks[2], (n,))) * occ,
        e_remain=jax.random.uniform(ks[3], (n,), minval=0.0, maxval=40.0) * occ,
        t_remain=(jax.random.randint(ks[4], (n,), 1, 100) * occ).astype(jnp.int32),
        rbar=(50.0 + 250.0 * jax.random.uniform(ks[5], (n,))) * occ,
        tau=(0.6 + 0.3 * jax.random.uniform(ks[6], (n,))) * occ,
        user_type=(jax.random.uniform(ks[7], (n,)) < 0.5).astype(jnp.float32) * occ,
        batt_soc=jnp.float32(0.37),
        v2g_debt=jax.random.uniform(ks[0], (n,), maxval=5.0) * occ,
    )


def random_targets(params, key):
    """Signed current targets for every EVSE lane + the battery."""
    n = params.evse_voltage.shape[0]
    k1, k2 = jax.random.split(key)
    te = jax.random.uniform(k1, (n,), minval=-1.0, maxval=1.0) * params.evse_max_current
    tb = jax.random.uniform(k2, (), minval=-1.0, maxval=1.0) * params.batt_max_current
    return te, tb


def random_action(env: ChargaxEnv, key):
    """A uniformly random discrete action for the env's action space."""
    return jax.random.randint(
        key, (env.num_action_heads,), 0, env.num_actions_per_head
    )


def fused_params(params):
    """``params`` with the hoisted kernel pole pack attached (what a
    ``fused_step=True`` env's ``make_params`` produces)."""
    if params.pole is not None:
        return params
    return replace(params, pole=fused_ops.build_pole_params(params))


def staged_transition(env: ChargaxEnv, params, state, te, tb):
    """The staged request → allocate → deliver stages, as env.step runs them."""
    dt = env.config.dt_hours
    applied = transition.request(params, state, te, tb, dt)
    alloc = transition.allocate(params, state, applied)
    return alloc, transition.deliver(params, state, alloc.applied, dt)


def assert_trees_equal(got, want, context: str = ""):
    """Bitwise equality over two pytrees, naming the offending leaf."""
    gl, gt = jax.tree_util.tree_flatten(got)
    wl, wt = jax.tree_util.tree_flatten(want)
    assert gt == wt, f"{context}: tree structures differ\n{gt}\nvs\n{wt}"
    for i, (g, w) in enumerate(zip(gl, wl)):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w), err_msg=f"{context}: leaf {i} of {gt}"
        )


def assert_trees_close(
    got, want, context: str = "", rtol=PARITY_RTOL, atol=PARITY_ATOL
):
    """fp32 op-reorder tolerance over two pytrees (pallas/interpret impls)."""
    gl, gt = jax.tree_util.tree_flatten(got)
    wl, wt = jax.tree_util.tree_flatten(want)
    assert gt == wt, f"{context}: tree structures differ"
    for i, (g, w) in enumerate(zip(gl, wl)):
        np.testing.assert_allclose(
            np.asarray(g),
            np.asarray(w),
            rtol=rtol,
            atol=atol,
            err_msg=f"{context}: leaf {i} of {gt}",
        )


@functools.lru_cache(maxsize=None)
def _parity_fn(env: ChargaxEnv, impl: str):
    """One jitted staged-vs-fused comparator per (env, impl) — params/state
    are traced args, so seed/scenario sweeps reuse one compile."""
    dt = env.config.dt_hours

    def both(params, fp, state, te, tb):
        alloc_s, charged_s = staged_transition(env, params, state, te, tb)
        alloc_f, charged_f = fused_ops.fused_transition(fp, state, te, tb, dt, impl=impl)
        return (alloc_s, charged_s), (alloc_f, charged_f)

    return jax.jit(both)


def assert_fused_matches_staged(env: ChargaxEnv, params, state, te, tb):
    """The harness's central assertion: the fused transition on the CPU
    ``ref`` impl is BIT-IDENTICAL to the staged pipeline on the same
    (params, state, targets) — applied currents, constraint excess, grid
    allocation and the full delivered state."""
    staged, fused = _parity_fn(env, "ref")(params, fused_params(params), state, te, tb)
    assert_trees_equal(fused[0], staged[0], "AllocationResult (fused vs staged)")
    assert_trees_equal(fused[1], staged[1], "ChargeResult (fused vs staged)")


def assert_fused_close(env: ChargaxEnv, params, state, te, tb, *, impl="interpret"):
    """Pallas/interpret impl agrees with the staged pipeline within fp32
    op-reorder tolerance (the MXU dot reassociates the Eq. 5 reduction)."""
    staged, fused = _parity_fn(env, impl)(params, fused_params(params), state, te, tb)
    assert_trees_close(fused[0], staged[0], f"AllocationResult ({impl} vs staged)")
    assert_trees_close(fused[1], staged[1], f"ChargeResult ({impl} vs staged)")


@functools.lru_cache(maxsize=None)
def _step_parity_fn(env: ChargaxEnv):
    fenv = env.with_fused_step(True)

    def both(key, params, fp, state, action):
        return env.step(key, state, action, params), fenv.step(key, state, action, fp)

    return jax.jit(both)


def assert_step_matches(env: ChargaxEnv, params, state, action, key):
    """Full ``env.step`` parity: the ``fused_step=True`` env's TimeStep is
    bit-identical to the staged env's on the same key/state/action."""
    ts_s, ts_f = _step_parity_fn(env)(key, params, fused_params(params), state, action)
    assert_trees_equal(ts_f, ts_s, "TimeStep (fused env.step vs staged)")


# ---------------------------------------------------------------------------
# Golden regression fixtures (tests/kernels/goldens/*.npz; regenerate with
# tools/make_kernel_goldens.py)
# ---------------------------------------------------------------------------
# canonical scenario -> the harness action mode its env needs
GOLDEN_SCENARIOS = {
    "shopping_pv_tou": "direct",
    "v2g_shopping_tou": "v2g",
    "grid_tight_transformer": "direct",
}
GOLDEN_STEPS = 24  # two hours at dt=5min: arrivals, charging, PV, curtailment


def compute_golden(name: str, fused: bool = True) -> dict[str, np.ndarray]:
    """Deterministic short rollout on a canonical scenario → physics digest.

    Fixed keys, max-charge action every step; returns the final state's
    physics-bearing arrays plus the reward sequence and last observation —
    exactly what a refactor that silently changes physics would move.
    """
    from repro import scenarios as scen

    env = make_env(GOLDEN_SCENARIOS[name]).with_fused_step(fused)
    params = scen.make(name).make_params(env)
    _, state = env.reset(jax.random.key(0), params)
    action = jnp.full(
        (env.num_action_heads,), env.num_actions_per_head - 1, jnp.int32
    )

    def body(carry, k):
        ts = env.step(k, carry, action, params)
        return ts.state, (ts.obs, ts.reward)

    keys = jax.random.split(jax.random.key(1), GOLDEN_STEPS)
    state, (obs_seq, reward) = jax.jit(lambda s: jax.lax.scan(body, s, keys))(state)
    return {
        "obs_last": np.asarray(obs_seq[-1]),
        "reward": np.asarray(reward),
        "soc": np.asarray(state.soc),
        "e_remain": np.asarray(state.e_remain),
        "v2g_debt": np.asarray(state.v2g_debt),
        "batt_soc": np.asarray(state.batt_soc),
        "profit_cum": np.asarray(state.profit_cum),
        "energy_delivered": np.asarray(state.energy_delivered),
        "energy_discharged": np.asarray(state.energy_discharged),
    }


# ---------------------------------------------------------------------------
# Hypothesis strategies (only when hypothesis is installed)
# ---------------------------------------------------------------------------
if HAVE_HYPOTHESIS:

    @st.composite
    def parity_cases(draw):
        """(env, params, state, te, tb) across modes × dt × architectures."""
        mode = draw(st.sampled_from(sorted(ACTION_MODES)))
        dt = draw(st.sampled_from(DT_MINUTES))
        arch = draw(st.sampled_from(ARCHITECTURES))
        seed = draw(st.integers(0, 2**31 - 1))
        env = make_env(mode, dt, arch)
        params = env.default_params
        n_occ = draw(st.integers(0, env.n_evse))
        state = random_state(env, params, jax.random.key(seed), n_occ)
        te, tb = random_targets(params, jax.random.key(seed ^ 0x5EED))
        return env, params, state, te, tb
