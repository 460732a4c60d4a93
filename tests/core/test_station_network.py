"""The Caltech ACN site: a station whose constraints overlap (each port is
wired across two transformer lines), built by ``station.constraint_network``;
the Eq. 5 scale on it, and ``flatten_tree`` kept bit for bit on the trees."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ChargaxEnv, EnvConfig, station
from repro.core.transition import constraint_scale
from repro.utils import replace

jax.config.update("jax_platform_name", "cpu")

LAY = station.ARCHITECTURES["caltech_acn54"]()
LINES, PODS = LAY.member[:3], LAY.member[3:]
SEEDS = range(6)


def _currents(seed: int, n: int, amps: float = 32.0) -> np.ndarray:
    """Seeded signed currents with some ports idle, as under a mixed policy."""
    rng = np.random.default_rng(seed)
    return (rng.uniform(-amps, amps, n) * (rng.random(n) < 0.8)).astype(np.float32)


def test_caltech_arrays():
    assert (LAY.n_evse, LAY.n_nodes) == (54, 5)
    # every port on exactly two of the three lines, 36 ports a line
    np.testing.assert_array_equal(LINES.sum(axis=0), 2.0)
    np.testing.assert_array_equal(LINES.sum(axis=1), 36.0)
    # the line pairs AB, BC, CA hold 18 ports each
    pairs = {tuple(LINES[:, j]) for j in range(54)}
    assert len(pairs) == 3
    assert all(sum(tuple(LINES[:, j]) == p for j in range(54)) == 18 for p in pairs)
    # two pods of 8 ports, disjoint, each split over all three pairs
    np.testing.assert_array_equal(PODS.sum(axis=1), 8.0)
    assert PODS.sum(axis=0).max() == 1.0
    for pod in PODS:
        assert len({tuple(LINES[:, j]) for j in np.flatnonzero(pod)}) == 3
    np.testing.assert_allclose(LAY.node_limit, [416.4] * 3 + [80.0] * 2, atol=0.05)
    np.testing.assert_allclose(LAY.node_limit[0], 150e3 / (np.sqrt(3) * 208.0), rtol=1e-6)
    np.testing.assert_allclose(LAY.evse_max_power_kw, 6.656, rtol=1e-6)
    assert LAY.evse_is_dc.sum() == 0
    # the transformer counts once on a port's path, though two lines hold it
    np.testing.assert_allclose(LAY.evse_path_eff, 0.98 * 0.95, rtol=1e-7)
    assert LAY.battery_node is None


@pytest.mark.parametrize("seed", SEEDS)
def test_eq5_invariant_on_overlapping_constraints(seed):
    budget = LAY.node_limit * LAY.node_eff
    cur = _currents(seed, 54) * 4.0  # far past every line and pod
    scale, excess = constraint_scale(jnp.asarray(cur), jnp.asarray(LAY.member), jnp.asarray(budget))
    scaled = cur * np.asarray(scale)
    assert float(excess) > 0.0
    assert np.all(LAY.member @ np.abs(scaled) <= budget * (1 + 1e-6))
    assert np.all(np.abs(scaled) <= np.abs(cur)) and np.all(scaled * cur >= 0)


@pytest.mark.parametrize("seed", SEEDS)
def test_constraint_scale_matches_a_loop(seed):
    budget = (LAY.node_limit * LAY.node_eff).astype(np.float32)
    cur = _currents(seed, 54, amps=64.0)
    scale, excess = constraint_scale(jnp.asarray(cur), jnp.asarray(LAY.member), jnp.asarray(budget))
    want, worst = np.ones(54), 0.0
    for h in range(LAY.n_nodes):
        held = np.flatnonzero(LAY.member[h])
        load = float(np.abs(cur[held].astype(np.float64)).sum())
        worst = max(worst, load - float(budget[h]))
        for j in held:
            want[j] = min(want[j], min(1.0, float(budget[h]) / load))
    np.testing.assert_allclose(np.asarray(scale), want, rtol=1e-6)
    np.testing.assert_allclose(float(excess), max(worst, 0.0), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("seed", SEEDS)
def test_magnitude_load_bounds_the_phasor_line_current(seed):
    """Line A carries ``I_AB - I_CA`` (currents of the ports on each pair,
    at the pair's voltage angle); its magnitude is at most the load Eq. 5
    counts on line A, ``sum |I|`` over the ports of both pairs."""
    cur = np.abs(_currents(seed, 54))
    angle = {"AB": 30.0, "BC": -90.0, "CA": 150.0}  # line-to-line voltages, positive sequence
    pair = [station.CALTECH_PHASE_PAIRS[j % 3] for j in range(54)]
    i_pair = {p: sum(cur[j] for j in range(54) if pair[j] == p) * np.exp(1j * np.deg2rad(a)) for p, a in angle.items()}
    phasor = {"A": i_pair["AB"] - i_pair["CA"], "B": i_pair["BC"] - i_pair["AB"], "C": i_pair["CA"] - i_pair["BC"]}
    load = LINES @ cur
    for row, line in enumerate("ABC"):
        assert abs(phasor[line]) <= load[row] * (1 + 1e-6)
        assert np.all(LINES[row, [j for j in range(54) if line in pair[j]]] == 1.0)


def _dfs_flatten(root, battery=None):
    """The tree flattening as a plain DFS: each node's leaves, in preorder,
    and each leaf's efficiency product from the root."""
    leaves, nodes, rows, path = [], [], [], []

    def dfs(n, eff):
        if isinstance(n, station.EVSE):
            leaves.append(n)
            path.append(eff * n.efficiency)
            return [len(leaves) - 1]
        nodes.append(n)
        rows.append(None)
        i, mine = len(rows) - 1, []
        for c in n.children:
            mine += dfs(c, eff * n.efficiency)
        rows[i] = mine
        return mine

    dfs(root, 1.0)
    member = np.zeros((len(nodes), len(leaves)), np.float32)
    for i, r in enumerate(rows):
        member[i, r] = 1.0
    f32 = lambda xs: np.array(xs, np.float32)  # noqa: E731
    return {
        "member": member,
        "node_limit": f32([n.max_current for n in nodes]),
        "node_eff": f32([n.efficiency for n in nodes]),
        "evse_voltage": f32([x.voltage for x in leaves]),
        "evse_max_current": f32([x.max_current for x in leaves]),
        "evse_eff": f32([x.efficiency for x in leaves]),
        "evse_path_eff": f32(path),
        "evse_is_dc": f32([float(x.is_dc) for x in leaves]),
    }


TREES = sorted(k for k in station.ARCHITECTURES if k != "caltech_acn54")


@pytest.mark.parametrize("arch", TREES)
def test_flatten_tree_arrays_unchanged(arch, monkeypatch):
    lay = station.ARCHITECTURES[arch]()
    monkeypatch.setattr(station, "flatten_tree", _dfs_flatten)
    want = station.ARCHITECTURES[arch]()
    for name, arr in want.items():
        got = getattr(lay, name)
        assert got.dtype == arr.dtype and got.shape == arr.shape
        assert got.tobytes() == arr.tobytes(), name
    assert lay.battery_node == 0


@pytest.mark.parametrize(
    "constraints",
    [
        [station.Constraint("a", 10.0, [0]), station.Constraint("a", 10.0, [1])],
        [station.Constraint("a", 10.0, [0, 2])],
        [
            station.Constraint("a", 10.0, [0], 0.98, equipment="t"),
            station.Constraint("b", 10.0, [1], 0.97, equipment="t"),
        ],
    ],
    ids=["repeated_name", "port_out_of_range", "equipment_two_efficiencies"],
)
def test_constraint_network_rejects(constraints):
    with pytest.raises(ValueError):
        station.constraint_network([station.ac_evse(), station.ac_evse()], constraints)


def test_battery_needs_a_battery_node():
    with pytest.raises(ValueError, match="battery"):
        ChargaxEnv(EnvConfig(architecture="caltech_acn54"))
    # on a tree the battery column sits on the root row
    member = np.asarray(ChargaxEnv(EnvConfig()).default_params.member)
    np.testing.assert_array_equal(member[:, -1], [1.0, 0.0, 0.0])


def test_env_steps_hold_every_line_and_pod():
    env = ChargaxEnv(EnvConfig(architecture="caltech_acn54", battery=False, scenario="work"))
    params = env.default_params
    assert (env.num_action_heads, env.obs_dim) == (55, 441)
    np.testing.assert_array_equal(np.asarray(params.member)[:, -1], 0.0)
    budget = LAY.node_limit * LAY.node_eff
    step = jax.jit(env.step)
    _, state = env.reset(jax.random.key(0), params)
    state = replace(state, t=jnp.int32(8 * 12))  # 08:00, as the garage fills
    top = jnp.full((55,), 2 * env.config.discretization, jnp.int32)  # every port at its maximum
    bound = 0
    for k in jax.random.split(jax.random.key(1), 24):
        ts = step(k, state, top, params)
        applied = np.asarray(ts.state.evse_current)
        assert np.all(LAY.member @ np.abs(applied) <= budget * (1 + 1e-6))
        bound += float(ts.info["constraint_excess"]) > 0.0
        state = ts.state
    assert bound > 0
