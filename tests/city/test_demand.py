"""Demand-allocation invariants: conservation, determinism, zero-pop inertness.

The CI sharding job re-runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` so the 2-device
station-axis split is exercised on every push; the device-count-gated test
activates there.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.city import (
    CityParams,
    StationFeatures,
    allocate_demand,
    choice_logits,
    demand_zones,
    layout_xy,
    make_city,
    stream_rate,
)
from repro.utils import stack_pytrees

jax.config.update("jax_platform_name", "cpu")


def _city(population=2000.0, n_stations=4, **kw):
    return make_city(n_stations=n_stations, population=population, **kw)


def _features(n_stations=4, free=6.0):
    return StationFeatures(
        price=jnp.linspace(0.2, 0.5, n_stations),
        occupancy=jnp.linspace(0.0, 0.9, n_stations),
        free_ports=jnp.full((n_stations,), jnp.float32(free)),
    )


def test_conservation_and_nonnegativity():
    city = _city()
    for t in (0, 90, 200):
        stream = stream_rate(city, jnp.int32(3), jnp.int32(t))
        alloc = allocate_demand(stream, city, _features())
        total = float(jnp.sum(alloc.rates) + alloc.overflow)
        np.testing.assert_allclose(total, float(stream), rtol=1e-5)
        assert np.all(np.asarray(alloc.rates) >= 0.0)
        assert float(alloc.overflow) >= 0.0
        np.testing.assert_allclose(float(jnp.sum(alloc.shares)), 1.0, rtol=1e-5)


def test_capacity_clamp_and_overflow():
    """A station absorbs at most its free ports; an over-capacity stream
    produces city-wide overflow (balking drivers), never over-assignment."""
    city = _city(population=50_000.0)
    feats = _features(free=2.0)
    stream = jnp.float32(100.0)  # >> 4 stations x 2 free ports
    alloc = allocate_demand(stream, city, feats)
    assert np.all(np.asarray(alloc.rates) <= 2.0 + 1e-5)
    np.testing.assert_allclose(float(jnp.sum(alloc.rates)), 8.0, rtol=1e-5)
    np.testing.assert_allclose(float(alloc.overflow), 92.0, rtol=1e-5)


def test_zero_population_yields_exact_zero_rates():
    """Not approximately zero — *exactly* 0.0 bits, the property the fleet's
    zero-pop bit-identity (tests/city/test_fleet_city.py) rests on."""
    city = _city(population=0.0)
    stream = stream_rate(city, jnp.int32(0), jnp.int32(100))
    assert float(stream) == 0.0
    alloc = allocate_demand(stream, city, _features())
    assert np.all(np.asarray(alloc.rates) == 0.0)
    assert float(alloc.overflow) == 0.0


def test_allocation_bit_deterministic_under_vmap():
    """The same city/features give bit-identical splits whether allocated
    one-at-a-time or as a vmapped stack (the sweep_layouts access pattern)."""
    cities = [_city(population=p) for p in (800.0, 2000.0, 5000.0)]
    feats = _features()
    stream = jnp.float32(40.0)
    solo = [allocate_demand(stream, c, feats) for c in cities]
    stacked = jax.jit(jax.vmap(lambda c: allocate_demand(stream, c, feats)))(
        stack_pytrees(cities)
    )
    for i, ref in enumerate(solo):
        assert np.array_equal(np.asarray(stacked.rates[i]), np.asarray(ref.rates))
        assert np.array_equal(
            np.asarray(stacked.overflow[i]), np.asarray(ref.overflow)
        )


def test_price_and_queue_shift_shares():
    """Gravity/queue logits point the right way: a pricier or busier station
    attracts a smaller share, all else equal."""
    city = _city(w_dist=0.0)
    base = StationFeatures(
        price=jnp.full((4,), 0.3),
        occupancy=jnp.zeros(4),
        free_ports=jnp.full((4,), 100.0),
    )
    ref = allocate_demand(jnp.float32(10.0), city, base)
    pricey = allocate_demand(
        jnp.float32(10.0), city, base._replace(price=base.price.at[0].add(0.2))
    )
    busy = allocate_demand(
        jnp.float32(10.0), city, base._replace(occupancy=base.occupancy.at[0].set(0.8))
    )
    assert float(pricey.shares[0]) < float(ref.shares[0])
    assert float(busy.shares[0]) < float(ref.shares[0])


def test_choice_logits_shape_and_distance_decay():
    city = _city(w_price=0.0, w_queue=0.0)
    lg = choice_logits(city, _features())
    assert lg.shape == (city.n_zones, city.n_stations)
    # zone 0 is the core; with only distance in play, nearer stations win
    d = jnp.linalg.norm(city.station_xy - city.zone_xy[0], axis=-1)
    order_lg = np.argsort(np.asarray(lg[0]))
    order_d = np.argsort(-np.asarray(d))
    assert list(order_lg) == list(order_d)


def test_layout_and_zone_builders_validate():
    assert layout_xy("ring", 6).shape == (6, 2)
    assert layout_xy("grid", 5).shape == (5, 2)
    assert layout_xy("clustered", 3).shape == (3, 2)
    with pytest.raises(ValueError):
        layout_xy("hexagonal", 4)
    with pytest.raises(ValueError):
        layout_xy("ring", 0)
    xy, frac = demand_zones(4)
    assert xy.shape == (4, 2) and frac.shape == (4,)
    np.testing.assert_allclose(frac.sum(), 1.0, rtol=1e-6)
    with pytest.raises(ValueError):
        demand_zones(0)


def test_make_city_from_scenario_and_overrides():
    city = make_city("city_grid_commuters", n_stations=6)
    assert isinstance(city, CityParams)
    assert city.n_stations == 6
    assert float(city.population) == 2400.0
    np.testing.assert_allclose(float(jnp.sum(city.arrival_profile)), 1.0, rtol=1e-5)
    override = make_city("city_grid_commuters", n_stations=6, population=7.0)
    assert float(override.population) == 7.0
    with pytest.raises(ValueError):
        make_city(layout=np.zeros((3, 2)), n_stations=4)


@pytest.mark.skipif(jax.device_count() < 2, reason="needs a 2-device mesh")
def test_sharded_city_coupled_fleet_matches_unsharded():
    """The stream split must respect the station-axis sharding: a 2-device
    city-coupled rollout reproduces the single-device one (same key)."""
    from repro.core import FleetEnv
    from repro.distributed import env_sharding, sharding
    from repro.launch.mesh import make_data_mesh

    n_dev = jax.device_count()
    archs = ["paper_16", "deep_4x4"] * n_dev
    city = make_city("city_ring_evening", n_stations=len(archs))

    def rollout(fleet, params):
        params = params if params is not None else fleet.default_params
        step = jax.jit(fleet.step)
        _, state = fleet.reset(jax.random.key(0), params)
        rates = []
        for i in range(20):
            a = fleet.sample_action(jax.random.key(1000 + i))
            _, state, r, _, info = step(jax.random.key(i), state, a, params)
            rates.append(np.asarray(info["city/arrival_rate"]))
        return np.stack(rates), np.asarray(state.profit_cum)

    ref_rates, ref_profit = rollout(FleetEnv(archs, city=city, shard=False), None)
    fleet = FleetEnv(archs, city=city)
    mesh = make_data_mesh()
    with jax.sharding.set_mesh(mesh):
        params = env_sharding.place_env_batch(fleet.default_params, mesh)
        got_rates, got_profit = rollout(fleet, params)

    np.testing.assert_allclose(got_rates, ref_rates, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_profit, ref_profit, rtol=1e-5, atol=1e-5)
