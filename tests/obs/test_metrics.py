"""MetricsAccumulator: bit-for-bit vs a Python loop, under scan and vmap."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import MetricsAccumulator

jax.config.update("jax_platform_name", "cpu")

NAMES = ("profit", "energy")


def _random_steps(key, t, batch=()):
    return {
        n: jax.random.normal(k, (t,) + batch) * 10.0
        for n, k in zip(NAMES, jax.random.split(key, len(NAMES)))
    }


def test_scan_matches_python_loop_bit_for_bit():
    t = 37
    vals = _random_steps(jax.random.key(0), t)

    acc0 = MetricsAccumulator.create(NAMES, max_names=("profit",))

    def body(acc, i):
        return acc.update({n: v[i] for n, v in vals.items()}), None

    scanned, _ = jax.jit(
        lambda a: jax.lax.scan(body, a, jnp.arange(t))
    )(acc0)

    looped = acc0
    for i in range(t):
        looped = looped.update({n: v[i] for n, v in vals.items()})

    for n in NAMES:
        assert np.asarray(scanned.sums[n]).tobytes() == np.asarray(
            looped.sums[n]
        ).tobytes(), n
    assert np.asarray(scanned.maxes["profit"]).tobytes() == np.asarray(
        looped.maxes["profit"]
    ).tobytes()
    assert float(scanned.count) == t


def test_vmap_lanes_match_independent_loops_bit_for_bit():
    t, b = 11, 4
    vals = _random_steps(jax.random.key(1), t, (b,))
    acc0 = MetricsAccumulator.create(NAMES, batch_shape=(b,))

    def body(acc, i):
        return acc.update({n: v[i] for n, v in vals.items()}), None

    batched, _ = jax.lax.scan(body, acc0, jnp.arange(t))

    for lane in range(b):
        solo = MetricsAccumulator.create(NAMES)
        for i in range(t):
            solo = solo.update({n: v[i, lane] for n, v in vals.items()})
        for n in NAMES:
            assert (
                np.asarray(batched.sums[n])[lane].tobytes()
                == np.asarray(solo.sums[n]).tobytes()
            ), (n, lane)


def test_update_missing_metric_is_an_error():
    acc = MetricsAccumulator.create(("profit",))
    with pytest.raises(KeyError):
        acc.update({"not_profit": jnp.float32(1.0)})


def test_merge_and_since():
    a = MetricsAccumulator.create(NAMES).update({n: jnp.float32(1.0) for n in NAMES})
    b = MetricsAccumulator.create(NAMES).update({n: jnp.float32(2.0) for n in NAMES})
    m = a.merge(b)
    assert float(m.sums["profit"]) == 3.0
    assert float(m.count) == 2.0
    with pytest.raises(ValueError):
        a.merge(MetricsAccumulator.create(("other",)))

    later = b.update({n: jnp.float32(5.0) for n in NAMES})
    delta = later.since(b)
    assert float(delta.sums["profit"]) == 5.0
    assert float(delta.count) == 1.0


def test_flush_totals_means_and_maxes():
    acc = MetricsAccumulator.create(("profit",), max_names=("peak",), batch_shape=(2,))
    acc = acc.update({"profit": jnp.array([1.0, 3.0]), "peak": jnp.array([7.0, 2.0])})
    acc = acc.update({"profit": jnp.array([1.0, 3.0]), "peak": jnp.array([4.0, 9.0])})
    out = acc.flush(means=("profit",))
    assert out["profit"] == pytest.approx(4.0)  # mean over lanes of per-lane sums
    assert out["profit_per_step"] == pytest.approx(2.0)
    assert out["peak_max"] == pytest.approx(9.0)
    assert out["steps"] == pytest.approx(2.0)

    per_lane = acc.flush(reduce_batch=False)
    assert np.allclose(per_lane["profit"], [2.0, 6.0])

