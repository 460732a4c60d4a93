"""In-jit metrics: a pure pytree accumulator for named scalar KPIs.

:class:`MetricsAccumulator` is a NamedTuple of ``{name: array}`` dicts plus
an update counter, so it threads through ``jit``/``vmap``/``lax.scan``
unchanged — domain KPIs (energy delivered, v2g debt, episode return, ...)
accumulate *on device* during the rollout scan and cross to the host exactly
once, at :meth:`MetricsAccumulator.flush`.  No per-step device syncs, no
python-side accounting inside the hot loop.

Accumulation is plain elementwise ``+`` / ``maximum`` in update order, so a
scanned accumulator matches a sequential Python-loop reference bit-for-bit
(``tests/obs/test_metrics.py``), and per-env lanes under ``vmap`` are the
independent per-env loops.

Typical use (what ``repro.envs.LogWrapper(..., metrics=...)`` does)::

    acc = MetricsAccumulator.create(("profit", "energy_delivered"),
                                    batch_shape=(num_envs,))
    def body(acc, info):
        return acc.update({k: info[k] for k in acc.names}), None
    acc, _ = jax.lax.scan(body, acc, infos)
    print(acc.flush(means=("profit",)))    # host boundary: plain floats
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax.numpy as jnp
import numpy as np


class MetricsAccumulator(NamedTuple):
    """Named scalar sums/maxes as a pytree (dict leaves are jit/vmap/scan
    compatible; the key sets are static structure)."""

    sums: dict[str, jnp.ndarray]
    maxes: dict[str, jnp.ndarray]
    count: jnp.ndarray  # number of update() calls (per batch lane)

    # ------------------------------------------------------------------
    # Construction
    # ------------------------------------------------------------------
    @classmethod
    def create(
        cls,
        sum_names: tuple[str, ...] | list[str] = (),
        max_names: tuple[str, ...] | list[str] = (),
        batch_shape: tuple[int, ...] = (),
    ) -> "MetricsAccumulator":
        """Zero-initialised accumulator; ``batch_shape`` adds leading batch
        axes (one independent accumulator per env lane under ``vmap``)."""
        return cls(
            sums={n: jnp.zeros(batch_shape, jnp.float32) for n in sum_names},
            maxes={n: jnp.full(batch_shape, -jnp.inf, jnp.float32) for n in max_names},
            count=jnp.zeros(batch_shape, jnp.float32),
        )

    @property
    def names(self) -> tuple[str, ...]:
        """All tracked metric names (sums then maxes)."""
        return tuple(self.sums) + tuple(m for m in self.maxes if m not in self.sums)

    # ------------------------------------------------------------------
    # In-jit ops (pure; return a new accumulator)
    # ------------------------------------------------------------------
    def update(self, values: dict[str, Any]) -> "MetricsAccumulator":
        """One step's named scalars folded in: sums add, maxes max-merge.

        Every tracked name must be present in ``values`` (missing keys are a
        trace-time ``KeyError`` — silently skipping a KPI would report a
        wrong total); extra keys are ignored.
        """
        sums = {n: s + values[n] for n, s in self.sums.items()}
        maxes = {n: jnp.maximum(m, values[n]) for n, m in self.maxes.items()}
        return MetricsAccumulator(sums, maxes, self.count + 1.0)

    def merge(self, other: "MetricsAccumulator") -> "MetricsAccumulator":
        """Combine two accumulators over the same names (e.g. across hosts
        or shards): sums/counts add, maxes max-merge."""
        if self.names != other.names:
            raise ValueError(
                f"cannot merge accumulators over different metrics: "
                f"{self.names} vs {other.names}"
            )
        return MetricsAccumulator(
            sums={n: s + other.sums[n] for n, s in self.sums.items()},
            maxes={n: jnp.maximum(m, other.maxes[n]) for n, m in self.maxes.items()},
            count=self.count + other.count,
        )

    def since(self, earlier: "MetricsAccumulator") -> "MetricsAccumulator":
        """The delta accumulated after ``earlier`` (sums/count subtract —
        the per-update KPI window PPO reports; maxes stay absolute)."""
        return MetricsAccumulator(
            sums={n: s - earlier.sums[n] for n, s in self.sums.items()},
            maxes=dict(self.maxes),
            count=self.count - earlier.count,
        )

    # ------------------------------------------------------------------
    # Host boundary
    # ------------------------------------------------------------------
    def flush(
        self, means: tuple[str, ...] | list[str] = (), reduce_batch: bool = True
    ) -> dict[str, float]:
        """Cross to the host ONCE: return plain-float totals.

        ``{name}`` is the summed total, ``{name}_per_step`` (for names in
        ``means``) divides by the update count, ``{name}_max`` reports
        max-merged names, and ``steps`` is the mean update count.  With
        ``reduce_batch`` (default) batch lanes are averaged — per-lane
        arrays are returned otherwise.
        """
        red = (lambda x: np.asarray(x).mean()) if reduce_batch else np.asarray
        out: dict[str, Any] = {}
        count = np.maximum(np.asarray(self.count), 1.0)
        for n, s in self.sums.items():
            out[n] = float(red(s)) if reduce_batch else red(s)
            if n in means:
                per = np.asarray(s) / count
                out[f"{n}_per_step"] = float(per.mean()) if reduce_batch else per
        for n, m in self.maxes.items():
            v = np.asarray(m)
            out[f"{n}_max"] = float(v.max()) if reduce_batch else v
        out["steps"] = float(np.asarray(self.count).mean()) if reduce_batch else np.asarray(self.count)
        return out

