"""Traffic drivers: one per kind of work, chosen by a traffic file's ``driver``.

A driver builds the system's timed program for a configuration, warms it
up, runs numbered calls, keeps what the first calls produced, and after
the window recomputes those calls with the plain reference to give the
numbers that decide ``correct``.

Each call ``i`` takes its randomness from ``fold_in(stream(seed, "calls"),
i)`` inside the compiled program, so a call needs no new key on the host
and one seed gives one sequence of inputs.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench.harness import compare, counts, tables

# calls that the reference recomputes: the first three of every run
N_CHECK = 3


def stream(seed: int, name: str):
    """A key for one named use of ``seed`` (any width: the high 32 bits are
    folded in, so seeds past 2**32 do not wrap)."""
    import jax

    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, {"calls": 0, "reset": 1}[name])


def _first_device_put(tree):
    import jax

    return jax.device_put(tree, jax.devices()[0])


class Driver:
    """Shared part: configuration, tables and their fingerprint check."""

    def __init__(self, config: dict, traffic: dict, seed: int, recorded_tables: dict | None):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.env, self.params = tables.build(config)
        self.tables = tables.as_dict(self.params)
        self.table_faults = tables.check(self.tables, recorded_tables) if recorded_tables is not None else []
        self.shapes = counts.station_shapes(config, self.tables)
        self.kept: dict[int, object] = {}
        self.finite: list = []

    def _compile(self, *args):
        t0 = time.perf_counter()
        self._compiled = self._fn.lower(*args).compile()
        self.compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.call(0)
        self.warmup_s = time.perf_counter() - t0

    def memory_text(self) -> str:
        m = self._compiled.memory_analysis()
        return (
            f"timed program's device bytes: args={m.argument_size_in_bytes} "
            f"out={m.output_size_in_bytes} temp={m.temp_size_in_bytes}"
        )

    def hlo_text(self) -> str:
        """The compiled timed program's HLO, with each op's scope metadata."""
        return self._compiled.as_text()

    def attempted_failed(self, lo: int, hi: int) -> tuple[int, int]:
        """Calls ``lo`` to ``hi - 1`` (the window's), and how many of them
        gave a result that is not finite."""
        flags = [bool(np.all(np.isfinite(np.asarray(x)))) for x in self.finite[lo:hi]]
        return len(flags), flags.count(False)

    def table_numbers(self) -> dict:
        return {"tables_changed": float(len(self.table_faults))}

    def reseed(self, seed: int):
        """Start over with another seed on the same compiled program."""
        self.seed, self.key, self.kept, self.finite = seed, stream(seed, "calls"), {}, []


class PPOUpdate(Driver):
    """One PPO update per call through ``repro.rl.make_train`` (fresh weights
    and fresh envs each call: ``make_train`` has no steady-state entry)."""

    def __init__(self, config, traffic, seed, recorded_tables=None):
        super().__init__(config, traffic, seed, recorded_tables)
        import jax

        from repro.rl import PPOConfig, make_train

        n, ppo = config["num_envs"], dict(config["ppo"])
        ppo["hidden"] = tuple(ppo["hidden"])
        self.env_steps_per_call = n * ppo["rollout_steps"]
        self.flops_per_call = counts.ppo_update_flops(config, self.shapes)
        cfg = PPOConfig(total_timesteps=self.env_steps_per_call, num_envs=n, **ppo)
        train = make_train(cfg, self.env, scenario_params=_first_device_put(self.params))

        def call(key, i):
            out = train(jax.random.fold_in(key, i))
            rs = out["runner_state"]
            return {"params": rs.params, "mu": rs.opt_state.mu, "metrics": {"loss": out["metrics"]["loss"][0]}}

        self.key = stream(seed, "calls")
        self._fn = jax.jit(call)
        self._compiled = None

    def setup(self):
        self._compile(self.key, np.int32(0))

    def call(self, i: int) -> int:
        import jax

        out = jax.block_until_ready(self._compiled(self.key, np.int32(i)))
        if i < N_CHECK:
            self.kept[i] = out
        self.finite.append(out["metrics"]["loss"])
        return self.env_steps_per_call

    def release(self):
        import jax

        self.kept = jax.device_get(self.kept)
        self.finite = jax.device_get(self.finite)
        self._compiled = self._fn = None
        gc.collect()

    # -- reference ---------------------------------------------------------
    def reference(self, mlp_dtype=None):
        """i -> the reference's result of call ``i``."""
        import jax
        import jax.numpy as jnp

        from bench.reference import ppo_ref

        tabs = _first_device_put({k: jnp.asarray(v) for k, v in self.tables.items()})
        update = jax.jit(ppo_ref.make_update(tabs, self.config, mlp_dtype or jnp.float32))
        return lambda i: jax.device_get(update(jax.random.fold_in(self.key, i)))

    @staticmethod
    def as_output(r) -> dict:
        """A reference result in the form the timed call returns."""
        return {"params": r.params, "mu": r.mu, "metrics": r.metrics}

    def numbers(self, got: dict, ref_of) -> dict:
        """Worst over the checked calls of each compared gap."""
        worst = {"loss_gap": 0.0, "grad_gap": 0.0, "change_gap": 0.0}
        for i, out in sorted(got.items()):
            r = ref_of(i)
            quiet = compare.quiet_leaves(r.mu)
            nums = {
                "loss_gap": compare.rel_gap(out["metrics"]["loss"], r.metrics["loss"], 0.1),
                "grad_gap": compare.worst_leaf_norm_gap(out["mu"], r.mu, quiet),
                # the median leaf: under Adam an element whose gradient sits
                # at round-off moves a whole step either way, so the worst
                # leaf's change swings from seed to seed (PERF.md)
                "change_gap": compare.median_leaf_norm_gap(
                    compare.tree_sub(out["params"], r.init_params),
                    compare.tree_sub(r.params, r.init_params),
                    quiet,
                ),
            }
            worst = {k: max(worst[k], v) for k, v in nums.items()}
        return worst


class RandomDay(Driver):
    """Batched simulation under uniform random actions: each call is one
    jitted day (``episode_steps`` steps) of ``AutoReset(VmapWrapper(env))``
    whose state carries over, returning per-env day sums of reward, energy
    delivered, cars arrived and cars rejected."""

    def __init__(self, config, traffic, seed, recorded_tables=None):
        super().__init__(config, traffic, seed, recorded_tables)
        import jax
        import jax.numpy as jnp

        from repro.envs import AutoReset, VmapWrapper

        n, n_scen = config["num_envs"], len(config["scenarios"])
        self.steps = self.env.config.episode_steps
        self.env_steps_per_call = n * self.steps
        self.flops_per_call = 0
        venv = AutoReset(VmapWrapper(self.env, n, num_scenarios=n_scen))
        params = _first_device_put(self.params)

        def call(state, key, i):
            def body(carry, k):
                s, acc = carry
                k_act, k_step = jax.random.split(k)
                ts = venv.step(k_step, s, venv.sample_action(k_act), params)
                inc = jnp.stack(
                    [ts.reward, ts.info["energy_delivered"], ts.info["arrived"], ts.info["rejected"]], axis=-1
                )
                return (ts.state, acc + inc), None

            keys = jax.random.split(jax.random.fold_in(key, i), self.steps)
            (state, acc), _ = jax.lax.scan(body, (state, jnp.zeros((n, 4), jnp.float32)), keys)
            return state, {"sums": acc, "day": state.day}

        self.key = stream(seed, "calls")
        self._reset = jax.jit(lambda k: venv.reset(k, params)[1])
        self._fn = jax.jit(call, donate_argnums=0)
        self._compiled = None
        self.state = None

    def reseed(self, seed: int):
        super().reseed(seed)
        self.state = self._reset(stream(seed, "reset"))

    def setup(self):
        self.state = self._reset(stream(self.seed, "reset"))
        self._compile(self.state, self.key, np.int32(0))

    def call(self, i: int) -> int:
        import jax

        self.state, out = self._compiled(self.state, self.key, np.int32(i))
        jax.block_until_ready(out)
        if i < N_CHECK:
            self.kept[i] = out
        self.finite.append(out["sums"])
        return self.env_steps_per_call

    def release(self):
        import jax

        self.kept = jax.device_get(self.kept)
        self.finite = [np.asarray(x).sum() for x in jax.device_get(self.finite)]
        self.state = self._compiled = self._fn = None
        gc.collect()

    # -- reference ---------------------------------------------------------
    def rows(self) -> np.ndarray:
        """Envs the reference follows: ``sample_envs`` drawn from the seed,
        the same number from each scenario's block, as ``(S, E)`` indices."""
        n, n_scen = self.config["num_envs"], len(self.config["scenarios"])
        per = n // n_scen
        take = min(int(self.traffic["sample_envs"]) // n_scen, per)
        rng = np.random.default_rng(self.seed)
        return np.stack([b * per + np.sort(rng.choice(per, take, replace=False)) for b in range(n_scen)])

    def reference(self, ftype=None):
        import jax
        import jax.numpy as jnp

        from bench.reference import chargax_ref as ref

        ftype = ftype or jnp.float32
        cfg, n = self.config, self.config["num_envs"]
        rows = jnp.asarray(self.rows())
        tabs = _first_device_put(ref.cast_tables({k: jnp.asarray(v) for k, v in self.tables.items()}, ftype))
        n_heads, n_levels = self.shapes["n_heads"], self.shapes["n_levels"]

        def day(states, key):
            def body(carry, k):
                s, acc = carry
                k_act, k_step = jax.random.split(k)
                acts = jax.random.randint(k_act, (n, n_heads), 0, n_levels, jnp.int32)[rows]
                s, r, _, info = ref.batch_autoreset_step(k_step, s, acts, tabs, cfg["env"], n, ftype, rows)
                inc = jnp.stack([r, info["energy_delivered"], info["arrived"], info["rejected"]], -1)
                return (s, acc + inc.astype(jnp.float32)), None

            zeros = jnp.zeros(rows.shape + (4,), jnp.float32)
            (states, acc), _ = jax.lax.scan(body, (states, zeros), jax.random.split(key, self.steps))
            return states, {"sums": acc, "day": states["day"]}

        day = jax.jit(day)
        states = jax.jit(lambda k: ref.batch_reset(k, tabs, n, ftype, rows))(stream(self.seed, "reset"))
        outs = {}

        def ref_of(i):
            nonlocal states
            for j in range(len(outs), i + 1):
                states, outs[j] = day(states, jax.random.fold_in(self.key, j))
            return jax.device_get(outs[i])

        return ref_of

    @staticmethod
    def as_output(r) -> dict:
        return r

    def numbers(self, got: dict, ref_of) -> dict:
        rows = self.rows()
        bad = []
        for i, out in sorted(got.items()):
            r = ref_of(i)
            # the timed call returns every env flat; the reference only the rows
            sums, day = np.asarray(out["sums"]), np.asarray(out["day"])
            if sums.ndim == 2:
                sums, day = sums[rows], day[rows]
            miss = compare.env_sum_mismatch(sums, r["sums"], float(self.traffic["sum_rtol"]))
            bad.append(miss | (day != np.asarray(r["day"])))
        return {"env_mismatch_share": float(np.mean(bad))}


DRIVERS = {"ppo_update": PPOUpdate, "random_day": RandomDay}
