"""Traffic drivers: one per kind of work, chosen by a traffic file's ``driver``.

A driver builds the system's timed program for a configuration, warms it
up, runs numbered calls, keeps what the first calls produced, and after
the window recomputes those calls with the plain reference to give the
numbers that decide ``correct``.

Each call ``i`` takes its randomness from ``fold_in(stream(seed, "calls"),
i)`` inside the compiled program, so a call needs no new key on the host
and one seed gives one sequence of inputs.

A driver runs on the devices it is given.  On more than one, the PPO
driver takes ``rl_train``'s data-parallel path: ``make_data_mesh`` over
the devices, ``make_shard_envs`` of that mesh into ``make_train``, and the
program lowered under ``set_mesh``; the scenario tables are replicated on
the mesh, and the reference is sharded alike.
"""
from __future__ import annotations

import contextlib
import gc
import re
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


def data_mesh(devices: list, num_envs: int):
    """``rl_train``'s data mesh over ``devices`` (``make_data_mesh``, which
    spans every visible device), with its check that the env batch divides;
    None on one device, where ``rl_train`` builds no mesh."""
    if len(devices) == 1:
        return None
    from repro.launch.mesh import make_data_mesh

    if num_envs % len(devices):
        raise ValueError(
            f"num_envs {num_envs} is not divisible by the {len(devices)} devices: "
            "the env batch shards evenly over all of them"
        )
    mesh = make_data_mesh()  # orders the devices by their place in the chips' topology
    if set(mesh.devices.flat) != set(devices):
        raise ValueError(f"make_data_mesh spans {list(mesh.devices.flat)}, the cell takes {devices}")
    return mesh


def shard_leading(mesh):
    """A ``with_sharding_constraint`` of each leaf's leading axis onto the
    mesh's ``data`` axis (replicated where it does not divide), from
    ``jax.sharding`` alone: the reference's copy of the program's sharding."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    n = mesh.shape["data"]

    def one(x):
        spec = PartitionSpec("data") if x.ndim and x.shape[0] % n == 0 else PartitionSpec()
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return lambda tree: jax.tree_util.tree_map(one, tree)


def undivided(hlo_text: str, num_envs: int, chips: int, n_scen: int, steps: int, obs_dim: int, n_evse: int) -> list[str]:
    """Parts of the env batch that a partitioned program holds whole on a
    chip, read from its per-chip shapes: the observations carried between
    steps (``[envs, obs]``), the rollout's stacked observations (``[steps,
    envs, obs]``) and the env state's per-port arrays (``[scenarios, envs
    per scenario, ports, ...]``).  Empty where each chip holds
    ``num_envs / chips`` envs of each."""
    shapes = {tuple(map(int, d.split(","))) for d in re.findall(r"\[(\d+(?:,\d+)+)\]", hlo_text)}
    rows = num_envs // chips
    out = []
    if (num_envs, obs_dim) in shapes or (rows, obs_dim) not in shapes:
        out.append(f"observations: not [{rows},{obs_dim}] alone")
    if (steps, rows, obs_dim) not in shapes:
        out.append(f"rollout buffer: no [{steps},{rows},{obs_dim}]")
    ports = [s for s in shapes if len(s) > 2 and s[2] == n_evse]
    whole = (n_scen, num_envs // n_scen)
    if any(s[:2] == whole for s in ports) or not any(s[0] * s[1] == rows for s in ports):
        out.append(f"env state: not {rows} envs' [..., {n_evse}, ...] port arrays alone")
    return out


class Driver:
    """Shared part: configuration, tables and their fingerprint check."""

    def __init__(self, config: dict, traffic: dict, seed: int, recorded_tables: dict | None, devices=None):
        import jax

        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices = list(devices or jax.devices()[:1])
        self.mesh = None
        self.env, self.params = tables.build(config)
        self.tables = tables.as_dict(self.params)
        self.table_faults = tables.check(self.tables, recorded_tables) if recorded_tables is not None else []
        self.shapes = counts.station_shapes(config, self.tables)
        self.kept: dict[int, object] = {}
        self.finite: list = []

    def _put(self, tree):
        """``tree`` on the first device, or replicated over the mesh."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(tree, NamedSharding(self.mesh, PartitionSpec()) if self.mesh else self.devices[0])

    def _on_mesh(self):
        import jax

        return jax.sharding.set_mesh(self.mesh) if self.mesh else contextlib.nullcontext()

    def _compile(self, *args):
        t0 = time.perf_counter()
        self._compiled = self._fn.lower(*args).compile()
        self.compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.call(0)
        self.finish()
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

    def finish(self):
        """Wait for every call made so far (a call that returns before its
        result is ready leaves it to this)."""

    def reseed(self, seed: int):
        """Start over with another seed on the same compiled program."""
        self.finish()
        self.seed, self.key, self.kept, self.finite = seed, stream(seed, "calls"), {}, []


class PPOUpdate(Driver):
    """One PPO update per call through ``repro.rl.make_train`` (fresh weights
    and fresh envs each call: ``make_train`` has no steady-state entry)."""

    def __init__(self, config, traffic, seed, recorded_tables=None, devices=None):
        super().__init__(config, traffic, seed, recorded_tables, devices)
        import jax

        from repro.distributed import env_sharding
        from repro.rl import PPOConfig, make_train

        n, ppo = config["num_envs"], dict(config["ppo"])
        ppo["hidden"] = tuple(ppo["hidden"])
        self.env_steps_per_call = n * ppo["rollout_steps"]
        self.flops_per_call = counts.ppo_update_flops(config, self.shapes)
        cfg = PPOConfig(total_timesteps=self.env_steps_per_call, num_envs=n, **ppo)
        self.mesh = data_mesh(self.devices, n)
        shard_envs = env_sharding.make_shard_envs(self.mesh) if self.mesh else None
        train = make_train(cfg, self.env, shard_envs=shard_envs, scenario_params=self._put(self.params))

        def call(key, i):
            out = train(jax.random.fold_in(key, i))
            rs = out["runner_state"]
            return {"params": rs.params, "mu": rs.opt_state.mu, "metrics": {"loss": out["metrics"]["loss"][0]}}

        self.key = stream(seed, "calls")
        self._fn = jax.jit(call)
        self._compiled = None
        self._ref_updates = {}

    def setup(self):
        with self._on_mesh():
            self._compile(self.key, np.int32(0))
        if self.mesh:
            ppo = self.config["ppo"]
            whole = undivided(
                self.hlo_text(), self.config["num_envs"], len(self.devices), len(self.config["scenarios"]),
                ppo["rollout_steps"], self.shapes["obs_dim"], self.shapes["n_evse"],
            )
            if whole:
                raise AssertionError(f"the timed program does not divide the env batch over {len(self.devices)} chips: {whole}")

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

        dtype = mlp_dtype or jnp.float32
        if dtype not in self._ref_updates:  # one compile per precision across reseeds
            tabs = self._put({k: jnp.asarray(v) for k, v in self.tables.items()})
            shard = shard_leading(self.mesh) if self.mesh else None
            self._ref_updates[dtype] = jax.jit(ppo_ref.make_update(tabs, self.config, dtype, shard=shard))
        update, key = self._ref_updates[dtype], self.key

        def ref_of(i):
            with self._on_mesh():
                return jax.device_get(update(jax.random.fold_in(key, i)))

        return ref_of

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
    delivered, cars arrived and cars rejected.

    A call dispatches its day and then waits for the day before, so one day
    is always queued on the chip, as in a loop that reads no result between
    days: the chip does not sit idle while the host dispatches the next
    day, and a host stall shorter than a day costs nothing."""

    def __init__(self, config, traffic, seed, recorded_tables=None, devices=None):
        super().__init__(config, traffic, seed, recorded_tables, devices)
        import jax
        import jax.numpy as jnp

        from repro.envs import AutoReset, VmapWrapper

        n, n_scen = config["num_envs"], len(config["scenarios"])
        self.steps = self.env.config.episode_steps
        self.env_steps_per_call = n * self.steps
        self.flops_per_call = 0
        venv = AutoReset(VmapWrapper(self.env, n, num_scenarios=n_scen))
        params = self._put(self.params)

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
        self._pending = None

    def reseed(self, seed: int):
        super().reseed(seed)
        self.state = self._reset(stream(seed, "reset"))

    def setup(self):
        self.state = self._reset(stream(self.seed, "reset"))
        self._compile(self.state, self.key, np.int32(0))

    def call(self, i: int) -> int:
        self.state, out = self._compiled(self.state, self.key, np.int32(i))
        if i < N_CHECK:
            self.kept[i] = out
        self.finite.append(out["sums"])
        self.finish()
        self._pending = out
        return self.env_steps_per_call

    def finish(self):
        import jax

        if self._pending is not None:
            jax.block_until_ready(self._pending)
            self._pending = None

    def release(self):
        import jax

        self.finish()
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
        tabs = self._put(ref.cast_tables({k: jnp.asarray(v) for k, v in self.tables.items()}, ftype))
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
