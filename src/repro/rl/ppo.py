"""PPO on Chargax — PureJaxRL-style, fully jitted (paper §5, App. B).

The whole training run (rollout scan -> GAE -> minibatch epochs) is one jitted
function; environments are vectorised on-device, matching the paper's setup
(Lu et al., 2022).  Hyperparameter defaults replicate paper Table 3.

Environment plumbing goes through the ``repro.envs`` protocol: ``make_train``
wraps the env as ``AutoReset(VmapWrapper(env, num_envs))`` — the wrapper
stack owns batching, the nested scenario×env layout (one exogenous-table
copy per scenario) and episode restarts, so this file contains *no*
env-specific vmap glue and any :class:`repro.envs.Environment` with the
Chargax action layout trains unchanged.

For pod-scale runs, ``shard_envs`` places the environment batch on the mesh's
data axes so rollouts parallelise across chips without host transfers
(DESIGN.md §3) — the same function compiles for 1 CPU device and for the
production mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.state import EnvParams
from repro.distributed import env_sharding
from repro.envs import AutoReset, Environment, LogWrapper, VmapWrapper
from repro.obs import annotate
from repro.optim import AdamWConfig, adamw_init, adamw_update, apply_updates, linear_anneal
from repro.rl import networks

# domain KPIs accumulated on device through the rollout scan (LogWrapper's
# MetricsAccumulator) and reported per update as ``metrics["kpi/<name>"]`` —
# batch-mean per-env-step rates, no extra device syncs.  All are per-step
# scalars the env already emits in ``info``.
DEFAULT_KPI_METRICS = (
    "profit",
    "energy_delivered",
    "energy_discharged",
    "v2g_debt",
    "missing_kwh",
    "rejected",
)


@dataclasses.dataclass(frozen=True)
class PPOConfig:
    """Paper Table 3 defaults."""

    total_timesteps: int = 10_000_000
    lr: float = 2.5e-4
    anneal_lr: bool = True
    gamma: float = 0.99
    gae_lambda: float = 0.95
    max_grad_norm: float = 100.0
    clip_eps: float = 0.2
    vf_clip: float = 10.0
    ent_coef: float = 0.01
    vf_coef: float = 0.25
    num_envs: int = 12
    rollout_steps: int = 300
    num_minibatches: int = 4
    update_epochs: int = 4
    hidden: tuple[int, ...] = (128, 128)
    # reward normalisation scale (profits are O(10) per step)
    reward_scale: float = 0.1

    @property
    def batch_size(self) -> int:
        return self.num_envs * self.rollout_steps

    @property
    def minibatch_size(self) -> int:
        return self.batch_size // self.num_minibatches

    @property
    def num_updates(self) -> int:
        return max(self.total_timesteps // self.batch_size, 1)


class Transition(NamedTuple):
    done: jnp.ndarray
    action: jnp.ndarray
    value: jnp.ndarray
    reward: jnp.ndarray
    log_prob: jnp.ndarray
    obs: jnp.ndarray
    info: dict


class RunnerState(NamedTuple):
    params: dict
    opt_state: Any
    env_state: Any
    obs: jnp.ndarray
    key: jax.Array
    update_idx: jnp.ndarray


def make_train(
    config: PPOConfig,
    env: Environment,
    env_params: EnvParams | None = None,
    shard_envs: Callable[[jnp.ndarray], jnp.ndarray] | None = None,
    scenario_params: EnvParams | None = None,
    kpi_metrics: tuple[str, ...] = DEFAULT_KPI_METRICS,
) -> Callable[[jax.Array], dict]:
    """Build the full jitted training function: key -> {runner_state, metrics}.

    ``env`` is any single-instance :class:`repro.envs.Environment`; batching
    and episode restarts come from ``AutoReset(VmapWrapper(env, num_envs))``.

    ``scenario_params`` — a stacked ``(S, ...)`` parameter pytree (e.g. from
    ``scenarios.stack_params``) — trains one agent across a scenario
    *distribution* for robustness (the paper's distribution-shift setting):
    the ``num_envs`` parallel environments are split into S contiguous blocks
    of ``num_envs // S`` and stepped under ``VmapWrapper``'s *nested* vmap
    (scenario axis outer, envs-per-scenario inner), so every rollout mixes
    all S worlds and the minibatches interleave them while device memory
    holds exactly ONE copy of each scenario's exogenous tables (leading axis
    S, never ``num_envs``).  The returned ``train`` function carries the
    resolved parameter pytree as ``train.lowered_env_params`` for
    introspection.
    """
    n_heads = env.action_space.shape[-1]
    n_actions = env.action_space.num_categories
    obs_dim = env.observation_space.shape[-1]
    constrain = shard_envs or env_sharding.constrain_env_batch

    if scenario_params is not None:
        if env_params is not None:
            raise ValueError("pass either env_params or scenario_params, not both")
        n_scen = jax.tree_util.tree_leaves(scenario_params)[0].shape[0]
        if config.num_envs % n_scen != 0:
            raise ValueError(
                f"num_envs={config.num_envs} is not a multiple of {n_scen} "
                "scenarios: the nested vmap assigns num_envs // S envs per "
                "scenario, so an uneven split would drop scenarios or skew "
                "the training mixture; adjust num_envs"
            )
        env_params = jax.tree_util.tree_map(jnp.asarray, scenario_params)
    else:
        env_params = env_params if env_params is not None else env.default_params
        n_scen = None

    lr = (
        linear_anneal(config.lr, config.num_updates * config.update_epochs * config.num_minibatches)
        if config.anneal_lr
        else (lambda step: jnp.float32(config.lr))
    )
    opt_cfg = AdamWConfig(max_grad_norm=config.max_grad_norm)

    # the wrapper stack owns ALL env batching: a flat (num_envs,) vmap, or
    # the nested scenario×env layout when scenario_params is given; AutoReset
    # restarts finished episodes inside step; LogWrapper (outermost, so its
    # running totals survive restarts) carries episode accounting and the
    # in-jit KPI accumulator.  reward/done/obs pass through LogWrapper
    # unchanged, so training math is bit-identical with KPIs on or off.
    venv = VmapWrapper(env, config.num_envs, num_scenarios=n_scen)
    wenv = LogWrapper(AutoReset(venv), metrics=tuple(kpi_metrics))

    def policy(params, obs):
        with annotate("ppo/mlp"):
            return networks.apply_actor_critic(params, obs, n_heads, n_actions)

    def log_prob(logits, action):
        with annotate("ppo/logprob"):
            return networks.log_prob(logits, action)

    def train(key: jax.Array) -> dict:
        key, k_net, k_reset = jax.random.split(key, 3)
        params = networks.init_actor_critic(
            k_net, obs_dim, n_heads, n_actions, config.hidden
        )
        opt_state = adamw_init(params)
        obs, env_state = wenv.reset(k_reset, env_params)
        obs = constrain(obs)

        def env_step(runner: RunnerState, _):
            params, opt_state, env_state, obs, key, upd = runner
            key, k_act, k_env = jax.random.split(key, 3)
            out = policy(params, obs)
            action = networks.sample_action(k_act, out.logits)
            logp = log_prob(out.logits, action)

            # step + auto-reset: ts.obs/ts.state restart where done, while
            # ts.reward/ts.done still describe the finishing transition
            ts = wenv.step(k_env, env_state, action, env_params)
            n_obs = constrain(ts.obs)

            t = Transition(
                ts.done, action, out.value, ts.reward * config.reward_scale, logp, obs,
                {k: ts.info[k] for k in ("profit", "missing_kwh", "rejected")},
            )
            return RunnerState(params, opt_state, ts.state, n_obs, key, upd), t

        def compute_gae(traj: Transition, last_val: jnp.ndarray):
            def scan_fn(carry, t):
                gae, next_value = carry
                delta = t.reward + config.gamma * next_value * (1 - t.done) - t.value
                gae = delta + config.gamma * config.gae_lambda * (1 - t.done) * gae
                return (gae, t.value), gae

            _, advantages = jax.lax.scan(
                scan_fn,
                (jnp.zeros_like(last_val), last_val),
                traj,
                reverse=True,
            )
            return advantages, advantages + traj.value

        def loss_fn(params, batch: Transition, gae, targets):
            out = policy(params, batch.obs)
            logp = log_prob(out.logits, batch.action)
            ratio = jnp.exp(logp - batch.log_prob)
            gae_n = (gae - gae.mean()) / (gae.std() + 1e-8)
            pg1 = ratio * gae_n
            pg2 = jnp.clip(ratio, 1 - config.clip_eps, 1 + config.clip_eps) * gae_n
            pg_loss = -jnp.minimum(pg1, pg2).mean()

            v_clip = batch.value + jnp.clip(
                out.value - batch.value, -config.vf_clip, config.vf_clip
            )
            v_losses = jnp.square(out.value - targets)
            v_losses_clip = jnp.square(v_clip - targets)
            v_loss = 0.5 * jnp.maximum(v_losses, v_losses_clip).mean()

            ent = networks.entropy(out.logits).mean()
            total = pg_loss + config.vf_coef * v_loss - config.ent_coef * ent
            return total, {"pg_loss": pg_loss, "v_loss": v_loss, "entropy": ent}

        def update_minibatch(carry, batch):
            params, opt_state = carry
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch[0], batch[1], batch[2]
            )
            with annotate("ppo/optim"):
                updates, opt_state, gnorm = adamw_update(grads, opt_state, params, lr, opt_cfg)
                params = apply_updates(params, updates)
            return (params, opt_state), {"loss": loss, "grad_norm": gnorm, **aux}

        def update_epoch(carry, _):
            params, opt_state, traj, gae, targets, key = carry
            key, k_perm = jax.random.split(key)
            bs = config.batch_size
            with annotate("ppo/shuffle"):
                perm = jax.random.permutation(k_perm, bs)

                flat = jax.tree_util.tree_map(
                    lambda x: x.reshape((bs,) + x.shape[2:]), (traj, gae, targets)
                )
                shuffled = jax.tree_util.tree_map(lambda x: jnp.take(x, perm, axis=0), flat)
                minibatches = jax.tree_util.tree_map(
                    lambda x: x.reshape((config.num_minibatches, -1) + x.shape[1:]), shuffled
                )
            (params, opt_state), metrics = jax.lax.scan(
                update_minibatch, (params, opt_state), minibatches
            )
            return (params, opt_state, traj, gae, targets, key), metrics

        def update_step(runner: RunnerState, _):
            acc_before = runner.env_state.metrics
            with annotate("ppo/rollout"):
                runner, traj = jax.lax.scan(
                    env_step, runner, None, config.rollout_steps
                )
            params, opt_state, env_state, obs, key, upd = runner
            with annotate("ppo/gae"):
                last_val = policy(params, obs).value
                gae, targets = compute_gae(traj, last_val)

            with annotate("ppo/update"):
                carry = (params, opt_state, traj, gae, targets, key)
                carry, metrics = jax.lax.scan(
                    update_epoch, carry, None, config.update_epochs
                )
                params, opt_state, _, _, _, key = carry

            mean_ep_reward = traj.reward.sum(axis=0).mean() / config.reward_scale
            mean_profit = traj.info["profit"].mean() * env.config.episode_steps
            out_metrics = {
                "mean_step_reward": traj.reward.mean() / config.reward_scale,
                "rollout_reward": mean_ep_reward,
                "mean_daily_profit": mean_profit,
                "missing_kwh": traj.info["missing_kwh"].mean(),
                "rejected": traj.info["rejected"].mean(),
                "loss": metrics["loss"].mean(),
                "entropy": metrics["entropy"].mean(),
                # LogWrapper episode accounting: last finished episode per env
                "episode_return": env_state.returned_episode_return.mean(),
                "episode_length": env_state.returned_episode_length.astype(
                    jnp.float32
                ).mean(),
            }
            if acc_before is not None:
                # this update's KPI window: batch-mean per-env-step rates from
                # the on-device accumulator (still traced — no host sync)
                delta = env_state.metrics.since(acc_before)
                steps = jnp.maximum(delta.count.mean(), 1.0)
                for n, s in delta.sums.items():
                    out_metrics[f"kpi/{n}"] = s.mean() / steps
            return RunnerState(params, opt_state, env_state, obs, key, upd + 1), out_metrics

        runner = RunnerState(params, opt_state, env_state, obs, key, jnp.int32(0))
        runner, metrics = jax.lax.scan(update_step, runner, None, config.num_updates)
        return {"runner_state": runner, "metrics": metrics}

    # introspection: the parameter pytree exactly as it will be closed over
    # and lowered — tests assert scenario tables keep leading axis S (one
    # copy per scenario), not num_envs (a copy per environment).
    train.lowered_env_params = env_params
    train.scenario_shape = (
        (n_scen, config.num_envs // n_scen) if n_scen is not None else None
    )
    return train


def make_ppo_policy(env: Environment, greedy: bool = True):
    """Wrap trained params into an eval policy: (params, key, obs) -> action."""
    n_heads = env.action_space.shape[-1]
    n_actions = env.action_space.num_categories

    def policy(params, key, obs):
        out = networks.apply_actor_critic(params, obs, n_heads, n_actions)
        if greedy:
            return jnp.argmax(out.logits, axis=-1)
        return networks.sample_action(key, out.logits)

    return policy
