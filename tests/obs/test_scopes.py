"""The named scopes inside the env step and the PPO update reach the compiled
programs' op metadata when annotations are on, nest under the scope each
belongs to, and are absent when annotations are off."""
import re

import jax
import pytest

from repro.core import ChargaxEnv, EnvConfig
from repro.envs import AutoReset, VmapWrapper
from repro.obs import enable_trace_annotations
from repro.rl import PPOConfig, make_train

jax.config.update("jax_platform_name", "cpu")

# scope -> the existing scopes it sits inside (one of them must hold each op)
PARENTS = {
    "env/departures": ("env/depart_arrive",),
    "env/arrive_count": ("env/depart_arrive",),
    "env/draw_cars": ("env/depart_arrive",),
    "env/draw_model": ("env/draw_cars",),
    "env/draw_soc0": ("env/draw_cars",),
    "env/car_lookup": ("env/depart_arrive",),
    "env/place_cars": ("env/depart_arrive",),
    "env/price_window": ("env/observe",),
    "ppo/mlp": ("ppo/rollout", "ppo/gae", "ppo/update"),
    "ppo/logprob": ("ppo/rollout", "ppo/update"),
    "ppo/shuffle": ("ppo/update",),
    "ppo/optim": ("ppo/update",),
}
OLD = (
    "env/decode", "env/apply_actions", "env/allocate", "env/fused_transition",
    "env/charge_cars", "env/depart_arrive", "env/reward", "env/observe",
    "ppo/rollout", "ppo/gae", "ppo/update", "eval/rollout", "eval/serve",
)


def _under(path: str, scope: str) -> bool:
    """``scope`` is a whole component of ``path`` (a transformation such as
    ``vmap(...)`` or ``transpose(jvp(...))`` may wrap it)."""
    return re.search(r"(?:^|[/(])" + re.escape(scope) + r"(?:[/)]|$)", path) is not None


def _op_names() -> list[str]:
    """op_name metadata of a compiled batched env step and a compiled PPO
    update, both at a tiny size, traced afresh."""
    env = ChargaxEnv(EnvConfig(episode_hours=1.0))
    params = env.default_params
    venv = AutoReset(VmapWrapper(env, 2))
    key = jax.random.key(0)
    _, state = venv.reset(key, params)
    step = jax.jit(lambda k, s, a: venv.step(k, s, a, params))
    texts = [step.lower(key, state, venv.sample_action(key)).compile().as_text()]
    cfg = PPOConfig(
        num_envs=2, rollout_steps=2, num_minibatches=1, update_epochs=1,
        total_timesteps=4, hidden=(8,),
    )
    texts.append(jax.jit(make_train(cfg, env)).lower(key).compile().as_text())
    return [n for t in texts for n in _program_op_names(t)]


def _program_op_names(hlo: str) -> list[str]:
    """op_names of a compiled HLO text, leaving out the scalar computations
    that reductions, scatters and sorts apply (``to_apply=``): those never run
    as ops of their own, and their metadata holds only the innermost scopes."""
    applied = set(re.findall(r"to_apply=%([\w.\-]+)", hlo))
    names, skip = [], False
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            skip = head.group(1) in applied
        elif not skip:
            names += re.findall(r'op_name="([^"]*)"', line)
    return names


@pytest.fixture(scope="module")
def op_names() -> dict[bool, list[str]]:
    out = {}
    for on in (True, False):
        prev = enable_trace_annotations(on)
        try:
            out[on] = _op_names()
        finally:
            enable_trace_annotations(prev)
    return out


@pytest.mark.parametrize("scope", sorted(PARENTS))
def test_scope_in_op_metadata_only_when_annotations_on(op_names, scope):
    held = [n for n in op_names[True] if _under(n, scope)]
    assert held, f"no op carries {scope!r}"
    # every op stays under the scope it was under before: the new name is a
    # label inside it, never a new home
    assert all(any(_under(n, p) for p in PARENTS[scope]) for n in held)
    assert not any(_under(n, scope) for n in op_names[False])


def test_backward_mlp_ops_are_named(op_names):
    assert any("transpose(jvp(ppo/mlp))" in n for n in op_names[True])


def test_no_scope_name_is_a_prefix_of_another():
    # ``in_scope`` in the benchmark's trace reducer matches by prefix
    names = sorted(set(PARENTS) | set(OLD))
    assert not [(a, b) for a in names for b in names if a != b and b.startswith(a)]
