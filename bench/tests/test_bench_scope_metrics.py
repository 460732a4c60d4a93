"""The readers of the metrics inside the env step and the PPO update, and of
the collectives between chips, checked on hand-built device ops against
hand-computed values, and on the recorded one-chip traces, whose ops carry
no such scope and no collective.  No device is needed."""
from __future__ import annotations

import os

import pytest

from bench.tests.tiny import ROOT

from bench import run
from bench.harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ("draw_model_ns.sim", "price_window_ns.train", "ppo_update_mlp_ms", "ppo_shuffle_ms", "ppo_logprob_ms", "collective_ms")

STEP = "jit(call)/while/body/wrap/AutoReset/wrap/VmapWrapper/vmap(vmap(env/depart_arrive))"
ROLL = "jit(call)/while/body/ppo/rollout/while/body"
UPD = "jit(call)/while/body/ppo/update/while/body/while/body"
# (duration ns, scope path): one op each, one after the other on one chip
OPS = [
    (100, STEP + "/env/draw_cars/vmap(env/draw_model)/jit(searchsorted)/while/body/gather"),
    (50, STEP + "/env/draw_cars/env/draw_model/gather"),  # the fleet mix of the day
    (30, STEP + "/env/draw_cars/vmap(env/draw_soc0)/jit(_gamma)/while/body/log"),
    (70, STEP + "/env/car_lookup/gather"),
    (45, STEP + "/env/place_cars/select_n"),
    (40, ROLL + "/wrap/AutoReset/wrap/VmapWrapper/vmap(vmap(env/observe))/env/price_window/gather"),
    (20, ROLL + "/ppo/mlp/dot_general"),
    (10, ROLL + "/ppo/logprob/jit(log_softmax)/sub"),
    (8, "jit(call)/while/body/ppo/gae/ppo/mlp/dot_general"),
    (25, UPD + "/jvp(ppo/mlp)/dot_general"),
    (60, UPD + "/transpose(jvp(ppo/mlp))/dot_general"),
    (5, UPD + "/transpose(jvp(ppo/logprob))/mul"),
    (35, UPD + "/ppo/optim/mul"),
    (15, "jit(call)/while/body/ppo/update/while/body/ppo/shuffle/gather"),
]
# (chip, duration ns, opcode): collectives on two chips, and a fusion that is none
COLLECTIVE_OPS = [(0, 40, "all-reduce"), (1, 60, "all-reduce"), (0, 30, "all-gather"), (1, 500, "fusion")]
N_CALLS, ENV_STEPS = 2, 10
WANT = {
    "draw_model_ns.sim": (100 + 50) / ENV_STEPS,
    "price_window_ns.train": 40 / ENV_STEPS,
    "ppo_update_mlp_ms": (25 + 60) / N_CALLS / 1e6,
    "ppo_shuffle_ms": 15 / N_CALLS / 1e6,
    "ppo_logprob_ms": (10 + 5) / N_CALLS / 1e6,
    "collective_ms": (40 + 60 + 30) / 2 / N_CALLS / 1e6,  # averaged over the two chips
}


def _reading(tr, n_calls: int, env_steps: int) -> run.Reading:
    return run.Reading(trace=tr, n_calls=n_calls, env_steps=env_steps, flops_per_call=0, env_step_bytes=1, env_step_ops=1, peak={})


def _hand_built(metric: str = "") -> run.Reading:
    ops, t = [], 0
    if metric == "collective_ms":
        for i, (chip, dur, opcode) in enumerate(COLLECTIVE_OPS):
            ops.append(trace.Op(t, dur, f"{opcode}.{i}", "jit(call)/while/body", chip, opcode))
            t += dur
    else:
        for i, (dur, scope) in enumerate(OPS):
            ops.append(trace.Op(t, dur, f"fusion.{i}", scope, 0, "fusion"))
            t += dur
    return _reading(trace.TraceData(ops, [trace.Span(0, t, trace.WINDOW_SPAN)]), N_CALLS, ENV_STEPS)


@pytest.mark.parametrize("metric", NEW)
def test_reader_by_hand(metric):
    assert run.load_reader(ROOT, metric)(_hand_built(metric)) == pytest.approx(WANT[metric])


def test_parts_fit_inside_their_layer_by_hand():
    ctx = _hand_built()
    read = {m: run.load_reader(ROOT, m)(ctx) for m in NEW + ("env_step_ns.sim", "ppo_update_ms")}
    assert read["collective_ms"] is None
    assert read["draw_model_ns.sim"] <= read["env_step_ns.sim"]
    assert read["ppo_update_mlp_ms"] + read["ppo_shuffle_ms"] <= read["ppo_update_ms"]


@pytest.mark.parametrize("metric", NEW)
@pytest.mark.parametrize("name", ["sim_trace.json.gz", "ppo_trace.json.gz"])
def test_reader_finds_nothing_in_traces_without_the_scopes(metric, name):
    ops, spans = trace.load_records(os.path.join(DATA, name))
    assert run.load_reader(ROOT, metric)(_reading(trace.TraceData(ops, spans), 1, 1)) is None
