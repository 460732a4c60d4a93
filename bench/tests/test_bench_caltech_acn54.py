"""The Caltech ACN cell at a tiny size on the host CPU: correct against the
plain reference through ``run.run_cell``, the control outside its limits,
the metrics it reports, and the reader of ``env/constraint_scale`` on
hand-built device ops and on recorded traces that lack the scope."""
from __future__ import annotations

import os

import pytest

from bench.tests.tiny import ROOT, run_tiny, tiny_cell

from bench import run
from bench.harness import trace

WORKLOAD = "ppo.caltech_acn54"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRIC = "constraint_scale_ns.train"


def test_tiny_cell_correct():
    rc, line, cell = run_tiny(WORKLOAD)
    assert rc == 0 and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"train_env_steps_per_s", "setup_s"}
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())
    assert line["checks"]["tables_changed"]["value"] == 0.0

    from bench.harness.drivers import DRIVERS

    d = DRIVERS[cell.traffic["driver"]](cell.config, cell.traffic, 1)
    assert d.env_steps_per_call == 8 * 4
    assert (d.shapes["n_evse"], d.shapes["n_nodes"], d.shapes["n_heads"], d.shapes["obs_dim"]) == (54, 5, 55, 441)


def test_readings_control_fails_program_passes():
    from bench import readings

    cell = tiny_cell(WORKLOAD)
    worst = readings.readings(cell, [5, 2**31 + 13], True, True, None, out=lambda s: None)
    failed = [k for k, v in worst["control"].items() if v > cell.limits[k]]
    assert failed
    assert all(worst["program"][k] < worst["control"][k] for k in failed)


def test_cell_reports_the_ppo_metrics_and_its_own():
    cell = run.load_cell(ROOT, WORKLOAD)
    names = {m["name"] for m in cell.per_layer}
    assert cell.chips == 1 and cell.rate_metric == "train_env_steps_per_s"
    assert {METRIC, "train_mfu", "env_step_ns.train", "price_window_ns.train", "idle_share.train"} <= names
    assert {"ppo_rollout_ms", "ppo_update_ms", "ppo_update_mlp_ms", "ppo_shuffle_ms", "ppo_logprob_ms"} <= names
    assert not names & {"collective_ms", "env_step_ns.sim", "draw_model_ns.sim"}


STEP = "jit(call)/while/body/ppo/rollout/while/body/wrap/LogWrapper/wrap/AutoReset/wrap/VmapWrapper"
# (duration ns, scope path): one op each, one after the other on one chip
OPS = [
    (30, STEP + "/vmap(vmap(env/apply_actions))/clamp"),
    (70, STEP + "/vmap(vmap(env/apply_actions))/env/constraint_scale/dot_general"),
    (20, STEP + "/vmap(vmap(env/apply_actions/env/constraint_scale))/reduce"),
    (50, STEP + "/vmap(vmap(env/charge_cars))/mul"),
]
ENV_STEPS = 10


def _reading(ops, spans, env_steps):
    tr = trace.TraceData(ops, spans)
    return run.Reading(trace=tr, n_calls=1, env_steps=env_steps, flops_per_call=0, env_step_bytes=1, env_step_ops=1, peak={})


def test_reader_by_hand():
    ops, t = [], 0
    for i, (dur, scope) in enumerate(OPS):
        ops.append(trace.Op(t, dur, f"fusion.{i}", scope, 0, "fusion"))
        t += dur
    ctx = _reading(ops, [trace.Span(0, t, trace.WINDOW_SPAN)], ENV_STEPS)
    got = run.load_reader(ROOT, METRIC)(ctx)
    assert got == pytest.approx((70 + 20) / ENV_STEPS)
    assert got <= run.load_reader(ROOT, "env_step_ns.train")(ctx)


@pytest.mark.parametrize("name", ["sim_trace.json.gz", "ppo_trace.json.gz"])
def test_reader_finds_nothing_in_traces_without_the_scope(name):
    ops, spans = trace.load_records(os.path.join(DATA, name))
    assert run.load_reader(ROOT, METRIC)(_reading(ops, spans, 1)) is None
