"""The benchmark's data-driven lookup, counts, peaks, comparisons and trace
reducer, checked against hand-computed values.  No device is needed."""
from __future__ import annotations

import json
import os
import re

import numpy as np
import pytest

from bench.tests.tiny import ROOT

from bench import run
from bench.harness import compare, counts, peaks, tables, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_shape():
    spec = _spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["bench"] and spec["command"][1] == "bench/run.py"
    assert 1 <= spec["run_seconds"] <= 51
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) for m in spec["end_to_end"] + spec["per_layer"])
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    layers_of = {}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        layers_of.setdefault(m["layer"], set()).add(m["name"])


@pytest.mark.parametrize("workload", [w["name"] for w in _spec()["workloads"]])
def test_every_cell_finds_its_files(workload):
    cell = run.load_cell(ROOT, workload)
    assert cell.traffic["driver"] in ("ppo_update", "random_day")
    assert cell.limits["tables_changed"] == 0 and cell.limits["failed_calls"] == 0
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert callable(run.load_reader(ROOT, m["name"]))


def test_new_cell_is_new_files_only(tmp_path):
    """A configuration, traffic mix, limits and metric reader placed in a
    fresh tree are found by name, with no code edited."""
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    for d in ("traffic", "limits", "metrics"):
        (tmp_path / "bench" / d).mkdir()
    (tmp_path / "bench" / "configs" / "toy.json").write_text(json.dumps({"num_envs": 4}))
    (tmp_path / "bench" / "configs" / "toy.tables.json").write_text("{}")
    (tmp_path / "bench" / "traffic" / "burst.json").write_text(json.dumps({"driver": "random_day", "trace_calls": 1}))
    (tmp_path / "bench" / "limits" / "burst.toy.json").write_text(json.dumps({"tables_changed": 0}))
    (tmp_path / "bench" / "metrics" / "toy_share.py").write_text("def read(ctx):\n    return 42.0\n")
    spec = {
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "burst.toy", "config": "toy", "traffic": "burst", "chips": 1}],
        "end_to_end": [
            {"name": "rate", "workloads": ["burst.toy"]},
            {"name": "setup_s"},
            {"name": "other_rate", "workloads": ["elsewhere"]},
        ],
        "per_layer": [
            {"name": "toy_share", "moves": "rate", "unit": "%"},
            {"name": "not_here", "moves": "other_rate", "unit": "%"},
        ],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = run.load_cell(str(tmp_path), "burst.toy")
    assert cell.config == {"num_envs": 4} and cell.traffic["trace_calls"] == 1
    assert cell.limits == {"tables_changed": 0} and cell.rate_metric == "rate"
    assert [m["name"] for m in cell.per_layer] == ["toy_share"]
    assert run.load_reader(str(tmp_path), "toy_share")(None) == 42.0


# ---------------------------------------------------------------------------
# peaks and counts
# ---------------------------------------------------------------------------
def test_peaks_table():
    p = peaks.peak("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu")


def _paper16():
    with open(os.path.join(ROOT, "bench", "configs", "paper16_shop.json")) as f:
        config = json.load(f)
    env, params = tables.build(config)
    return config, tables.as_dict(params)


def test_mlp_flops_paper16():
    config, tabs = _paper16()
    sh = counts.station_shapes(config, tabs)
    assert (sh["obs_dim"], sh["n_heads"], sh["n_levels"]) == (137, 17, 21)
    # actor 137-128-128-357, critic 137-128-128-1, 2 FLOPs per multiply-add
    actor = 2 * (137 * 128 + 128 * 128 + 128 * 357)
    critic = 2 * (137 * 128 + 128 * 128 + 128 * 1)
    assert counts.mlp_forward_flops(137, (128, 128), 357) == actor + critic == 227_328
    # rollout forward + 4 epochs x (forward + backward)
    assert counts.ppo_update_flops(config, sh) == 4096 * 300 * 227_328 * 13


def test_env_step_bytes_paper16():
    config, tabs = _paper16()
    state = 10 * 16 * 4 + 16 * 4 + 8 * 4 + 4 + 4 + 4 + 288 * 4  # ports, t_rem, totals, b_soc, t, day, price row
    assert state == 1900
    rows = 6 * 16 * 4 + (3 * 17 * 4 + 3 * 4) + 5 * 8 * 4 + 5 * 4 + 31 * 4
    assert counts.env_step_bytes(config, tabs) == 2 * state + 17 * 4 + 137 * 4 + rows == 5320
    assert counts.env_step_ops(config, tabs) == 2 * 3 * 17


# ---------------------------------------------------------------------------
# tables, keys, comparisons
# ---------------------------------------------------------------------------
def test_tables_match_their_fingerprint_and_a_change_shows():
    _, tabs = _paper16()
    with open(os.path.join(ROOT, "bench", "configs", "paper16_shop.tables.json")) as f:
        recorded = json.load(f)
    assert tables.check(tabs, recorded) == []
    tabs["price_buy_table"] = tabs["price_buy_table"] * np.float32(1.001)
    assert tables.check(tabs, recorded) == ["price_buy_table"]


def test_stream_keys_keep_high_seed_bits():
    import jax

    from bench.harness.drivers import stream

    a, b = (np.asarray(jax.random.key_data(stream(s, "calls"))) for s in (5, 5 + 2**32))
    assert not np.array_equal(a, b)
    assert not np.array_equal(a, np.asarray(jax.random.key_data(stream(5, "reset"))))


def test_worst_leaf_gap_by_hand():
    want = {"a": np.array([3.0, 4.0]), "b": np.array([0.0, 1.0]), "c": np.array([0.0, 2.0])}
    got = {"a": np.array([3.0, 4.5]), "b": np.array([0.0, 1.0]), "c": np.array([0.0, 2.0])}
    gaps = compare.leaf_norm_gaps(got, want)
    assert gaps == {"a": pytest.approx((np.hypot(3, 4.5) - 5.0) / 5.0), "b": 0.0, "c": 0.0}
    assert compare.worst_leaf_norm_gap(got, want) == gaps["a"]
    assert compare.median_leaf_norm_gap(got, want) == 0.0
    # a quiet leaf is measured against the median leaf's norm, and can be skipped
    got["b"] = np.array([0.0, 1.2])
    assert compare.worst_leaf_norm_gap(got, want, skip={"a"}) == pytest.approx(0.2 / 2.0)
    assert compare.median_leaf_norm_gap(got, want, skip={"c"}) == pytest.approx(0.5 * (gaps["a"] + 0.1))
    assert compare.quiet_leaves({"x": np.ones(4), "y": np.ones(4), "z": np.full(4, 1e-5)}) == {"z"}


def test_env_sum_mismatch_by_hand():
    want = np.array([[100.0, 5.0, 3.0, 0.0], [100.0, 5.0, 3.0, 0.0], [100.0, 5.0, 3.0, 0.0]])
    got = want.copy()
    got[1, 0] += 0.05  # within 1e-3 of 100
    got[2, 2] += 1  # one more car arrived
    assert compare.env_sum_mismatch(got, want, 1e-3).tolist() == [False, False, True]


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------
def test_op_names_and_scopes():
    assert trace.parse_op_name("%fusion.685 = f32[4096]{0:T(1024)} fusion(f32[4]{0} %x), kind=kLoop") == ("fusion.685", "fusion")
    assert trace.parse_op_name("%while.3 = (s32[]{:T(128)}, f32[8]) while((s32[]) %t)")[1] == "while"
    hlo = '  %fusion.1 = f32[8]{0} fusion(%x), metadata={op_name="jit(call)/ppo/rollout/while/body/env/reward/add"}\n'
    assert trace.scope_map(hlo) == {"fusion.1": "jit(call)/ppo/rollout/while/body/env/reward/add"}
    assert trace.in_scope("jit(call)/ppo/rollout/while/body/env/reward/add", "env/")
    assert not trace.in_scope("jit(call)/ppo/update/loss/add", "ppo/rollout")
    assert trace.layer_of("jit(c)/while/body/wrap/AutoReset/env/depart_arrive/x") == "env/depart_arrive"
    assert trace.layer_of("jit(c)/env/draw_cars/vmap(env/draw_soc0)/jit(_gamma)/log") == "env/draw_soc0"


def test_reducer_by_hand():
    """Three ops on one chip inside a 100 ns window: a 20 ns env op, a
    10 ns wrapper op overlapping it by 5 ns, a 30 ns update op."""
    ops = [
        trace.Op(10, 20, "a", "jit(f)/wrap/AutoReset/env/reward/add", 0, "fusion"),
        trace.Op(25, 10, "b", "jit(f)/wrap/AutoReset/select", 0, "fusion"),
        trace.Op(60, 30, "c", "jit(f)/ppo/update/dot", 0, "fusion"),
        trace.Op(200, 5, "d", "jit(f)/env/reward", 0, "fusion"),  # outside the window
    ]
    spans = [trace.Span(0, 100, trace.WINDOW_SPAN), trace.Span(0, 95, trace.CALL_SPAN)]
    td = trace.TraceData(ops, spans)
    assert td.window_ns == 100 and td.busy_ns() == 20 + 5 + 30
    assert td.scope_ns("env/") == 20 and td.scope_ns("wrap/") == 30
    assert td.scope_ns("wrap/", exclude=("env/",)) == 10
    assert td.scope_ns("ppo/rollout") is None
    assert td.idle_gaps()[0] == [f"{trace.CALL_SPAN} at +0.000 ms", 25e-9]
    gaps = sorted(g[1] for g in td.idle_gaps())
    assert gaps == pytest.approx([10e-9, 10e-9, 25e-9])
    assert td.top_ops()[0] == ["ppo/update:fusion", 30e-9]


@pytest.mark.parametrize("name", ["sim_trace.json.gz", "ppo_trace.json.gz"])
def test_reducer_on_recorded_chip_trace(name):
    path = os.path.join(DATA, name)
    ops, spans = trace.load_records(path)
    td = trace.TraceData(ops, spans)
    busy = td.busy_ns()
    assert 0 < busy <= td.window_ns
    env = td.scope_ns("env/")
    assert env is not None and 0 < env <= sum(o.dur_ns for o in td.ops)
    wrap_self = td.scope_ns("wrap/", exclude=("env/",))
    assert wrap_self is None or wrap_self < td.scope_ns("wrap/")
    assert len(td.top_ops()) <= 10 and len(td.idle_gaps()) <= 10
    assert all(isinstance(k, str) and v >= 0 for k, v in td.top_ops() + td.idle_gaps())
