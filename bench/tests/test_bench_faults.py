"""With the timed path broken underneath, a run's ``correct`` comes out
false: a step that leaves its state unchanged, half of the batch left out,
an answer altered where it is made (tiny size, host CPU)."""
from __future__ import annotations

import pytest

from bench.tests.tiny import run_tiny


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch", "altered_answer"])
@pytest.mark.parametrize("workload", ["ppo.paper16_shop", "sim.paper16_shop"])
def test_fault_is_caught(workload, fault):
    from bench.harness.faults import FAULTS
    from bench.tests.tiny import tiny_cell

    kind = tiny_cell(workload).traffic["driver"]
    with FAULTS[fault](kind):
        rc, line, cell = run_tiny(workload)
    assert rc == 0
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
