"""With the four-device cell's timed path broken underneath, ``correct``
comes out false: each fault the cell can have, the exchange between chips
left out among them (tiny size, four CPU devices in a child process)."""
from __future__ import annotations

import pytest

from bench.tests.tiny import on_four_devices

FAULTS = ["unchanged_state", "half_batch", "altered_answer", "no_exchange"]


@pytest.fixture(scope="module")
def faulted():
    return dict(zip(FAULTS, on_four_devices("ppo.paper16_shop_dp4", [(4, f) for f in FAULTS])))


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_caught_on_four_devices(faulted, fault):
    line = faulted[fault]["line"]
    assert faulted[fault]["rc"] == 0 and line["device"]["count"] == 4
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
