"""The check catches a broken timed path: a run driven with a fault
planted underneath comes out not correct (the harness's look for a chip
is skipped; everything else of a run is driven)."""
import time

import pytest

from perfbench import harness
from perfbench.tests import tiny


@pytest.mark.parametrize("fault", ["token", "drop_half", "retrieval"])
def test_planted_fault_reads_not_correct(tmp_path, fault):
    root = tiny.make_copy(tmp_path)
    res = harness.run(root, "tiny.rag", 4242, 3.0, False,
                      time.perf_counter(), require_tpu=False, fault=fault)
    assert res["correct"] is False
    over = [k for k, c in res["checked"].items() if c["value"] > c["limit"]]
    assert over, res["checked"]


def test_control_in_the_programs_place_reads_over_the_limit(tmp_path):
    """The float8 control's widest gap, on the tiny cell's own served
    sequences, exceeds the limit the float32 program meets."""
    root = tiny.make_copy(tmp_path)
    res = harness.run(root, "tiny.rag", 77, 6.0, False,
                      time.perf_counter(), require_tpu=False,
                      control="fp8")
    assert res["correct"] is True
    for name, v in res["control"].items():
        assert v > res["checked"][name]["limit"], (name, v)
