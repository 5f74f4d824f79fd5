"""``correct`` comes out false under the control and under each fault a
cell can have, with the rest of a run driven as the command drives it."""
import pytest

from chipbench.tests import tiny

CASES = [("ycsb-c.uniform.flat", "control"),
         ("ycsb-c.uniform.flat", "half_batch"),
         ("ycsb-c.uniform.flat", "altered_answer"),
         ("ycsb-b.zipf.flat", "control"),
         ("ycsb-b.zipf.flat", "state_unchanged"),
         ("ycsb-b.zipf.flat", "half_batch"),
         ("ycsb-b.zipf.flat", "altered_answer")]


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_makes_the_run_incorrect(workload, fault):
    r = tiny.run(workload, fault=fault, seconds=0.3)
    assert r["correct"] is False
    assert r["checks"]["wrong_reads"]["value"] > 0


def test_low_word_only_is_refused_at_32_bit_keys():
    # 32-bit keys have no high word to drop: nothing to catch
    with pytest.raises(ValueError, match="32-bit"):
        tiny.run("ycsb-c.uniform.flat", fault="low_word_only", seconds=0.3)
