"""The output check's control and planted faults at a size a CPU test
holds (``bench/calibrate.py``): the float32 reference against itself
reads nothing, and the reference computed in float8, or with
half of every batch left out, fails at least one number under the
cells' limits."""
import json

import pytest

from bench import check


@pytest.mark.parametrize("workload", ["sage-full", "sage-s", "gcn-full"])
def test_control_and_half_batch_fail_a_limit(cpu_cell, capsys, tiny_root,
                                             workload):
    from bench import calibrate
    from bench.registry import Registry
    assert calibrate.main(["--workload", workload, "--seeds", "3",
                           "--program", "0"], root=tiny_root) == 0
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()
            if x.startswith("{")]
    assert [r["reading"] for r in rows] == ["control", "half_batch"]
    limits = Registry(tiny_root).limits(workload)
    for r in rows:
        ok, checks = check.judge(r["values"], limits)
        assert not ok, (r["reading"], checks)


def test_reference_against_itself_reads_zero(cpu_cell, tiny_root):
    cell = cpu_cell.Cell("sage-full", tiny_root)
    ref = cell.reference(5)
    values = check.numbers(ref, cell.reference(5))
    assert values == {"loss0": 0.0, "loss1": 0.0, "loss2": 0.0,
                      "grad0": 0.0, "grad0_dist": 0.0, "change3": 0.0}
