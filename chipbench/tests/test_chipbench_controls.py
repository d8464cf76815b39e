"""The control, the reference with three-pass bfloat16 products in the
program's place, at a size a CPU test holds (5,000 nodes at the replica's
mean degree).  On the chip at the cell's size it reads far above the
cell's limits (PERF.md); on the CPU its products are the same but the
program's own readings are smaller too, so here it is held against the
sound reading of the same size, and the reference against itself passes
the limits."""
import pytest

from conftest import tiny_cell

from chipbench.lib import compare, harness


def _cell():
    published = harness.load_cell("gcn-blogcatalog.train").config["graph"]
    cell = tiny_cell("gcn-blogcatalog.train")
    g = cell.config["graph"]
    g["num_nodes"] = 5000
    g["num_edges"] = int(5000 * published["num_edges"]
                         / published["num_nodes"])
    return cell


@pytest.mark.parametrize("seed", [41, 42, 43])
def test_three_pass_control_reads_far_above_the_program(seed):
    from chipbench import controls

    cell = _cell()
    [(_, ctl)] = list(controls.train_control(cell, [seed], "high"))
    [(_, prog)] = list(controls.train_program(cell, [seed]))
    assert ctl["grad_diff"] > 10 * prog["grad_diff"], (ctl, prog)


@pytest.mark.parametrize("seed", [41, 42, 43])
def test_reference_against_itself_passes_the_limits(seed):
    from chipbench import controls

    cell = _cell()
    [(_, numbers)] = list(controls.train_control(cell, [seed], "highest"))
    numbers = {k: numbers[k] for k in cell.params["limits"]}
    ok, checks = compare.judge(numbers, cell.params["limits"])
    assert ok, checks
