"""The control (the reference computed in bfloat16, in the program's place)
fails its cell's limits, and the program passes them, at a test's size."""
import pytest

from gnsbench import control


@pytest.mark.parametrize("name", ["products_train", "papers_train_device",
                                  "products_serve"])
def test_control_fails_and_program_passes(tiny_cell, cpu, name):
    cell = tiny_cell(name)
    r = control.readings(cell, 4242, cpu, 1.0, lambda m: None)
    lim = cell.limits
    assert all(r["program"][k] <= lim[k] for k in lim), r["program"]
    assert any(r["control"][k] > lim[k] for k in r["control"]), r["control"]
    if "half_batch" in r:
        assert any(r["half_batch"][k] > lim[k] for k in r["half_batch"])
