"""The lower-precision control, put in the program's place at a toy size
on the CPU, reads at least three times what the program reads on one of
the cell's numbers, while the program passes the cell's limits; a
training cell's control fails them there too.  (The readings that set the
limits were taken on the chip at the cells' own sizes with
``bench/control.py`` and are kept in each cell's file, where
``test_benchmark_json`` holds every limit between them.  At sizes a CPU
test can hold, the serving control's logit gaps lie about the serving
limit, which full depth leaves far behind; see PERF.md.)"""

import pytest

from bench import control
from bench.tests import toy

CONTROL = {"fcn-synthetic-4h": "bf16", "smollm-135m": "fp8"}


@pytest.mark.parametrize("name", toy.CELLS)
def test_control_reads_apart_from_the_program(name):
    c, w = toy.cell(name)
    line = control.measure(name, 2**31 + 5, 0.5, control=CONTROL[c["name"]],
                           workload=w, config=c, require_chip=False)
    limits = w["limits"]
    assert all(v <= limits[k] for k, v in line["program"].items()), line
    assert any(v >= 3 * line["program"][k] and v > 0
               for k, v in line["control"].items()), line
    if w["kind"] == "train":
        assert line["control_correct"] is False, line
