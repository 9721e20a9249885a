"""Each cell rehearsed on the CPU with its timed path broken underneath,
once for each fault the cell can have: ``correct`` has to come out false."""

import pytest

from bench import harness
from bench.tests import toy
from bench.tests.test_rehearsal import rehearse

FAULTS = {"train": ("state_unchanged", "half_batch"),
          "serve_closed": ("token_altered",), "serve_open": ("token_altered",)}


@pytest.mark.parametrize("name,fault", [
    (n, f) for n in toy.CELLS for f in FAULTS[harness.load_workload(n)["kind"]]])
def test_a_broken_timed_path_is_not_correct(name, fault, tmp_path):
    run = rehearse(name, tmp_path, fault=fault)
    assert not run.correct, harness.check_lines(run)


