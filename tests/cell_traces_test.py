"""What a cell's step is, written once (PR 74): the forward's jaxpr of every
one-chip train cell of ``BENCHMARK.json`` at its rehearsal size, traced by
``harness.cell_step_jaxpr`` and held to ``tests/pins/traces.json``'s
``step/<cell>``.  A change that leaves a cell alone leaves its line alone; a
new cell has its case by being in ``BENCHMARK.json`` and adds its line."""
import pytest

import harness


@pytest.mark.parametrize("cell", harness.train_cells(chips=1))
def cell_step_traces_as_pinned_test(cell):
    harness.pinned("step/" + cell, harness.cell_step_jaxpr(cell))
