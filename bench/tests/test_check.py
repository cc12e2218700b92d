"""The check on the CPU at a tiny size: the program passes, and the control
and each planted fault that the cell can have turn ``correct`` false.

Each case drives the rest of a run (set-up, the window of the cell's own
traffic, the check) with the look for a chip skipped, at 20,000 x 100 with
1,000 pool queries and the cell's own limits.
"""
import copy

import pytest

from bench import faults, run

SPEC = run.load_spec()
CELLS = [c["name"] for c in SPEC["workloads"]]
CELL_FAULTS = [
    (cell, fault)
    for cell in CELLS
    for fault in faults.FAULTS
    if run.load_cell(cell, SPEC)[2]["op"] == "build" or fault not in faults.BUILD_FAULTS
]


def tiny(cell):
    entry, config, mix = run.load_cell(cell, SPEC)
    config = copy.deepcopy(config)
    config["shape"].update(n=20000, queries=1000)
    mix = dict(mix)
    if mix["op"] == "search" and mix["loop"] == "closed":
        mix["request_queries"] = 64
    if mix["loop"] == "open":
        mix["rate_per_s"] = 2000  # several requests to a micro-batch
    return entry, config, mix


def run_tiny(cell, system_of=None, seconds=1.0):
    entry, config, mix = tiny(cell)
    system = None if system_of is None else system_of(config)
    return run.run_cell(entry, config, mix, seed=2**32 + 17, seconds=seconds, trace=False,
                        metrics=run.cell_metrics(SPEC, cell, per_layer=False), system=system)


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct(cell):
    result = run_tiny(cell)
    assert result["correct"], result["check"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "check"


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    result = run_tiny(cell, faults.control)
    assert not result["correct"], result["check"]


@pytest.mark.parametrize(("cell", "fault"), CELL_FAULTS)
def test_fault_is_not_correct(cell, fault):
    result = run_tiny(cell, lambda config: faults.Faulty(run.Program(config["index"]), fault))
    assert not result["correct"], result["check"]
