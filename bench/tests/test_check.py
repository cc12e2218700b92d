"""The check on the CPU at a tiny size: the program passes, and the control
and each planted fault that the cell can have turn ``correct`` false.

Each case drives the rest of a run (set-up, the window of the cell's own
traffic, the check) with the look for a chip skipped, at 20,000 x 100 with
1,000 pool queries and the cell's own limits. A cell served on several shards
runs in a child process with as many CPU devices (``multichip.py``).
"""
import copy

import pytest

from bench import faults, run
from bench.tests import multichip

SPEC = run.load_spec()
CELLS = [c["name"] for c in SPEC["workloads"]]


def can_have(cell, fault):
    _, config, mix = run.load_cell(cell, SPEC)
    if fault in faults.SHARD_FAULTS:
        return run.shards_of(config) > 1
    return mix["op"] == "build" or fault not in faults.BUILD_FAULTS


CELL_FAULTS = [(cell, fault) for cell in CELLS for fault in faults.FAULTS if can_have(cell, fault)]


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


def system_of(name, config):
    """The system of a case by its name: ``program`` (the program itself),
    ``control``, or a planted fault of ``bench/faults.py``."""
    if name == "program":
        return None
    if name == "control":
        return faults.control(config)
    return faults.Faulty(run.Program(config["index"], run.shards_of(config)), name)


def run_here(cell, system="program", seconds=1.0):
    entry, config, mix = tiny(cell)
    return run.run_cell(entry, config, mix, seed=2**32 + 17, seconds=seconds, trace=False,
                        metrics=run.cell_metrics(SPEC, cell, per_layer=False),
                        system=system_of(system, config))


def run_tiny(cell, system="program", seconds=1.0):
    shards = run.shards_of(run.load_cell(cell, SPEC)[1])
    if shards > 1:
        return multichip.call(shards, "bench.tests.test_check:run_here", cell, system, seconds)
    return run_here(cell, system, seconds)


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct(cell):
    result = run_tiny(cell)
    assert result["correct"], result["check"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "check"


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    result = run_tiny(cell, "control")
    assert not result["correct"], result["check"]


@pytest.mark.parametrize(("cell", "fault"), CELL_FAULTS)
def test_fault_is_not_correct(cell, fault):
    result = run_tiny(cell, fault)
    assert not result["correct"], result["check"]
