"""A whole run of each cell, driven on the CPU at the program's
smoke size with the chip check skipped, comes out correct; with the timed
path broken underneath, ``correct`` comes out false. On the sound path
the control, the reference in fp8 arithmetic, reads a widest gap at
least three times the served program's.

The faults a serving cell can have: a token altered where the decode
step produces it, a decode step that returns its state unchanged, and
half of the batch left out. (One chip: no exchange between chips.)
The sound path is also driven through the generator's open loop.
"""

import pytest

from bench_helpers import check_run, fault_cases


@pytest.mark.parametrize("cell,fault", fault_cases())
def test_run_correct_only_on_the_sound_path(cell, fault, monkeypatch):
    check_run(cell, fault, monkeypatch)


@pytest.mark.parametrize("cell", [c for c, f in fault_cases()
                                  if f is None])
def test_open_loop_run_is_correct(cell, monkeypatch):
    check_run(cell, None, monkeypatch, arrival="poisson")
