"""The check catches each fault a cell can have, and its control.

Each run skips the harness's look for a chip and drives the rest of a
run at a tiny size on the CPU, with the timed path broken underneath:

* training: a step that returns its state unchanged; half of Psi left
  out, the mean taken over the rest; and the control, the program's own
  bfloat16 storage path;
* serving: an answer altered where the engine produces it (one per
  flush); half of each flush answered wrongly; and the control, the
  reference rounded to float8 put in the program's place.

The faults are listed by the kind and query of a cell's mix
(``tiny.FAULTS``), and every cell of the benchmark, with the rehearsal's
own cells, runs its kind's list at its own shrunk configuration and
against its own limits.  A single chip has no exchange between chips to
leave out.  The sound run of each cell reads correct at the same size
(the rehearsal).
"""
from __future__ import annotations

import pytest

from . import tiny

CELLS = [w["name"] for w in tiny.bench()["workloads"]]
CASES = [(w, o) for w in CELLS for o in tiny.FAULTS.get(tiny.kind_of(w), [])]


@pytest.mark.parametrize("workload,options", CASES,
                         ids=[f"{w}-{next(iter(o.values()))}"
                              for w, o in CASES])
def test_fault_or_control_reads_not_correct(workload, options):
    out = tiny.run(workload, **options)
    assert out["correct"] is False
    assert tiny.over_limits(out), out["checks"]


def test_every_cell_has_a_fault_case():
    for w in CELLS:
        assert tiny.kind_of(w) in tiny.FAULTS, w
