"""The check catches each fault a cell can have, and its control.

Each run skips the harness's look for a chip and drives the rest of a
run at a tiny size on the CPU, with the timed path broken underneath:

* training: a step that returns its state unchanged; half of Psi left
  out, the mean taken over the rest; and the control, the program's own
  bfloat16 storage path;
* serving: an answer altered where the engine produces it (one per
  flush); half of each flush answered wrongly; and the control, the
  reference rounded to float8 put in the program's place.

A single chip has no exchange between chips to leave out.  The sound
run of each cell reads correct at the same size (the rehearsal).
``yahoo.train`` runs the code of ``netflix.train`` on other sizes.
"""
from __future__ import annotations

import pytest

from chipbench import harness

from . import tiny

CASES = [
    ("netflix.train", {"fault": "unchanged"}),
    ("netflix.train", {"fault": "half_batch"}),
    ("netflix.train", {"variant": {"dtype": "bfloat16"}}),
    ("yahoo.serve_topk", {"fault": "altered"}),
    ("yahoo.serve_topk", {"fault": "half_batch"}),
    ("yahoo.serve_topk", {"check_dtype": "float8_e4m3fn"}),
    ("netflix.serve_predict", {"fault": "altered"}),
    ("netflix.serve_predict", {"fault": "half_batch"}),
    ("netflix.serve_predict", {"check_dtype": "float8_e4m3fn"}),
]


@pytest.mark.parametrize("workload,options", CASES,
                         ids=[f"{w}-{next(iter(o.values()))}"
                              for w, o in CASES])
def test_fault_or_control_reads_not_correct(workload, options):
    out = tiny.run(workload, **options)
    assert out["correct"] is False
    over = [name for name, c in out["checks"].items()
            if c["limit"] is not None and not c["value"] <= c["limit"]]
    assert over, out["checks"]


def test_every_cell_has_a_fault_case():
    cells = {w["name"] for w in harness.load_benchmark()["workloads"]}
    assert cells <= {w for w, _ in CASES} | {"yahoo.train"}
