"""The control: the reference with its weight sums and folded-weight
offset kept in bfloat16, put in the program's place, reads not correct
against the exact reference, on three seeds."""

import pytest

from bench import control, run as R
from bench.tests.helpers import load_spec

#: sizes at which the control is read here (the cells' own sizes for
#: serving and the four-PE reduce; smaller ones for the others)
SIZES = [
    ("gnm18.reduce", {"n_per_pe": 8192}),
    ("gnm11.rnp", {"n_per_pe": 1024}),
    ("serve.mix16", {}),
    ("gnm14x4.reduce", {}),
]


@pytest.mark.parametrize("workload,override", SIZES,
                         ids=[w for w, _ in SIZES])
def test_control_reads_not_correct(workload, override):
    spec = load_spec()
    _, _, traffic = R.cell_spec(spec, workload)
    for seed in (1, 2, 3):
        out = control.control(workload, seed, traffic=dict(traffic, **override),
                              spec=spec)
        assert not out["correct"], (seed, out["checks"])
        assert any(c["value"] > c["limit"] for c in out["checks"].values())
