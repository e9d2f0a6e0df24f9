"""A run whose timed path is broken underneath reads ``correct`` false,
for each fault its cell can have: a step that returns its state unchanged,
an answer altered where it is produced, half of a batch left out, and the
exchange between chips left out."""

import numpy as np
import pytest

from bench.tests.helpers import in_four_devices, measure_small


def _unchanged_reduce(real):
    def disredu(pg, cfg):
        from repro.core import rules as R

        state, prob, rounds = real(pg, cfg)
        return R.init_state(prob.w0, prob.is_local, prob.is_ghost), prob, rounds
    return disredu


def _altered_reduce(real):
    def disredu(pg, cfg):
        state, prob, rounds = real(pg, cfg)
        s = state.status
        return state._replace(status=s.at[0].set((s[0] + 1) % 4)), prob, rounds
    return disredu


def _altered_second_graph(real):
    """An altered status on every graph but the first the window sees."""
    seen = []

    def disredu(pg, cfg):
        state, prob, rounds = real(pg, cfg)
        if seen and pg is not seen[0]:
            s = state.status
            state = state._replace(status=s.at[0].set((s[0] + 1) % 4))
        seen.append(pg)
        return state, prob, rounds
    return disredu


def _unchanged_rnp(real):
    def solve(pg, algo, cfg):
        members, state, it = real(pg, algo, cfg)
        return np.zeros_like(members), state, it
    return solve


def _altered_rnp(real):
    def solve(pg, algo, cfg):
        members, state, it = real(pg, algo, cfg)
        members = members.copy()
        members[0] = not members[0]
        return members, state, it
    return solve


def _half_batch(real):
    def solve_batch(self, graphs):
        return real(self, graphs)[:len(graphs) // 2]
    return solve_batch


def _altered_answer(real):
    def solve_batch(self, graphs):
        out = real(self, graphs)
        m = out[0].members.copy()
        m[0] = not m[0]
        out[0] = out[0]._replace(members=m)
        return out
    return solve_batch


FAULTS = [
    ("gnm18.reduce", "repro.core.distributed", "disredu", _unchanged_reduce),
    ("gnm18.reduce", "repro.core.distributed", "disredu", _altered_reduce),
    ("gnm18.reduce", "repro.core.distributed", "disredu",
     _altered_second_graph),
    ("gnm11.rnp", "repro.core.solvers", "solve", _unchanged_rnp),
    ("gnm11.rnp", "repro.core.solvers", "solve", _altered_rnp),
    ("serve.mix16", "repro.core.serve", "MWISService.solve_batch",
     _half_batch),
    ("serve.mix16", "repro.core.serve", "MWISService.solve_batch",
     _altered_answer),
]


@pytest.mark.parametrize("workload,module,attr,fault", FAULTS,
                         ids=[f[3].__name__.strip("_") for f in FAULTS])
def test_fault_reads_not_correct(monkeypatch, workload, module, attr, fault):
    import importlib

    owner = importlib.import_module(module)
    *path, name = attr.split(".")
    for p in path:
        owner = getattr(owner, p)
    monkeypatch.setattr(owner, name, fault(getattr(owner, name)))
    out = measure_small(workload)
    assert not out["correct"]
    assert out["failed"] > 0


def test_exchange_left_out_reads_not_correct():
    out = in_four_devices(
        "import json\n"
        "import jax.numpy as jnp\n"
        "from repro.core import exchange as X\n"
        "X.exchange_shmap = lambda state, *a, **k: "
        "(state, jnp.zeros((), bool))\n"
        "from bench.tests.helpers import measure_small\n"
        "out = measure_small('gnm14x4.reduce')\n"
        "print(json.dumps(dict(correct=out['correct'], "
        "checks={k: v['value'] for k, v in out['checks'].items()})))")
    assert not out["correct"], out
