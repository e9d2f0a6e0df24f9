"""The benchmark's input generators: a uniform G(n, m) for the graph cells,
and the serving launcher's stream draw for draw."""

import numpy as np
import pytest

from bench import graphs


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 5])
def test_gnm_is_simple_and_uniform_over_ids(seed):
    n = 1 << 14
    indptr, indices, weights = graphs.gnm(n, 4 * n, seed)
    assert indptr[-1] == 8 * n
    src = np.repeat(np.arange(n), np.diff(indptr))
    assert (src != indices).all()
    assert np.unique(src.astype(np.int64) * n + indices).shape[0] == 8 * n
    assert weights.min() >= 1 and weights.max() <= 200
    # every tenth of the ids keeps the average degree 8
    deg = np.diff(indptr)[: n - n % 10].reshape(10, -1).mean(axis=1)
    assert np.abs(deg - 8).max() < 0.3, deg


def test_serve_stream_is_the_launchers():
    from repro.core.serve import MWISService, ServeConfig
    from repro.launch.serve import mwis_requests

    cells = MWISService(ServeConfig()).cells
    want = mwis_requests(cells, 12, 4, seed=0)
    got = graphs.serve_stream([dict(L=c.L, E=c.E) for c in cells], 12, 4,
                              0.8, 0)
    assert len(got) == len(want)
    for (indptr, indices, w), g in zip(got, want):
        assert (indptr == g.indptr).all() and (indices == g.indices).all()
        assert (w == g.weights).all()
