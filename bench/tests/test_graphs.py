"""The benchmark's input generators: a uniform G(n, m) for the graph cells,
and the serving launcher's stream draw for draw."""

import hashlib
import json
import os

import numpy as np
import pytest

from bench import cells, graphs


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


#: SHA-256 of each graph's (indptr, indices, weights) bytes, in the order
#: of the traffic file's graph_seeds, at the file's full size, as
#: graphs.gnm made them before graph families were found by name
GNM_HASHES = {
    "reduce18": [
        "1d82bedceb442195e4e4df8ec6e983061f49fede3c780bf01abf1ed46f30e25c",
        "32a043a8dfba051c84cb3b8b1a31723c0dc07b04df0d569e035a6acadbea5ebc"],
    "rnp11": [
        "6b24bee91b9641dd3e84c08896b24433393212d47df9498e21a0c8bd9183d1ba",
        "c43beedd3e3928e327f6bc590f6facf398f66bb6cc988420e617523bf6592261"],
    "reduce14x4": [
        "39a6c65f989905f6e801b2745c976eb1b61321b91426f2f408707dd4791ccf97",
        "b72d89402ae11ac10e19e38feeecf7ebc04af2cdbcab2c6d2def4e696d2e798a"],
}


@pytest.mark.parametrize("traffic", sorted(GNM_HASHES))
def test_gnm_family_makes_the_cells_graphs_byte_for_byte(traffic):
    def load(rel):
        with open(os.path.join(cells.ROOT, "bench", rel)) as f:
            return json.load(f)

    config, t = load("configs/gnm-d8.json"), load(f"traffic/{traffic}.json")
    assert "family" not in config
    cell = cells.kind(t["kind"])(config, t, 2**31 + 5, cells.Spans())
    cell.make_inputs()
    got = [hashlib.sha256(b"".join(np.ascontiguousarray(a).tobytes()
                                   for a in g)).hexdigest()
           for g in cell.graphs]
    assert got == GNM_HASHES[traffic]
