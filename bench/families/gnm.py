"""Uniform G(n, m) with m = ``edges_per_vertex`` n and vertex weights
uniform in [``weight_lo``, ``weight_hi``]: ``graphs.gnm``, draw for draw."""

from bench import graphs


def generate(n: int, config: dict, seed: int) -> graphs.Csr:
    return graphs.gnm(n, n * config["edges_per_vertex"], seed,
                      config["weight_lo"], config["weight_hi"])
