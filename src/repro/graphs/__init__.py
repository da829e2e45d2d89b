from repro.graphs.generators import (  # noqa: F401
    bipartite_powerlaw,
    bipartite_random,
    genrmf,
    grid_road,
    powerlaw,
    random_sparse,
    washington_rlg,
)
