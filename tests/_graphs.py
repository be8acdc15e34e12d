"""The fixed graphs the oracle tests sweep (``test_mfbc_core``,
``test_metrics``): directed and undirected, weighted and unweighted, a
79-hop weighted path whose sweeps run past ``core.mfbf.TRACE_CAP`` and a
32-leaf star."""
from repro.graphs.generators import (erdos_renyi, path_graph, ring_of_cliques,
                                     rmat, star_graph, uniform_random)

GRAPHS = {
    "path8": lambda: path_graph(8),
    "path8_w": lambda: path_graph(8, weighted=True, seed=3),
    "path80_w": lambda: path_graph(80, weighted=True, seed=4),
    "star33": lambda: star_graph(33),
    "roc4x4": lambda: ring_of_cliques(4, 4),
    "roc3x5_w": lambda: ring_of_cliques(3, 5, weighted=True, seed=1),
    "er40": lambda: erdos_renyi(40, 0.15, seed=7),
    "er40_w": lambda: erdos_renyi(40, 0.15, seed=7, weighted=True, max_weight=9),
    "er40_dir_w": lambda: erdos_renyi(40, 0.12, seed=11, weighted=True,
                                      max_weight=7, directed=True),
    "rmat5": lambda: rmat(5, 4, seed=5),
    "rmat5_dir_w": lambda: rmat(5, 3, seed=9, weighted=True, max_weight=5,
                                directed=True),
    "uni60": lambda: uniform_random(60, 6.0, seed=13),
}
