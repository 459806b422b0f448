"""The program's multi-seed BFS region growing with cut refinement
(``repro.graph.partition.bfs_grow_partition``): the stand-in for METIS.

Configuration keys: ``n_parts`` and ``seed``."""


def partition(graph, spec: dict):
    from repro.graph.partition import bfs_grow_partition

    return bfs_grow_partition(graph, int(spec["n_parts"]), seed=int(spec["seed"]))
