"""The program's hashed placement (``repro.graph.partition.hash_partition``):
Pregel's and Giraph's default, ``hash(id) mod N``, which balances vertices
and arcs and cuts nearly every edge.

Configuration keys: ``n_parts`` and ``seed``."""


def partition(graph, spec: dict):
    from repro.graph.partition import hash_partition

    return hash_partition(graph, int(spec["n_parts"]), seed=int(spec["seed"]))
