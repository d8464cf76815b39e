"""The benchmark's graphs: a frozen copy of the program's dataset replicas.

A configuration's graph is part of the yardstick, so its generator lives
here and does not move when the program's does.  The arithmetic is that of
the program's `graphs/csr.py` (`random_power_law`, `random_community_graph`,
`from_edges`) and `graphs/datasets.py` (`make_dataset`) at the time the
benchmark was written; `tests/test_chipbench_reference.py` checks that both
still give the same CSR at a small size.

A graph is ``(indptr, indices)``: row ``v`` (the destination) gathers the
sources ``indices[indptr[v]:indptr[v + 1]]``, as in the program.
"""
from __future__ import annotations

import numpy as np

__all__ = ["make_graph", "from_edges", "power_law", "community"]


def from_edges(num_nodes: int, src, dst, *, symmetrize: bool = False):
    """CSR from an edge list src -> dst, deduplicated, rows sorted."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if symmetrize:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    key = np.unique(dst * num_nodes + src)
    dst, src = key // num_nodes, key % num_nodes
    order = np.argsort(dst, kind="stable")
    src, dst = src[order], dst[order]
    indptr = np.zeros(num_nodes + 1, dtype=np.int64)
    np.add.at(indptr, dst + 1, 1)
    return np.cumsum(indptr), src.astype(np.int32)


def power_law(num_nodes: int, avg_degree: float, *, exponent: float,
              seed: int):
    """Chung-Lu graph with Pareto target degrees (Type III replicas)."""
    rng = np.random.default_rng(seed)
    w = rng.pareto(exponent - 1.0, size=num_nodes) + 1.0
    w = w / w.mean() * avg_degree
    w = np.clip(w, 0.25, num_nodes / 4)
    num_edges = int(num_nodes * avg_degree)
    p = w / w.sum()
    src = rng.choice(num_nodes, size=num_edges, p=p)
    dst = rng.choice(num_nodes, size=num_edges, p=p)
    keep = src != dst
    return from_edges(num_nodes, src[keep], dst[keep], symmetrize=True)


def community(num_communities: int, community_size: int, *, p_intra: float,
              seed: int):
    """Disjoint Erdos-Renyi communities of equal size (Type II replicas:
    batched small graphs with no edge between them)."""
    rng = np.random.default_rng(seed)
    n = num_communities * community_size
    m = int(p_intra * community_size * (community_size - 1) / 2)
    srcs, dsts = [], []
    for c in range(num_communities):
        lo, hi = c * community_size, (c + 1) * community_size
        if m > 0:
            a = rng.integers(lo, hi, size=m)
            b = rng.integers(lo, hi, size=m)
            keep = a != b
            srcs.append(a[keep])
            dsts.append(b[keep])
    src = np.concatenate(srcs) if srcs else np.zeros(0, np.int64)
    dst = np.concatenate(dsts) if dsts else np.zeros(0, np.int64)
    return from_edges(n, src, dst, symmetrize=True)


def _power_law(spec: dict):
    n = int(spec["num_nodes"])
    return power_law(n, spec["num_edges"] / spec["num_nodes"],
                     exponent=spec["exponent"], seed=spec["seed"])


def _community(spec: dict):
    """Without ``community_size`` or ``num_communities``: communities of
    up to 40 nodes at the published mean degree (the DD replica).  With
    either: that many communities of that size (the other one follows from
    ``num_nodes``), drawing ``num_edges`` undirected pairs in all, as
    `power_law` draws them."""
    n = int(spec["num_nodes"])
    size, num = spec.get("community_size"), spec.get("num_communities")
    if size is None and num is None:
        avg_deg = spec["num_edges"] / spec["num_nodes"]
        comm = max(2, min(40, int(np.sqrt(n))))
        return community(max(1, n // comm), comm,
                         p_intra=min(0.9, avg_deg / max(comm - 1, 1)),
                         seed=spec["seed"])
    size = int(size or n // num)
    num = int(num or max(1, n // size))
    return community(num, size,
                     p_intra=spec["num_edges"] / (num * size * (size - 1) / 2),
                     seed=spec["seed"])


_GENERATORS = {"power_law": _power_law, "community": _community}


def make_graph(spec: dict):
    """The configuration's ``graph`` entry -> ``(indptr, indices)``.

    ``spec["generator"]`` names the generator (``power_law``: Chung-Lu
    with ``exponent``; ``community``: disjoint Erdos-Renyi communities,
    optionally of ``community_size`` nodes, ``num_communities`` of them);
    ``spec["num_nodes"]`` and ``spec["num_edges"]`` are the published
    sizes it aims at; ``spec["seed"]`` fixes the graph.
    """
    name = spec["generator"]
    if name not in _GENERATORS:
        raise ValueError(f"no generator {name!r}; known: "
                         f"{sorted(_GENERATORS)}")
    return _GENERATORS[name](spec)
