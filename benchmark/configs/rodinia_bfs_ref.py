"""rodinia_bfs: data recipe, plain reference, control, a level's bytes.

Rodinia 3.1's breadth-first search (``opencl/bfs``): the graph is what the
suite's ``inputGen/graphgen.cpp`` makes (every node draws 2 to 4 edges to
uniformly random nodes, each entered in both directions), in the CSR of two
``int`` tables a node (``starting``, ``no_of_edges``) and one ``int`` entry
an edge (``edges``).  The reference is a level-synchronous BFS in numpy over
that CSR, written here and importing nothing of the program: the frontier's
adjacency lists are cut out with one ``repeat`` a level, the unvisited
targets marked, and a node's cost is the level that first reached it.

The STRUCTURE is the configuration's (``graph_seed``) and so are the source
nodes; ``--seed`` relabels the nodes and draws nothing else, so every seed
has other tables, other scatter targets and the same degrees and level
sizes.  The configuration relabels WITHIN work-groups (``seed_relabels``
``"within_work_groups"``: a random permutation of every 256 neighbouring
labels), which also keeps every node in its chunk of the launch ladder and
with it the passes a level's loop makes there: relabelled all over
(``true``) six seeds spread the call's median by 1.9 % (PERF.md, PR 40);
``false`` is the identity.  A traversal's source is none of the
kernels' arguments: the loop ``traversal`` keeps the cycle of sources and
leaves, for every call it made, the source and the levels it ran in
``arrays["traversals"]`` (base labels; ``arrays["relabel"]`` maps them).
"""

import numpy as np

_DRAWS = (2, 4)  # graphgen.cpp: MIN_EDGES, MAX_INIT_EDGES


def base_edges(nodes: int, graph_seed: int):
    """``(src, dst)`` of every directed edge entry, in base labels: node
    ``i`` draws 2 to 4 random nodes, and each draw enters ``i -> j`` and
    ``j -> i`` (self-loops and repeats stay, as graphgen keeps them)."""
    rng = np.random.default_rng([int(graph_seed), 0xBF5])
    draws = rng.integers(_DRAWS[0], _DRAWS[1] + 1, nodes)
    a = np.repeat(np.arange(nodes, dtype=np.int32), draws)
    b = rng.integers(0, nodes, a.size, dtype=np.int32)
    return np.concatenate([a, b]), np.concatenate([b, a])


def csr(src, dst, nodes: int, padded: int):
    """``starting``, ``no_of_edges`` (``padded`` long: the nodes the range
    rounds up to have no edges) and ``edges`` by source node."""
    order = np.argsort(src, kind="stable")
    counts = np.bincount(src, minlength=padded).astype(np.int32)
    starting = np.zeros(padded, np.int32)
    np.cumsum(counts[:-1], out=starting[1:])
    return starting, counts, np.ascontiguousarray(dst[order])


def inputs(cfg, params, rng):
    nodes, n = int(cfg["nodes"]), int(params["n"])
    src, dst = base_edges(nodes, cfg["graph_seed"])
    how = cfg["seed_relabels"]
    if how == "within_work_groups":
        groups = np.arange(nodes) // int(cfg["local_range"])
        relabel = np.lexsort((rng.random(nodes), groups)).astype(np.int32)
    else:
        relabel = (rng.permutation(nodes).astype(np.int32) if how
                   else np.arange(nodes, dtype=np.int32))
    starting, counts, edges = csr(relabel[src], relabel[dst], nodes, n)
    return {
        "starting": starting, "no_of_edges": counts, "edges": edges,
        "mask": np.zeros(n, np.int8), "updating": np.zeros(n, np.int8),
        "visited": np.zeros(n, np.int8), "cost": np.full(n, -1, np.int32),
        "over": np.zeros(1, np.int8),
        # not kernel arguments: the labels, and the loop's log
        "relabel": relabel, "traversals": [],
    }, (nodes,)


def call_values(cfg, params, values):
    """Every call hands the kernels the same scalar; the set apart is a
    tuple of its own, which is how the loop knows the last warm-up call and
    the fresh call (``traversal.py``)."""
    return {"cycle": [list(values)], "apart": list(values)}


def bfs(starting, counts, edges, source: int, stats: list | None = None):
    """Hop distance from ``source`` of every node of the CSR (-1:
    unreachable) and the levels a level-synchronous traversal runs: one a
    frontier, the last of which discovers nothing.  ``stats`` receives
    ``(frontier, edge entries, discovered)`` of every level."""
    cost = np.full(starting.size, -1, np.int32)
    cost[source] = 0
    frontier = np.array([source], np.int64)
    levels = 0
    while frontier.size:
        levels += 1
        cnt = counts[frontier].astype(np.int64)
        total = int(cnt.sum())
        # entry k of the frontier's lists laid end to end
        first = np.repeat(starting[frontier].astype(np.int64)
                          - np.concatenate([[0], np.cumsum(cnt)[:-1]]), cnt)
        targets = edges[first + np.arange(total)]
        cost[targets[cost[targets] < 0]] = levels
        fresh = np.flatnonzero(cost == levels)
        if stats is not None:
            stats.append((int(frontier.size), total, int(fresh.size)))
        frontier = fresh
    return cost, levels


def bfs_queue(starting, counts, edges, source: int):
    """The same distances by the textbook queue, node by node: what the
    checks hold :func:`bfs` against on a small graph."""
    from collections import deque

    cost = [-1] * len(starting)
    cost[source] = 0
    todo = deque([source])
    while todo:
        u = todo.popleft()
        for v in edges[starting[u]:starting[u] + counts[u]]:
            if cost[v] < 0:
                cost[v] = cost[u] + 1
                todo.append(int(v))
    return np.asarray(cost, np.int32)


def kernel_cost(cfg, params, items, frontier=0, edge_entries=0, discovered=0):
    """Bytes one level (one ``BFS_1`` and one ``BFS_2`` over ``items`` work
    items) has to move by the ALGORITHM's data, whatever lowering moves
    them: ``mask[tid]`` and ``updating[tid]`` once a node (the guard is a
    scalar); a frontier node's ``starting``, ``no_of_edges``, ``cost`` and
    the ``mask`` byte it clears; an edge entry and one ``visited`` byte an
    entry of a frontier node's list; ``cost`` and ``updating`` (5 bytes) a
    discovered node, and the 3 bytes ``BFS_2`` stores for it.  Integer
    compares and one add: no operations to speak of."""
    return {"ops": 0,
            "bytes": 2 * int(items) + 13 * int(frontier)
            + 5 * int(edge_entries) + 8 * int(discovered)}


def compare(cfg, params, arrays, values, observed, seed, precision=None):
    """Two numbers, both exact.  ``cost_differing``: nodes whose ``cost`` in
    the caller's array is not the hop distance from THAT call's source,
    after the window's last call and after the fresh call into an array
    poisoned anew (all 1 000 000 nodes of each; the padding must read -1).
    ``levels_off``: by how many levels the two calls' traversals missed the
    source's eccentricity + 1.  The control (``precision``: "one-level-short")
    is the reference stopped one level early, in the program's place."""
    from cells import Compared

    lim = cfg["limits"]
    csr_ = arrays["starting"], arrays["no_of_edges"], arrays["edges"]
    log = arrays["traversals"]
    calls = [(log[-2], observed["outputs"]), (log[-1], observed["fresh"]["outputs"])]
    differing = levels_off = 0
    for (source, levels), outputs in calls:
        want, want_levels = bfs(*csr_, int(arrays["relabel"][source]))
        if precision is None:
            got = outputs["cost"]
        elif precision == "one-level-short":
            got = np.where(want == want_levels - 1, -1, want)
            levels = want_levels - 1
        else:
            raise ValueError(f"no control {precision!r}")
        differing += int((got != want).sum())
        levels_off += abs(int(levels) - want_levels)
    return [Compared("cost_differing", float(differing), lim["cost_differing"]),
            Compared("levels_off", float(levels_off), lim["levels_off"])]
