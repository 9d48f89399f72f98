"""The plain walk: SPTAG's own search of a BKT index, written plainly in
numpy and heapq, one query at a time — a reference for WHAT A WALK OF THIS
BUDGET OVER THIS GRAPH FINDS, not id for id (the program pops 64 nodes a
trip for every query of a batch at once; this pops one).

Imports nothing of the program.  It takes arrays: the rows, the `(n, m)`
neighbour lists and the tree of a SAVED folder, read here from the
folder's reference-format `graph.bin` and `tree.bin` (`read_graph`,
`read_tree`: the layout of BKTree::SaveTrees / NeighborhoodGraph::SaveGraph
upstream).  What is compared with the program is recall against the exact
scan on the same queries, and rows scored a query.

The search is `BKT::Index<T>::SearchIndex` (AnnService/src/Core/BKT/
BKTIndex.cpp:105-157, the `Search` macro; line numbers as SURVEY.md 3.2
gives them, /root/reference being the checkout they refer to):

    InitSearchTrees / SearchTrees (inc/Core/Common/BKTree.h:279-320) seed
    the candidate queue from the tree until `initial_pivots` leaves are
    in; then, while the queue holds a node: pop the nearest; if it is no
    farther than the worst of the best k so far it joins them and the
    no-better count is reset, else the count rises and the walk ends once
    it passes MaxCheck / 64 or MaxCheck rows have been scored; score the
    node's neighbours not yet seen and queue them; descend the tree for
    `other_pivots` more leaves when the queue's head is farther than the
    tree queue's.

Departures from that text, each small and on purpose:
  * distances in float64 (upstream: float32 SIMD, BKTIndex.cpp:148) —
    this is the yardstick, rounding must not decide a neighbour;
  * no tombstones (the CheckDeleted macro argument, :120-138) and no
    duplicate-centre chase (`checkNode < -1`, :121-131): a cell's rows are
    distinct and none is deleted;
  * the candidate queue is unbounded (upstream: MaxCheck x 30 cells,
    inc/Core/Common/WorkSpace.h:182-208), the visited set a Python set
    (upstream: OptHashPosVector, WorkSpace.h:33-134);
  * without a tree (`tree=None`) the walk starts from `seeds`, all scored
    up front, and never re-seeds (for callers that have only a pivot
    list; the BKTree.h:296-320 descent is what `tree` gives).
"""

from __future__ import annotations

import heapq
import os

import numpy as np

NODE = np.dtype([("centerid", "<i4"), ("childStart", "<i4"),
                 ("childEnd", "<i4")])       # BKTNode, BKTree.h:26
INITIAL_PIVOTS, OTHER_PIVOTS = 50, 4         # ParameterDefinitionList.h


def read_graph(path: str) -> np.ndarray:
    """graph.bin: int32 rows, int32 neighbours a row, then the lists
    (-1 pads a short one) -> (rows, m) int32."""
    with open(path, "rb") as f:
        rows, m = np.frombuffer(f.read(8), "<i4")
        return np.frombuffer(f.read(), "<i4").reshape(int(rows), int(m))


def read_tree(path: str) -> tuple:
    """tree.bin: int32 tree count, that many int32 root positions, int32
    node count, the nodes -> (roots, nodes)."""
    with open(path, "rb") as f:
        count = int(np.frombuffer(f.read(4), "<i4")[0])
        roots = np.frombuffer(f.read(4 * count), "<i4")
        nodes = int(np.frombuffer(f.read(4), "<i4")[0])
        return roots, np.frombuffer(f.read(nodes * NODE.itemsize), NODE)


def _dist(rows, query, ids) -> np.ndarray:
    x = rows[ids].astype(np.float64) - query
    return np.einsum("ij,ij->i", x, x)


class _Walk:
    """The state of one query's search: WorkSpace upstream."""

    def __init__(self, rows, query, tree):
        self.rows, self.query = rows, query.astype(np.float64)
        self.seen, self.scored = set(), 0
        self.queue, self.tree_queue = [], []     # NGQueue, SPTQueue
        self.nodes = None
        if tree is not None:
            roots, self.nodes = tree
            for root in roots:                   # InitSearchTrees
                node = self.nodes[root]
                if node["childStart"] < 0:
                    self._push_tree([int(root)])
                else:
                    self._push_tree(range(node["childStart"],
                                          node["childEnd"]))

    def _push_tree(self, positions) -> None:
        positions = [p for p in positions
                     if 0 <= self.nodes[p]["centerid"] < len(self.rows)]
        if positions:
            d = _dist(self.rows, self.query,
                      self.nodes["centerid"][positions])
            for dist, p in zip(d.tolist(), positions):
                heapq.heappush(self.tree_queue, (dist, p))

    def descend(self, limit: int) -> None:
        """SearchTrees: pop tree cells until `limit` rows are scored."""
        while self.tree_queue:
            dist, p = heapq.heappop(self.tree_queue)
            node = self.nodes[p]
            center = int(node["centerid"])
            if center not in self.seen:
                self.seen.add(center)
                heapq.heappush(self.queue, (dist, center))
                if node["childStart"] < 0:
                    self.scored += 1
            if node["childStart"] < 0:
                if self.scored >= limit:
                    break
            else:
                self._push_tree(range(node["childStart"],
                                      node["childEnd"]))

    def score(self, ids) -> None:
        ids = [i for i in ids if i >= 0 and i not in self.seen]
        if not ids:
            return
        self.seen.update(ids)
        self.scored += len(ids)
        for dist, i in zip(_dist(self.rows, self.query, ids).tolist(), ids):
            heapq.heappush(self.queue, (dist, i))


def walk(rows, graph, query, k: int, max_check: int, tree=None,
         seeds=None, initial_pivots: int = INITIAL_PIVOTS,
         other_pivots: int = OTHER_PIVOTS) -> tuple:
    """One query -> ((k,) ids nearest first, -1 padded, (k,) float64
    squared L2 distances, rows scored)."""
    w = _Walk(rows, query, tree)
    if tree is not None:
        w.descend(initial_pivots)
    else:
        w.score([int(s) for s in seeds])
    best, no_better, limit = [], 0, max_check // 64     # max-heap of (-d, id)
    while w.queue:
        dist, node = heapq.heappop(w.queue)
        if len(best) < k or dist <= -best[0][0]:
            no_better = 0
            heapq.heappush(best, (-dist, node))
            if len(best) > k:
                heapq.heappop(best)
        else:
            no_better += 1
            if no_better > limit or w.scored > max_check:
                break
        w.score(graph[node].tolist())
        if tree is not None and w.tree_queue and (
                not w.queue or w.queue[0][0] > w.tree_queue[0][0]):
            w.descend(other_pivots + w.scored)
    found = sorted((-d, i) for d, i in best)
    ids = np.full(k, -1, np.int64)
    dists = np.full(k, np.inf)
    ids[:len(found)] = [i for _, i in found]
    dists[:len(found)] = [d for d, _ in found]
    return ids, dists, w.scored


def walk_all(rows, graph, queries, k: int, max_check: int, tree=None,
             seeds=None) -> tuple:
    """Every query in turn -> ((Q, k) ids, (Q, k) distances, (Q,) rows
    scored)."""
    out = [walk(rows, graph, q, k, max_check, tree=tree, seeds=seeds)
           for q in queries]
    return (np.stack([o[0] for o in out]), np.stack([o[1] for o in out]),
            np.array([o[2] for o in out]))


def from_folder(folder: str) -> tuple:
    """(graph, tree) of a saved BKT folder."""
    return (read_graph(os.path.join(folder, "graph.bin")),
            read_tree(os.path.join(folder, "tree.bin")))
