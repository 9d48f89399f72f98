"""Reference-binary-compatible persistence primitives.

Every on-disk structure of the reference index folder is reproduced
byte-for-byte so indexes built by the reference C++ tools load here and vice
versa (SURVEY.md §5 "Checkpoint / resume"):

* ``vectors.bin`` / any Dataset<T>: int32 rows, int32 cols, row-major data
  (Dataset<T>::Save, /root/reference/AnnService/inc/Core/Common/
  Dataset.h:144-158).
* ``graph.bin``: int32 rows, int32 neighborhoodSize, rows of int32 neighbor
  ids, -1 padded (NeighborhoodGraph::SaveGraph, inc/Core/Common/
  NeighborhoodGraph.h:376-386).
* ``tree.bin`` (BKT): int32 treeNumber, int32 treeStart[treeNumber],
  int32 nodeCount, nodes of {int32 centerid, childStart, childEnd}
  (BKTree::SaveTrees, inc/Core/Common/BKTree.h:219-229).
* ``tree.bin`` (KDT): int32 treeNumber, int32 treeStart[treeNumber],
  int32 nodeCount, nodes of {int32 left, right, split_dim, float32
  split_value} (KDTree::SaveTrees, inc/Core/Common/KDTree.h:100-110).
* ``deletes.bin``: int32 deletedCount, then a Dataset<int8> of shape (N, 1)
  holding the tombstone flags (Labelset::Save, inc/Core/Common/
  Labelset.h:47-52).

All integers are little-endian (x86 reference).
"""

from __future__ import annotations

import contextlib
import io
from typing import Tuple

import numpy as np

BKT_NODE_DTYPE = np.dtype(
    [("centerid", "<i4"), ("childStart", "<i4"), ("childEnd", "<i4")])
KDT_NODE_DTYPE = np.dtype(
    [("left", "<i4"), ("right", "<i4"),
     ("split_dim", "<i4"), ("split_value", "<f4")])


@contextlib.contextmanager
def open_write(path_or_stream):
    # path writes funnel through the crash-safe helper (fsync before
    # close + deterministic storage-fault hooks — io/atomic.py, GL411);
    # streams pass through untouched as before
    from sptag_tpu.io import atomic

    with atomic.checked_open(path_or_stream, "wb") as f:
        yield f


@contextlib.contextmanager
def open_read(path_or_stream):
    if hasattr(path_or_stream, "read"):
        yield path_or_stream
    else:
        with open(path_or_stream, "rb") as f:
            yield f


def write_matrix(path_or_stream, array: np.ndarray) -> None:
    array = np.ascontiguousarray(array)
    rows, cols = array.shape
    with open_write(path_or_stream) as f:
        f.write(np.int32(rows).tobytes())
        f.write(np.int32(cols).tobytes())
        # the rows as they lie in memory: `tobytes()` is a second copy of
        # the block (3.4 GB beside 3.4 GB at 8.84M x 384 int8)
        f.write(array.reshape(-1).view(np.uint8).data)


def read_matrix(path_or_stream, dtype) -> np.ndarray:
    dtype = np.dtype(dtype)
    with open_read(path_or_stream) as f:
        header = f.read(8)
        rows = int(np.frombuffer(header, "<i4", 1, 0)[0])
        cols = int(np.frombuffer(header, "<i4", 1, 4)[0])
        # read INTO the array: a bytes payload and then its copy held the
        # block twice while a corpus loaded
        out = np.empty((rows, cols), dtype)
        into = out.reshape(-1).view(np.uint8).data
        got = 0
        while got < len(into):
            n = f.readinto(into[got:])
            if not n:
                raise ValueError(
                    f"matrix of {rows} x {cols} {dtype} ends after {got} of "
                    f"{len(into)} bytes")
            got += n
    return out


def write_graph(path_or_stream, graph: np.ndarray) -> None:
    write_matrix(path_or_stream, graph.astype("<i4", copy=False))


def read_graph(path_or_stream) -> np.ndarray:
    return read_matrix(path_or_stream, "<i4")


def write_deletes(path_or_stream, mask: np.ndarray) -> None:
    """mask: (N,) bool/int8 tombstone flags.

    Byte convention (verified against an index built by the reference
    indexbuilder, round 3): deleted rows store 1, LIVE rows store -1 —
    the Labelset's backing Dataset<int8> is memset to -1 at Initialize
    (inc/Core/Common/Dataset.h:65) and Insert flips a cell to 1
    (inc/Core/Common/Labelset.h:39-45)."""
    m = mask.astype(bool).reshape(-1, 1)
    flags = np.where(m, np.int8(1), np.int8(-1))
    with open_write(path_or_stream) as f:
        f.write(np.int32(int(m.sum())).tobytes())
        write_matrix(f, np.ascontiguousarray(flags))


def read_deletes(path_or_stream) -> np.ndarray:
    with open_read(path_or_stream) as f:
        f.read(4)  # deleted count; recomputed from the flags
        flags = read_matrix(f, np.int8)
    # deleted iff exactly 1 (Labelset::Contains, Labelset.h:34-37); the
    # -1 fill bytes of live rows must NOT read as tombstones
    return (flags.reshape(-1) == 1)


def write_tree_forest(path_or_stream, tree_starts: np.ndarray,
                      nodes: np.ndarray) -> None:
    """Shared BKT/KDT forest layout (the node dtype differs)."""
    tree_starts = np.ascontiguousarray(tree_starts, dtype="<i4")
    with open_write(path_or_stream) as f:
        f.write(np.int32(len(tree_starts)).tobytes())
        f.write(tree_starts.tobytes())
        f.write(np.int32(len(nodes)).tobytes())
        f.write(np.ascontiguousarray(nodes).tobytes())


def read_tree_forest(path_or_stream,
                     node_dtype) -> Tuple[np.ndarray, np.ndarray]:
    with open_read(path_or_stream) as f:
        tree_number = int(np.frombuffer(f.read(4), "<i4")[0])
        tree_starts = np.frombuffer(f.read(4 * tree_number), "<i4").copy()
        node_count = int(np.frombuffer(f.read(4), "<i4")[0])
        nodes = np.frombuffer(f.read(node_count * node_dtype.itemsize),
                              dtype=node_dtype).copy()
    return tree_starts, nodes
