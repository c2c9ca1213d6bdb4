"""The plain reference: breadth-first search over an edge set kept here.

It imports nothing of the program. The graph of every version is rebuilt
from the initial edge list and the update operations that the traffic
generator emitted, applied to a sorted array of edge keys. Distances are
hop counts (the configurations state unit weights).

Both searches are level-synchronous and bit-parallel: each vertex holds
one bit per search source in uint64 words, and one level is an OR of the
neighbours' frontier words (a gather plus `np.bitwise_or.reduceat` over
the CSR rows).
"""
from __future__ import annotations

import numpy as np

#: Distance of an unreachable pair.
UNREACHABLE = np.iinfo(np.int64).max


class EdgeSet:
    """An undirected simple graph as a sorted array of keys u*n + v, u < v."""

    def __init__(self, n: int, edges: np.ndarray):
        self.n = int(n)
        self.keys = np.unique(self._keys(edges))

    def _keys(self, pairs: np.ndarray) -> np.ndarray:
        pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
        lo = np.minimum(pairs[:, 0], pairs[:, 1])
        hi = np.maximum(pairs[:, 0], pairs[:, 1])
        keep = lo != hi
        return lo[keep] * self.n + hi[keep]

    def apply(self, ops) -> None:
        """Apply one batch of (u, v, op[, w]) updates: op 0 or False
        inserts, op 1 or True deletes. An insert of an existing edge or a
        delete of a missing one changes nothing (paper §3)."""
        ops = list(ops)
        if not ops:
            return
        arr = np.asarray([(int(o[0]), int(o[1]), int(o[2])) for o in ops],
                         np.int64)
        if np.any(arr[:, 2] > 1):
            raise ValueError("re-weight updates are outside the unit-weight "
                             "reference")
        dels = self._keys(arr[arr[:, 2] == 1, :2])
        ins = self._keys(arr[arr[:, 2] == 0, :2])
        keys = self.keys[~np.isin(self.keys, dels)]
        self.keys = np.union1d(keys, ins)

    def degrees(self) -> np.ndarray:
        u, v = np.divmod(self.keys, self.n)
        return (np.bincount(u, minlength=self.n)
                + np.bincount(v, minlength=self.n))

    def csr(self) -> "Csr":
        u, v = np.divmod(self.keys, self.n)
        src = np.concatenate([u, v])
        dst = np.concatenate([v, u])
        order = np.argsort(dst, kind="stable")
        indptr = np.zeros(self.n + 1, np.int64)
        np.cumsum(np.bincount(dst, minlength=self.n), out=indptr[1:])
        return Csr(self.n, indptr, src[order])


class Csr:
    """Rows are vertices; row v lists the neighbours of v."""

    def __init__(self, n: int, indptr: np.ndarray, indices: np.ndarray):
        self.n = n
        self.indices = indices
        nonempty = indptr[1:] > indptr[:-1]
        self.rows = np.flatnonzero(nonempty)
        self.starts = indptr[:-1][nonempty]

    def gather_or(self, words: np.ndarray) -> np.ndarray:
        """out[v] = OR of words[u] over the neighbours u of v."""
        out = np.zeros_like(words)
        if self.rows.size:
            out[self.rows] = np.bitwise_or.reduceat(
                words[self.indices], self.starts, axis=0)
        return out


def _bits(count: int) -> tuple[int, np.ndarray]:
    words = max(1, (count + 63) // 64)
    return words, np.left_shift(np.uint64(1),
                                (np.arange(count) % 64).astype(np.uint64))


def _seed(n: int, sources: np.ndarray) -> np.ndarray:
    words, bit = _bits(len(sources))
    seed = np.zeros((n, words), np.uint64)
    for i, s in enumerate(sources):
        seed[s, i // 64] |= bit[i]
    return seed


def pair_distances(csr: Csr, qs: np.ndarray, qt: np.ndarray) -> np.ndarray:
    """d(qs[i], qt[i]) for every pair, UNREACHABLE where none exists."""
    qs = np.asarray(qs, np.int64)
    qt = np.asarray(qt, np.int64)
    sources, src_idx = np.unique(qs, return_inverse=True)
    _, bit = _bits(len(sources))
    word, mask = src_idx // 64, bit[src_idx]
    reached = _seed(csr.n, sources)
    frontier = reached.copy()
    out = np.full(qs.shape, UNREACHABLE, np.int64)
    out[qs == qt] = 0
    level = 0
    while True:
        todo = out == UNREACHABLE
        if not todo.any() or not frontier.any():
            return out
        level += 1
        new = csr.gather_or(frontier) & ~reached
        reached |= new
        frontier = new
        hit = todo & ((new[qt, word] & mask) != 0)
        out[hit] = level


def landmark_planes(csr: Csr, landmarks: np.ndarray
                    ) -> tuple[np.ndarray, np.ndarray]:
    """(dist [R, V], hub [R, V]) of the highway-cover labelling.

    dist[j, v] is d(landmarks[j], v), UNREACHABLE where none exists.
    hub[j, v] is True where some shortest path from landmarks[j] to v
    passes through another landmark, v itself included.
    """
    landmarks = np.asarray(landmarks, np.int64)
    r = len(landmarks)
    if r > 64:
        raise ValueError("one search word holds at most 64 landmarks")
    seed = _seed(csr.n, landmarks)
    # A landmark is "another landmark" to every plane but its own.
    other = np.zeros((csr.n, 1), np.uint64)
    other[landmarks] = ~seed[landmarks]
    reached, frontier = seed.copy(), seed.copy()
    via = np.zeros_like(seed)
    dist_v = np.full((csr.n, r), UNREACHABLE, np.int64)
    dist_v[landmarks, np.arange(r)] = 0
    _, bit = _bits(r)
    level = 0
    while frontier.any():
        level += 1
        new = csr.gather_or(frontier) & ~reached
        via |= new & (csr.gather_or(frontier & via) | other)
        reached |= new
        frontier = new
        idx = np.flatnonzero(new[:, 0])
        planes = np.unpackbits(new[idx, 0].view(np.uint8).reshape(-1, 8),
                               axis=1, bitorder="little")[:, :r]
        dist_v[idx] = np.where(planes, level, dist_v[idx])
    hub = (via[None, :, 0] & bit[:, None]) != 0
    return dist_v.T, hub
