"""Plain SLCA / ELCA over a generated corpus: the benchmark's reference.

Straight from the definitions (Xu and Papakonstantinou; Guo et al.), on
the corpus's preorder arrays, with nothing of the program under test:

  * a node is a common ancestor (CA) of keywords w1..wk when its subtree
    holds a node that directly contains each wi;
  * an SLCA is a CA with no CA below it;
  * an ELCA is a CA that keeps an occurrence of every wi once the subtrees
    of the CAs below it are taken out.  CAs are closed under taking the
    parent, so the topmost CAs below a CA are its CA children, and the
    test is ``count_i(v) - sum of count_i(child CA) >= 1`` for every i.

Preorder ids make a subtree the interval ``[v, v + size[v])``, so every
count is two binary searches into a keyword's sorted occurrence list.

``answer(..., control=True)`` is the benchmark's control: it breaks the
ELCA guarantee by answering ELCA queries with the SLCA set, the cheaper
semantics a faster path might be tempted to serve.  The comparison must
call it wrong.
"""
from __future__ import annotations

import hashlib

import numpy as np

from .corpus import Corpus

_EMPTY = np.zeros(0, np.int64)


class Reference:
    def __init__(self, corpus: Corpus):
        self.c = corpus
        self.ids = corpus.word_ids()
        self._occ: dict[str, np.ndarray] = {}

    def occurrences(self, word: str) -> np.ndarray:
        """Sorted ids of the nodes whose label or text is ``word``."""
        got = self._occ.get(word)
        if got is None:
            k = self.ids.get(word, -2)
            got = np.flatnonzero(
                (self.c.label_kw == k) | (self.c.text_kw == k)
            ).astype(np.int64)
            self._occ[word] = got
        return got

    def common_ancestors(self, words: list[str]):
        """(sorted CA ids, per-keyword subtree counts [k, n_ca])."""
        occ = [self.occurrences(w) for w in words]
        if not occ or any(o.size == 0 for o in occ):
            return _EMPTY, np.zeros((len(words), 0), np.int64)
        rare = min(occ, key=len)
        # candidates: the rarest keyword's occurrences and their ancestors
        level, parts = rare, [rare]
        while level.size:
            level = self.c.parent[level].astype(np.int64)
            level = np.unique(level[level >= 0])
            parts.append(level)
        cand = np.unique(np.concatenate(parts))
        end = cand + self.c.size[cand]
        counts = np.stack([
            np.searchsorted(o, end) - np.searchsorted(o, cand) for o in occ
        ])
        keep = (counts > 0).all(axis=0)
        return cand[keep], counts[:, keep]

    def answer(self, words: list[str], semantics: str,
               control: bool = False) -> np.ndarray:
        ca, counts = self.common_ancestors(words)
        if ca.size == 0:
            return _EMPTY
        if semantics == "slca" or control:
            # the next CA in preorder is the first one below, if any is
            nxt = np.append(ca[1:], np.iinfo(np.int64).max)
            return ca[nxt >= ca + self.c.size[ca]]
        if semantics != "elca":
            raise ValueError(f"semantics must be slca|elca, got {semantics!r}")
        par = self.c.parent[ca].astype(np.int64)
        pos = np.searchsorted(ca, par)
        has_ca_parent = (par >= 0) & (pos < ca.size)
        has_ca_parent[has_ca_parent] = ca[pos[has_ca_parent]] == par[has_ca_parent]
        below = np.zeros_like(counts)
        for i in range(counts.shape[0]):
            np.add.at(below[i], pos[has_ca_parent], counts[i, has_ca_parent])
        return ca[(counts - below >= 1).all(axis=0)]


def digest(ids) -> str:
    """Digest of an answer: its ids as little-endian int64, in order."""
    return hashlib.blake2b(
        np.asarray(ids, dtype="<i8").tobytes(), digest_size=16
    ).hexdigest()
