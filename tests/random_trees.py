"""Random XML trees with deliberate redundancy, shared by the search tests.

Subtrees are reused through shared ``NodeSpec`` objects, so DAG compression,
dummy nodes, nested RCs and offset splicing all occur.  Kept apart from the
hypothesis-driven property tests so that importing it needs no hypothesis.
"""
import numpy as np

from repro.core import NodeSpec, build_tree

WORDS = ["usa", "english", "vinyl", "rock", "jazz", "rpm", "red", "blue"]


def random_tree(seed: int, n_target: int):
    rng = np.random.default_rng(seed)

    def words() -> str:
        k = int(rng.integers(0, 3))
        return " ".join(rng.choice(WORDS, size=k, replace=True)) if k else ""

    pool: list[NodeSpec] = []
    count = [0]

    def make(depth: int) -> NodeSpec:
        count[0] += 1
        # reuse an existing subtree (creates redundancy / nested RCs)
        if pool and rng.random() < 0.3:
            return pool[int(rng.integers(0, len(pool)))]
        n_children = 0
        if depth < 5 and count[0] < n_target:
            n_children = int(rng.integers(0, 4))
        node = NodeSpec(
            label=f"tag{int(rng.integers(0, 4))}",
            text=words(),
            children=[make(depth + 1) for _ in range(n_children)],
        )
        if rng.random() < 0.4:
            pool.append(node)
        return node

    root = NodeSpec("root", children=[make(1) for _ in range(3)])
    return build_tree(root)
