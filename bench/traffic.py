"""One general generator for every traffic mix under ``bench/traffic/``.

A mix is a JSON file of parameters; nothing about a mix lives in code:

  * ``pool``: the distinct queries.  The paper's Q1-Q9 take ranks 1-9 when
    ``paper_queries_first``; each value of ``k`` takes an equal share of
    the other ranks, in an order shuffled from ``pool_seed``, and a query
    of ``k`` words draws ``k`` distinct ``fields`` and one value of each,
    uniformly.  A field is a named list of words in ``fields`` or
    ``year`` (every release year).
  * ``zipf_s``: popularity over pool rank is proportional to 1 / rank^s.
  * ``slca_share``: the share of requests asking for SLCA; the rest ELCA.
  * ``loop``: ``open`` sends at ``rate_per_s`` with Poisson gaps; ``closed``
    runs ``clients`` clients, each sending its next query when its answer
    arrives, ``per_client`` queries ready for each.
  * ``mix_seed``: the multiset of requests (ranks, semantics, gaps) is drawn
    once from it, so every run sends the same work and the same gaps; the
    run's ``--seed`` only orders them.  Open-loop gaps are scaled so the
    last arrival falls at the end of the window.
"""
from __future__ import annotations

import json
import os

import numpy as np

from .corpus import QUERIES, YEAR_BASE, N_YEARS

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC_DIR = os.path.join(HERE, "traffic")


def load(name: str, directory: str = TRAFFIC_DIR) -> dict:
    with open(os.path.join(directory, f"{name}.json")) as f:
        return json.load(f)


def _field_values(spec: dict) -> dict[str, list[str]]:
    out = {k: list(v) for k, v in spec.get("fields", {}).items()}
    if spec.get("year_field", True):
        out["year"] = [str(YEAR_BASE + y) for y in range(N_YEARS)]
    return out


def pool(spec: dict) -> list[list[str]]:
    """The mix's distinct queries, most popular first."""
    p = spec["pool"]
    out: list[list[str]] = []
    seen: set[frozenset] = set()
    if p.get("paper_queries_first"):
        for _, kws in QUERIES.values():
            out.append(list(kws))
            seen.add(frozenset(kws))
    fields = _field_values(p)
    names = sorted(fields)
    ks = list(p["k"])
    rng = np.random.default_rng(p["pool_seed"])
    # each k takes an equal share of the drawn ranks, in a shuffled order
    n = p["size"] - len(out)
    slots = np.tile(ks, n // len(ks) + 1)[:n]
    for k in rng.permutation(slots).tolist():
        while True:
            chosen = rng.choice(len(names), size=k, replace=False)
            words = [
                fields[names[i]][int(rng.integers(len(fields[names[i]])))]
                for i in chosen
            ]
            key = frozenset(words)
            if len(key) == k and key not in seen:
                seen.add(key)
                out.append(words)
                break
    return out


def _draw(spec: dict, n: int, rng: np.random.Generator):
    """``n`` pool ranks (Zipf) and semantics, exactly ``slca_share`` SLCA."""
    size = spec["pool"]["size"]
    w = 1.0 / np.arange(1, size + 1) ** float(spec["zipf_s"])
    ranks = rng.choice(size, size=n, p=w / w.sum())
    n_slca = int(round(n * float(spec["slca_share"])))
    sems = np.array(["slca"] * n_slca + ["elca"] * (n - n_slca))
    return ranks, sems[rng.permutation(n)]


def open_schedule(spec: dict, seconds: float, seed: int):
    """[(send offset s, pool index, semantics)] for one open-loop window."""
    n = max(1, int(round(float(spec["rate_per_s"]) * seconds)))
    mix = np.random.default_rng(spec["mix_seed"])
    ranks, sems = _draw(spec, n, mix)
    gaps = mix.exponential(1.0, n)
    run = np.random.default_rng(seed)
    order = run.permutation(n)
    gaps = gaps[run.permutation(n)]
    at = np.cumsum(gaps) * (seconds / gaps.sum())
    return [
        (float(t), int(ranks[i]), str(sems[i])) for t, i in zip(at, order)
    ]


def closed_sequences(spec: dict, seed: int):
    """Per client, its queries in order: [[(pool index, semantics)]]."""
    c, per = int(spec["clients"]), int(spec["per_client"])
    mix = np.random.default_rng(spec["mix_seed"])
    ranks, sems = _draw(spec, c * per, mix)
    order = np.random.default_rng(seed).permutation(c * per)
    pairs = [(int(ranks[i]), str(sems[i])) for i in order]
    return [pairs[j * per: (j + 1) * per] for j in range(c)]
