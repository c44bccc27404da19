"""``power_law``: Barabási–Albert preferential-attachment SNP systems.

A copy of ``repro.core.generators.power_law`` and the bounded random
rules it draws, kept here so the data cannot move when the program does.
Equal arguments build the identical system on every Python version.
"""

from __future__ import annotations

import random
from typing import Optional

import numpy as np

from bench.systems import PlainSystem


def _rules(m: int, rules_per_neuron: int, max_spikes: int,
           rng: random.Random):
    rows = []
    for i in range(m):
        for _ in range(rules_per_neuron):
            consume = rng.randint(1, max_spikes)
            rows.append((i, consume, rng.choice([0, 1, 1, 2]),
                         rng.randint(consume, max_spikes),
                         rng.choice([0, 0, 1]), rng.random() < 0.5))
    return rows


def generate(seed: int, m: int, attach: int = 4,
             rules_per_neuron: int = 2, max_spikes: int = 3,
             max_in: Optional[int] = None,
             graph_seed: Optional[int] = None) -> PlainSystem:
    """Barabási–Albert preferential attachment: node ``i`` synapses onto
    ``attach`` distinct earlier nodes drawn by degree, so the in-degree is
    heavy-tailed; ``max_in`` caps it (rejection sampling, then an
    ascending scan of eligible nodes).  The graph is drawn from
    ``graph_seed`` (default: ``seed``), the rules and initial spikes from a
    second stream of ``seed``; ``repro.core.generators.power_law`` is the
    case ``graph_seed == seed``."""
    if not 1 <= attach < m:
        raise ValueError(f"need 1 <= attach < m, got attach={attach}, m={m}")
    if max_in is not None and max_in < attach:
        raise ValueError(f"max_in {max_in} < attach {attach}")
    rng = random.Random((seed if graph_seed is None else graph_seed) ^ 0x5eed)
    syn = []
    in_deg = [0] * m
    pool = []
    for i in range(attach + 1):
        for j in range(attach + 1):
            if i != j:
                syn.append((i, j))
                pool.append(j)
                in_deg[j] += 1
    for i in range(attach + 1, m):
        targets = set()
        for _ in range(50 * attach):
            if len(targets) == attach:
                break
            j = pool[rng.randrange(len(pool))]
            if max_in is None or in_deg[j] < max_in:
                targets.add(j)
        if len(targets) < attach:
            for j in range(i):
                if len(targets) == attach:
                    break
                if max_in is None or in_deg[j] < max_in:
                    targets.add(j)
            if len(targets) < attach:
                raise ValueError(f"cannot attach {attach} edges under "
                                 f"max_in={max_in} at node {i}")
        for j in sorted(targets):
            syn.append((i, j))
            pool.append(j)
            in_deg[j] += 1
        pool.append(i)

    rng = random.Random(seed)
    rules = np.asarray(_rules(m, rules_per_neuron, max_spikes, rng),
                       np.int64)
    init = np.asarray([rng.randint(0, max_spikes) for _ in range(m)],
                      np.int64)
    syn = np.asarray(syn, np.int64)
    cap = "" if max_in is None else f"c{max_in}"
    return PlainSystem(
        name=f"power-law-{m}a{attach}{cap}", init=init,
        rule_neuron=rules[:, 0], consume=rules[:, 1], produce=rules[:, 2],
        base=rules[:, 3], period=rules[:, 4], covering=rules[:, 5] > 0,
        src=syn[:, 0], dst=syn[:, 1], out_neuron=m - 1)
