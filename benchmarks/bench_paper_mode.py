"""Benchmark: the paper's own algorithm vs. this framework's engine.

The 2011 simulator enumerates spiking vectors on the HOST (Python string
concatenation, Algorithm 2) and ships one ``S_k · M`` vector-matrix product
at a time to the device.  ``paper_mode_step`` reimplements that faithfully
(strings and all); ``explore`` is our batched rank-decode engine.  The
ratio is the reproduction -> beyond-paper speedup reported in
EXPERIMENTS.md §Perf (CPU numbers; the architectural gap only widens on a
real accelerator, where per-vector host round-trips dominate).
"""

import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compile_system, explore, paper_pi
from repro.core.generators import random_system, scaled_pi
from repro.core.system import SNPSystem


def _paper_applicable(spikes: int, base: int, covering: bool,
                      period: int) -> bool:
    if spikes < base:
        return False
    if covering:
        return True
    if period > 0:
        return (spikes - base) % period == 0
    return spikes == base


@jax.jit
def _device_svm(s_vec, m_mat, c_vec):
    # the paper's device step: one spiking vector times M, plus C_k
    return c_vec + s_vec @ m_mat


def paper_mode_explore(system: SNPSystem, max_steps: int,
                       max_configs: int = 100000):
    """Algorithm 1+2 as published: host strings enumerate S_k; the device
    multiplies one vector at a time."""
    comp = compile_system(system)
    m_mat = comp.M.astype(jnp.float32)
    rules = [system.rules[i] for i in np.asarray(comp.rule_order)]
    seen = {tuple(system.initial_spikes)}
    frontier = [tuple(system.initial_spikes)]
    for _ in range(max_steps):
        nxt = []
        for cfg in frontier:
            # II-1/II-2: per-neuron {1,0} strings for applicable rules
            per_neuron = []
            for ni in range(system.num_neurons):
                idxs = [i for i, r in enumerate(rules) if r.neuron == ni]
                apps = [i for i in idxs if _paper_applicable(
                    cfg[ni], rules[i].regex_base, rules[i].covering,
                    rules[i].regex_period)]
                strings = []
                for a in apps:
                    s = ["0"] * len(idxs)
                    s[idxs.index(a)] = "1"
                    strings.append("".join(s))
                per_neuron.append(strings if strings
                                  else ["0" * len(idxs)] if idxs else [""])
            if all(set(p) == {"0" * len(p[0])} or p == [""]
                   for p in per_neuron):
                continue
            # II-3: exhaustive pairwise concatenation -> tmp3
            tmp3 = [""]
            for strings in per_neuron:
                tmp3 = [a + b for a in tmp3 for b in strings]
            # device: one vector-matrix product per spiking vector
            c_vec = jnp.asarray(cfg, jnp.float32)
            for s_str in tmp3:
                s_vec = jnp.asarray([int(ch) for ch in s_str], jnp.float32)
                new = tuple(int(v) for v in np.asarray(
                    _device_svm(s_vec, m_mat, c_vec)))
                if new not in seen:
                    seen.add(new)
                    nxt.append(new)
                    if len(seen) >= max_configs:
                        return seen
        frontier = nxt
        if not frontier:
            break
    return seen


def rows():
    out = []
    cases = [
        ("pi", paper_pi(True), 10, dict(frontier_cap=64, visited_cap=1024,
                                        max_branches=16)),
        ("pi_x3", scaled_pi(3), 4, dict(frontier_cap=256, visited_cap=8192,
                                        max_branches=64)),
        ("random_24n", random_system(24, 2, 0.15, seed=3), 5,
         dict(frontier_cap=256, visited_cap=8192, max_branches=64)),
    ]
    for name, system, steps, kw in cases:
        comp = compile_system(system)
        cap = 100000
        t0 = time.perf_counter()
        seen = paper_mode_explore(system, steps, max_configs=cap)
        t_paper = time.perf_counter() - t0

        explore(comp, max_steps=steps, **kw)  # warm compile
        t0 = time.perf_counter()
        res = explore(comp, max_steps=steps, **kw)
        t_ours = time.perf_counter() - t0

        mine = {tuple(int(v) for v in row) for row in res.configs}
        capped = len(seen) >= cap
        overflow = (res.branch_overflow or res.frontier_overflow
                    or res.visited_overflow)
        if capped or overflow:
            # caps/overflow make raw set equality meaningless; soundness:
            # whichever explored less must be contained in the other
            small, big = (mine, seen) if overflow else (seen, mine)
            agree = f"subset={small <= big or capped}"
        else:
            agree = f"equal={seen == mine}"
        out.append((f"paper_mode/{name}", t_paper * 1e6,
                    f"paper={len(seen)}cfg engine={len(mine)}cfg {agree}"))
        out.append((f"batched_engine/{name}", t_ours * 1e6,
                    f"speedup={t_paper / max(t_ours, 1e-9):.1f}x"))
    return out
