"""The data every cell runs on: an SNP system as flat numpy arrays.

A configuration file names its generator, ``bench/generators/<name>.py``,
whose ``generate(seed, **args)`` builds a :class:`PlainSystem`, which the
plain reference reads; :func:`to_program` turns it into the program's
``SNPSystem`` for the system under test.
"""

from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path

import numpy as np

__all__ = ["PlainSystem", "generator", "build", "to_program"]


@dataclass(frozen=True)
class PlainSystem:
    """An SNP system without delays as flat arrays.

    Rules are listed neuron by neuron (``rule_neuron`` is non-decreasing),
    in the order the generator drew them; that order is the order in
    which a neuron's applicable rules are numbered when a branch is
    decoded.  Regular expressions are the progressions
    ``{base + t * period}``; ``covering`` rules apply at ``spikes >= base``.
    """

    name: str
    init: np.ndarray          # (m,) int64 spikes
    rule_neuron: np.ndarray   # (n,) int64
    consume: np.ndarray       # (n,) int64
    produce: np.ndarray       # (n,) int64
    base: np.ndarray          # (n,) int64
    period: np.ndarray        # (n,) int64
    covering: np.ndarray      # (n,) bool
    src: np.ndarray           # (E,) int64 synapse sources
    dst: np.ndarray           # (E,) int64 synapse targets
    out_neuron: int

    @property
    def num_neurons(self) -> int:
        return int(self.init.shape[0])

    @property
    def num_rules(self) -> int:
        return int(self.rule_neuron.shape[0])

    @property
    def num_synapses(self) -> int:
        return int(self.src.shape[0])

    def in_degrees(self) -> np.ndarray:
        return np.bincount(self.dst, minlength=self.num_neurons)


ROOT = Path(__file__).resolve().parents[1]


def generator(name: str, root: Path = ROOT):
    """``generate`` of ``bench/generators/<name>.py``."""
    from bench.spec import plugin
    return plugin(root, "generators", name).generate


def build(config: dict, seed: int, root: Path = ROOT) -> PlainSystem:
    """The system a configuration file describes, drawn from ``seed``."""
    return generator(config["generator"], root)(seed, **config["args"])


def to_program(plain: PlainSystem):
    """The same system as the program's ``SNPSystem``."""
    from repro.core import Rule, SNPSystem
    rules = tuple(
        Rule(neuron=int(i), consume=int(c), produce=int(p),
             regex_base=int(b), regex_period=int(q), covering=bool(cv))
        for i, c, p, b, q, cv in zip(
            plain.rule_neuron, plain.consume, plain.produce, plain.base,
            plain.period, plain.covering))
    synapses = tuple(zip(plain.src.tolist(), plain.dst.tolist()))
    return SNPSystem(num_neurons=plain.num_neurons,
                     initial_spikes=tuple(plain.init.tolist()),
                     rules=rules, synapses=synapses,
                     output_neuron=plain.out_neuron, name=plain.name)
