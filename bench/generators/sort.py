"""``sort``: the sorting SN P system of Ionescu and Sburlan, and its inputs.

M. Ionescu and D. Sburlan, "Some applications of spiking neural P
systems", Computing and Informatics 27(3):515-528 (2008), sorting section,
written here from the paper's construction and independent of
``repro.core.generators.sorting``.  At size ``n``:

* input neurons ``0..n-1``: input ``i`` holds ``x_i`` spikes and has
  ``a+/a -> a`` (regex base 1, period 1, consume 1, produce 1);
* middle neurons ``n..2n-1``: neuron ``n + j - 1`` has ``a^j -> a`` and,
  for every other ``k`` in ``1..n``, ``a^k -> λ``, its rules in ``k``
  order;
* output neurons ``2n..3n-1``: no rules;
* synapses: every input onto every middle neuron, and middle neuron
  ``n + j - 1`` onto outputs ``2n..2n+j-1``.

The system is deterministic; after ``max(x) + 1`` steps output
``2n + k - 1`` holds the ``k``-th largest input.  The inputs are data:
:func:`draw_inputs` draws one vector per trace, and
:func:`sorted_outputs` is what the outputs must then hold.
"""

from __future__ import annotations

import numpy as np

from bench.systems import PlainSystem


def generate(seed: int, n: int) -> PlainSystem:
    """The system on ``n`` naturals, every input empty; the construction
    draws nothing, so ``seed`` changes nothing."""
    del seed
    j = np.arange(1, n + 1)
    middle = np.repeat(n + j - 1, n)             # neuron of each middle rule
    k = np.tile(j, n)                            # its regex base and consume
    fires = k == np.repeat(j, n)
    src = np.concatenate([np.repeat(np.arange(n), n),
                          np.repeat(n + j - 1, j)])
    dst = np.concatenate([n + np.tile(np.arange(n), n),
                          2 * n + np.arange(n * (n + 1) // 2)
                          - np.repeat(j * (j - 1) // 2, j)])
    ones = np.ones(n, np.int64)
    return PlainSystem(
        name=f"sort-{n}", init=np.zeros(3 * n, np.int64),
        rule_neuron=np.concatenate([np.arange(n), middle]),
        consume=np.concatenate([ones, k]),
        produce=np.concatenate([ones, fires.astype(np.int64)]),
        base=np.concatenate([ones, k]),
        period=np.concatenate([ones, np.zeros(n * n, np.int64)]),
        covering=np.zeros(n + n * n, bool),
        src=src, dst=dst, out_neuron=-1)


def draw_inputs(plain: PlainSystem, rng: np.random.Generator, batch: int,
                high: int) -> np.ndarray:
    """``(batch, 3n)`` initial configurations: each row's inputs uniform
    in ``0..high``, every other neuron empty."""
    n = plain.num_neurons // 3
    rows = np.zeros((batch, 3 * n), np.int64)
    rows[:, :n] = rng.integers(0, high + 1, (batch, n))
    return rows


def output_neurons(plain: PlainSystem) -> slice:
    n = plain.num_neurons // 3
    return slice(2 * n, 3 * n)


def sorted_outputs(plain: PlainSystem, rows: np.ndarray) -> np.ndarray:
    """What the outputs hold once every spike of ``rows`` has landed:
    each row's inputs in descending order."""
    n = plain.num_neurons // 3
    return -np.sort(-np.asarray(rows)[:, :n], axis=1)
