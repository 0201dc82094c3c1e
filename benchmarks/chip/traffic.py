"""The benchmark's one traffic generator, driven by a mix file.

A mix is ``traffic/<name>.json``. Its keys:

``source``            where the traffic model comes from
``flows``             flows of the port that this chip monitors
``events_per_period`` events offered per period; 0 = line rate, one full
                      event batch every period
``trace_periods``     periods of events generated before the stream cycles
``frame_bytes``, ``frame_parts``
                      the packet-size mix: sizes and their parts by count
                      (IMIX 64/594/1518 B at 7:4:1)
``protocol``          the IP protocol of every flow

The model is a traffic tester's (RFC 2544 test traffic, addresses and
ports pseudorandom as RFC 4814 asks): every flow carries the same load.
The stream is a run of rounds, each round every flow once in a random
order, and the packet sizes are the mix's parts repeated and shuffled.
Everything is drawn from ``--seed`` through one ``numpy`` generator: the
same seed gives the same stream, and every seed gives the same flows'
counts and the same sizes, in another order.
"""
from __future__ import annotations

import json
import os
from typing import Dict

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> Dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def flow_keys(n: int, rng: np.random.Generator, mix: Dict) -> np.ndarray:
    """``n`` five-tuples: pseudorandom addresses and ports."""
    five = np.zeros((n, 5), np.uint32)
    five[:, 0] = rng.integers(0, 1 << 32, n, dtype=np.uint32)
    five[:, 1] = rng.integers(0, 1 << 32, n, dtype=np.uint32)
    five[:, 2] = rng.integers(0, 1 << 32, n, dtype=np.uint32)
    five[:, 3] = mix["protocol"]
    return five


def build(mix: Dict, seed: int, batch_events: int) -> Dict:
    """The cell's event stream.

    Returns ``{"five_tuple": (L, 5) u32, "size": (L,) u32,
    "per_period": events offered per period}``, where
    ``L = per_period * trace_periods``. ``batch_events`` is the step's
    event batch (the line-rate ``per_period``)."""
    rng = np.random.default_rng(seed)
    per_period = mix["events_per_period"] or batch_events
    if per_period > batch_events:
        raise ValueError(f"{per_period} events per period exceed the "
                         f"batch of {batch_events}")
    length = per_period * mix["trace_periods"]
    n = mix["flows"]
    keys = flow_keys(n, rng, mix)
    rounds = -(-length // n)
    fidx = np.concatenate([rng.permutation(n) for _ in range(rounds)])
    pattern = np.repeat(np.asarray(mix["frame_bytes"], np.uint32),
                        mix["frame_parts"])
    size = rng.permutation(np.resize(pattern, length))
    return {"five_tuple": keys[fidx[:length]], "size": size,
            "per_period": per_period}
