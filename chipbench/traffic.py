"""The one generator that every traffic file feeds.

A traffic mix is a JSON file of parameters in ``chipbench/traffic/``. Its
``loop`` names the runner, ``chipbench/runners/<loop>.py``, and the other
keys are that runner's parameters:

* ``{"loop": "batch", "crossbars": B}``: engine calls over ``B``
  independent crossbar images, the same images replayed call after call;
* ``{"loop": "open", "arrivals": a, "rate_per_s": r, "mix": {kind: weight},
  "max_units": u}``: requests due at ``r`` per second with the gap shape
  ``chipbench/arrivals/<a>.py``, served by ``submit`` + ``step(max_units=u)``.

Every seed draws the same work: the same number of requests of each kind
and the same set of gaps, in an order and with operand values that the
seed draws.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

from . import bench

# independent random streams drawn from one seed
STREAM_ORDER, STREAM_GAPS, STREAM_OPERANDS, STREAM_WARM = range(4)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def kinds_for(mix: Dict[str, float], n: int) -> List[str]:
    """``n`` request kinds in exact proportion to ``mix`` (largest
    remainder), in the mix's key order."""
    total = float(sum(mix.values()))
    want = {k: n * w / total for k, w in mix.items()}
    count = {k: int(math.floor(v)) for k, v in want.items()}
    rest = sorted(mix, key=lambda k: -(want[k] - count[k]))
    for k in rest[:n - sum(count.values())]:
        count[k] += 1
    return [k for k in mix for _ in range(count[k])]


def open_schedule(traffic: dict, seconds: float, seed: int
                  ) -> List[Tuple[float, str]]:
    """``(due_s, kind)`` for every request due in a window of ``seconds``."""
    n = max(1, int(round(float(traffic["rate_per_s"]) * seconds)))
    kinds = kinds_for(traffic["mix"], n)
    rng(seed, STREAM_ORDER).shuffle(kinds)
    gaps = np.array(bench.load_module("arrivals", traffic["arrivals"])
                    .gaps(traffic, n), dtype=np.float64)
    rng(seed, STREAM_GAPS).shuffle(gaps)
    # every seed sums the same gaps: the last request is due at the same
    # time, inside the window
    due = np.cumsum(gaps)
    due *= min(1.0, seconds * (n - 0.5) / n / due[-1])
    return list(zip(due.tolist(), kinds))
