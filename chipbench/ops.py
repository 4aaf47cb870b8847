"""The request kinds a configuration names, dispatched by their ``op``.

A request spec is ``{"op": <kind>, "shape": [...], ...}``. The kind's own
file, ``chipbench/kinds/<op>.py``, gives its operands, plain reference,
control and exact form, and submits it to a ``PlanService``; an engine
cell's plan and batched decode are in ``chipbench/plans/<op>.py``. The
references are plain integer numpy and import nothing of the program. Each
control is the reference with one guarantee that the configuration states
broken, in any array namespace (``numpy`` here, ``jax.numpy`` on the chip).
"""
from __future__ import annotations

import numpy as np

from . import bench


def kind(spec: dict):
    return bench.load_module("kinds", spec["op"])


def operands(spec: dict, rng: np.random.Generator, batch=()) -> tuple:
    """Fresh operands for one request (or a leading ``batch`` of them)."""
    return kind(spec).operands(spec, rng, tuple(batch))


def reference(spec: dict, a, b) -> np.ndarray:
    """Exact result of one request (or a leading batch of them)."""
    return kind(spec).reference(spec, a, b)


def control(spec: dict, a, b, xp=np):
    """The reference with one stated guarantee broken, computed in ``xp``."""
    return kind(spec).control(spec, a, b, xp)


def wrong(spec: dict, got, want) -> int:
    """Number of results that differ in any element (leading axis = results;
    a single result counts as one), compared in the kind's exact form."""
    exact = kind(spec).exact
    g, w = exact(spec, np.asarray(got)), exact(spec, np.asarray(want))
    if g.shape != w.shape:
        return len(w) if w.ndim > 1 else 1
    diff = g != w
    if diff.ndim <= 1:
        return int(diff.any())
    return int(diff.reshape(diff.shape[0], -1).any(axis=1).sum())


def submit(svc, spec: dict, a, b):
    """Submit one request of this kind to a ``PlanService``."""
    return kind(spec).submit(svc, spec, a, b)


def make_plan(spec: dict, geometry: dict):
    """The single-crossbar plan of an engine cell."""
    return bench.load_module("plans", spec["op"]).make(spec, geometry)


def decode_batch(spec: dict, plan, mems: np.ndarray) -> np.ndarray:
    """Results of a batch of final crossbar images."""
    return bench.load_module("plans", spec["op"]).decode(spec, plan, mems)
