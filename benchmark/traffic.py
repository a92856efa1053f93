"""The one traffic generator: a mix is a data file, never code.

``traffic/<mix>.json`` holds the sizes of a training job's batches and,
under ``fields``, how each array of a batch is drawn.  A dimension is a
number, or the name of a number in the mix or in the configuration's
``model``.  The draws:

    uniform        float32 in [0, 1)
    one_hot        float32 one-hot rows over ``classes``
    tokens         int32 in [``low``, ``high``)
    choose_k       float32 0/1 rows with exactly ``k`` ones each
    ones           float32 ones
    where          ``mask`` field > 0 ? the constant ``then`` : field ``else``

Every seed gives the same shapes and the same amount of work; only the
values differ.  ``cycle`` batches are made once, in bulk, on the host;
the window cycles them, so every step pays a real host-to-device copy.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _dim(value, mix: dict, model: dict) -> int:
    if isinstance(value, str):
        value = mix[value] if value in mix else model[value]
    return int(value)


def _draw(spec: dict, rng, n: int, mix: dict, model: dict, done: dict):
    dims = (n,) + tuple(_dim(d, mix, model) for d in spec.get("shape", ()))
    kind = spec["draw"]
    if kind == "uniform":
        return rng.random(dims, dtype=np.float32)
    if kind == "one_hot":
        classes = _dim(spec["classes"], mix, model)
        out = np.zeros((n, classes), np.float32)
        out[np.arange(n), rng.integers(0, classes, n)] = 1.0
        return out
    if kind == "tokens":
        return rng.integers(_dim(spec["low"], mix, model),
                            _dim(spec["high"], mix, model), dims,
                            dtype=np.int32)
    if kind == "choose_k":
        k = _dim(spec["k"], mix, model)
        order = np.argsort(rng.random(dims), axis=-1)
        return (order < k).astype(np.float32)
    if kind == "ones":
        return np.ones(dims, np.float32)
    if kind == "where":
        other = done[spec["else"]]
        return np.where(done[spec["mask"]] > 0,
                        np.asarray(spec["then"], other.dtype), other)
    raise KeyError(f"traffic.py knows no draw named {kind!r}")


def make_batches(mix: dict, model: dict, seed: int) -> list:
    """``mix['cycle']`` batches as dicts of numpy arrays, drawn in bulk
    from ``seed``.  Fields are drawn in the file's order, so a ``where``
    may name any field above it; fields whose name starts with ``_``
    are scaffolding and are dropped."""
    rng = np.random.default_rng(seed)
    batch, cycle = int(mix["batch"]), int(mix["cycle"])
    n = batch * cycle
    done: dict = {}
    for name, spec in mix["fields"].items():
        done[name] = _draw(spec, rng, n, mix, model, done)
    return [{k: v[i * batch:(i + 1) * batch] for k, v in done.items()
             if not k.startswith("_")} for i in range(cycle)]


class DeadlineCycle:
    """Iterate ``batches`` round and round until ``seconds`` have passed
    since :meth:`start`; counts what it handed out.  The program's feeder
    pulls from it on its own thread, a few batches ahead of the step."""

    def __init__(self, batches: list, seconds: float):
        self.batches, self.seconds = batches, float(seconds)
        self.handed_out = 0
        self.deadline = None

    def start(self) -> float:
        now = time.perf_counter()
        self.deadline = now + self.seconds
        return now

    def __iter__(self):
        if self.deadline is None:
            raise RuntimeError("DeadlineCycle.start() was not called")
        while time.perf_counter() < self.deadline:
            yield self.batches[self.handed_out % len(self.batches)]
            self.handed_out += 1
