"""What an entry reads off the program's state for the comparison:
per-leaf norms, the first gradient out of an optax state, and the two
listeners every entry hangs on the program's ``fit``.  A listener takes
the score as the loop hands it, python float or device scalar, and
converts it itself where it needs the number."""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def leaf_norms(tree):
    """L2 norm of every leaf, in float32, as a list in leaf order."""
    return [jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for leaf in jax.tree_util.tree_leaves(tree)]


@jax.jit
def change_norms(now, before):
    return leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        now, before))


class FlatReader:
    """Per-leaf norms of a program's tree under the reference's flat names.
    ``flatten`` maps the program's tree to ``{reference name: array}`` in
    the reference's shapes; the norms come back in ``names`` order."""

    def __init__(self, flatten, weights: dict):
        self.names = sorted(weights)     # jax flattens a dict in key order
        self._weights = weights
        self._norms = jax.jit(lambda tree: leaf_norms(flatten(tree)))
        self._change = jax.jit(
            lambda tree, before: change_norms(flatten(tree), before))

    def norms(self, tree):
        return self._norms(tree)

    def change(self, tree):
        return self._change(tree, self._weights)

    def as_dict(self, values, scale: float = 1.0) -> dict:
        return {n: float(v) / scale for n, v in zip(self.names, values)}


class FirstSteps:
    """Listener for the first steps: every score as handed over, the
    optimizer's first moment after step one, the parameters' change after
    the last.  ``params_of(model)`` is the program's parameter tree."""

    def __init__(self, reader: FlatReader, n_steps: int, params_of):
        self.reader, self.n_steps, self.params_of = reader, n_steps, params_of
        self.losses, self.moment, self.change = [], None, None

    def iteration_done(self, model, iteration, epoch, loss):
        self.losses.append(loss)
        if len(self.losses) == 1:
            self.moment = self.reader.norms(first_moment(model.opt_state))
        if len(self.losses) == self.n_steps:
            self.change = self.reader.change(self.params_of(model))

    def readings(self, optimizer: dict) -> dict:
        """What ``compare.gaps`` takes, as python floats."""
        return {
            "losses": [float(x) for x in self.losses],
            "grad_norms": self.reader.as_dict(
                self.moment, first_gradient_factor(optimizer)),
            "delta_norms": self.reader.as_dict(self.change),
        }


class Cadence:
    """The window's listener: counts every step and reads the loss every
    ``every`` steps, as ``ScoreIterationListener(every)`` does for a user
    who logs it.  ``float()`` takes a python float and a device scalar
    alike (the second waits for the step), so the entry never relies on
    the program's loop having read the loss."""

    def __init__(self, every: int):
        self.every, self.steps, self.losses = max(1, int(every)), 0, []

    def iteration_done(self, model, iteration, epoch, loss):
        self.steps += 1
        if self.steps % self.every == 0:
            self.losses.append(float(loss))


def first_moment(opt_state):
    """The momentum trace (SGD) or ``mu`` (Adam) inside an optax state:
    after exactly one step it is the first gradient as the optimizer
    got it, times the factor :func:`first_gradient_factor` gives."""
    found = []

    def walk(node):
        for attr in ("trace", "mu"):
            if hasattr(node, attr) and not isinstance(node, dict):
                found.append(getattr(node, attr))
                return
        if isinstance(node, (tuple, list)):
            for child in node:
                walk(child)

    walk(opt_state)
    if len(found) != 1:
        raise ValueError(
            f"expected one momentum or Adam state in the optimizer's "
            f"state, found {len(found)}")
    return found[0]


def first_gradient_factor(optimizer: dict) -> float:
    """moment after one step = factor x first gradient."""
    if optimizer["kind"] == "adam":
        return 1.0 - optimizer["beta1"]
    if optimizer["kind"] == "nesterov":
        return 1.0
    raise KeyError(f"no first-moment rule for {optimizer['kind']!r}")
