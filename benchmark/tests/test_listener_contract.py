"""An entry takes the score as the loop hands it.  ``Trainer.fit`` hands
its listeners the step's device scalar; ``BertForMaskedLM.fit`` reads the
loss itself and hands over a python float, until a program PR gives it
Trainer's form.  Here the tiny BERT twin's ``fit`` hands over each kind in
turn, through the whole of ``harness.run_cell``: the result line is plain
JSON, the loss is read at the mix's cadence, every batch handed out became
a step, and the comparison still passes."""

import json
import math

import jax.numpy as jnp
import numpy as np
import pytest

import harness
import tiny
from test_harness_cpu import run_tiny

CELL = "bert_base.mlm_s512_b32"
HANDS_OVER = {"device_scalar": jnp.float32, "numpy_scalar": np.float32,
              "python_float": float}


class _Handing:
    """A listener's stand-in inside ``fit``: converts the score to what
    the loop under test would hand over, keeps it in ``handed``, then
    calls the listener."""

    def __init__(self, listener, convert, handed: list):
        self.listener, self.convert, self.handed = listener, convert, handed

    def iteration_done(self, model, iteration, epoch, loss):
        self.handed.append(self.convert(loss))
        self.listener.iteration_done(model, iteration, epoch,
                                     self.handed[-1])


@pytest.mark.parametrize("kind", sorted(HANDS_OVER))
def test_bert_window_takes_the_score_as_handed(kind, tmp_path, monkeypatch):
    from deeplearning4j_tpu.models.bert import BertForMaskedLM
    fit, convert, handed = BertForMaskedLM.fit, HANDS_OVER[kind], []

    def fit_handing(self, batches, updater=None, epochs=1, listeners=None):
        return fit(self, batches, updater=updater, epochs=epochs,
                   listeners=[_Handing(x, convert, handed)
                              for x in listeners])
    monkeypatch.setattr(BertForMaskedLM, "fit", fit_handing)
    result = run_tiny(CELL, limits=tiny.LIMITS[CELL], tmp_path=tmp_path)
    assert handed and all(type(x) is type(convert(0.0)) for x in handed)
    window = json.loads(json.dumps(result))["window"]
    loss_every = tiny.bert_base()[1]["loss_every"]
    assert window["steps"] == result["attempted"] > loss_every
    assert window["losses_read"] == window["steps"] // loss_every
    assert type(result["window"]["last_loss"]) is float
    assert result["correct"] is True and result["failed"] == 0, \
        result["compared"]


@pytest.mark.parametrize("x,want", [
    (jnp.float32(1.5), 1.5), (np.float32(1.5), 1.5), (np.float64(2.0), 2.0),
    (3, 3.0), (float("nan"), 1e30), (jnp.float32(jnp.nan), 1e30),
    (float("inf"), 1e30), (np.float32("-inf"), 1e30)])
def test_finite_returns_a_python_float(x, want):
    got = harness._finite(x)
    assert type(got) is float and math.isfinite(got) and got == want
    json.dumps({"x": got}, allow_nan=False)
