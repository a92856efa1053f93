"""A run with the timed path broken underneath has to come out with
``correct`` false.  The harness's look for a chip is skipped; the rest of
the run is the real one at a tiny size.  Once for each fault a one-chip
training cell can have:

* a step that returns its state unchanged;
* half of the batch left out, the mean taken over the rest.

(The exchange between chips and an altered token are not faults a
one-chip training cell can have.)  The limits are the tiny cells' own:
well above what their sound runs read on the CPU, under what the faults
read.
"""

import dataclasses

import jax
import numpy as np
import pytest

import tiny
from test_harness_cpu import run_tiny

TINY_LIMITS = tiny.LIMITS


@pytest.fixture
def fresh_steps():
    from deeplearning4j_tpu.train import step_cache
    step_cache.clear_step_cache()
    yield
    step_cache.clear_step_cache()


def _frozen_trainer_step(monkeypatch):
    from deeplearning4j_tpu.train import trainer

    def make(net, tx, with_stats=False, opt_state_shardings=None):
        loss_fn = trainer.make_loss_fn(net)

        @jax.jit
        def step(params, state, opt_state, features, labels, fmask, lmask,
                 rng):
            loss, _ = loss_fn(params, state, features, labels, fmask, lmask,
                              rng)
            return params, state, opt_state, loss
        return step
    monkeypatch.setattr(trainer, "make_train_step", make)


def _frozen_bert_step(monkeypatch):
    from deeplearning4j_tpu.models import bert

    def make(self, tx):
        config = self.config

        @jax.jit
        def step(params, opt_state, ids, labels, weights, attn, rng):
            loss = bert.mlm_loss(params, config, ids, labels, weights,
                                 attention_mask=attn, train=True, rng=rng)
            return params, opt_state, loss
        return step
    monkeypatch.setattr(bert.BertForMaskedLM, "make_train_step", make)


def _half_batch(monkeypatch):
    """The feeder drops the second half of every batch from the loss."""
    from deeplearning4j_tpu.data import device_pipeline
    from deeplearning4j_tpu.data.dataset import DataSet
    stage = device_pipeline.DeviceFeeder.stage

    def broken(self, batch):
        if isinstance(batch, DataSet):
            keep = np.ones((batch.num_examples(),), np.float32)
            keep[len(keep) // 2:] = 0.0
            batch = dataclasses.replace(batch, labels_mask=keep)
        else:
            keep = np.ones((len(batch["input_ids"]), 1), np.float32)
            keep[len(keep) // 2:] = 0.0
            batch = dict(batch, label_weights=batch["label_weights"] * keep)
        return stage(self, batch)
    monkeypatch.setattr(device_pipeline.DeviceFeeder, "stage", broken)


FAULTS = {
    ("resnet50_unfused.train_b128", "state_unchanged"): _frozen_trainer_step,
    ("resnet50_unfused.train_b128", "half_batch"): _half_batch,
    ("bert_base.mlm_s512_b32", "state_unchanged"): _frozen_bert_step,
    ("bert_base.mlm_s512_b32", "half_batch"): _half_batch,
}


@pytest.mark.parametrize("name", sorted(tiny.CELLS))
def test_sound(name, tmp_path, fresh_steps):
    result = run_tiny(name, limits=TINY_LIMITS[name], tmp_path=tmp_path)
    assert result["correct"] is True, result["compared"]


@pytest.mark.parametrize("name,fault", sorted(FAULTS))
def test_fault_reads_not_correct(name, fault, tmp_path, monkeypatch,
                                 fresh_steps):
    FAULTS[name, fault](monkeypatch)
    result = run_tiny(name, limits=TINY_LIMITS[name], tmp_path=tmp_path)
    assert result["correct"] is False, result["compared"]
    over = [n for n, c in result["compared"].items()
            if c["value"] > c["limit"]]
    assert over, result["compared"]
    if fault == "state_unchanged":
        # nothing moved: the change reads exactly 1 by the measure
        assert result["compared"]["delta_gap"]["value"] == pytest.approx(1.0)
