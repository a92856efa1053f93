"""Smoke tests for the examples gallery (dl4j-examples parity): every
example must run end-to-end at tiny sizes on the test mesh."""

import numpy as np
import pytest

from examples import (bert_mlm_finetune, char_rnn_textgen,
                      data_parallel_training, early_stopping,
                      fault_tolerant_training, lenet_cifar10,
                      lstm_uci_har, mlp_mnist, model_serving,
                      multislice_dcn_training, online_learning,
                      pipeline_parallel_bert, replica_scaling,
                      training_dashboard, transfer_learning,
                      warm_restart, word2vec_embeddings)


def test_mlp_mnist_example():
    # 2 epochs: 1 epoch on 512 synthetic samples lands right at the 0.5
    # threshold and flips with jax-version numerics (0.46 on 0.4.x,
    # >0.5 on the rig's newer jax); 2 epochs is robustly >0.9
    acc = mlp_mnist.main(epochs=2, batch_size=64, hidden=32,
                         n_synthetic=512, verbose=False)
    assert acc > 0.5


def test_lenet_cifar10_example():
    acc = lenet_cifar10.main(epochs=1, batch_size=64, n_synthetic=256,
                             verbose=False)
    assert 0.0 <= acc <= 1.0


def test_lstm_uci_har_example():
    acc = lstm_uci_har.main(epochs=1, batch_size=32, n_synthetic=128,
                            verbose=False)
    assert 0.0 <= acc <= 1.0


@pytest.mark.slow
def test_char_rnn_example_generates_text():
    text = char_rnn_textgen.main(epochs=1, seq_len=16, batch_size=8,
                                 hidden=24, verbose=False)
    assert isinstance(text, str) and len(text) > 60


def test_bert_finetune_example_loss_decreases():
    losses = bert_mlm_finetune.main(epochs=3, seq_len=16, batch_size=8,
                                    verbose=False)
    assert losses[-1] < losses[0]


def test_transfer_learning_example_freezes_base():
    net = transfer_learning.main(pretrain_epochs=1, finetune_epochs=1,
                                 verbose=False)
    assert net.conf.layers[-1].n_out == 5


def test_early_stopping_example_stops_and_restores():
    result = early_stopping.main(max_epochs=8, patience=2, verbose=False)
    assert result.total_epochs <= 8
    assert np.isfinite(result.best_model_score)


def test_data_parallel_example():
    acc = data_parallel_training.main(epochs=2, verbose=False)
    assert acc > 0.5


def test_word2vec_example():
    model = word2vec_embeddings.main(epochs=8, vector_size=16, verbose=False)
    assert model.similarity("cat", "dog") > model.similarity("cat", "gpu")


def test_dashboard_example_writes_report(tmp_path):
    out = training_dashboard.main(epochs=2,
                                  report_path=str(tmp_path / "r.html"),
                                  verbose=False)
    html = open(out).read()
    assert "Score (loss)" in html and "histogram" in html.lower()


def test_multislice_dcn_example():
    losses = multislice_dcn_training.main(steps=6, verbose=False)
    assert losses[-1] < losses[0]


def test_model_serving_example(tmp_path):
    result = model_serving.main(train_epochs=1, workdir=str(tmp_path),
                                verbose=False)
    # deploy → hot-swap → rollback: three versions answered over HTTP
    assert result["versions_served"] == [1, 2, 3]
    assert result["final_version"] == 3


def test_warm_restart_example(tmp_path):
    result = warm_restart.main(workdir=str(tmp_path), verbose=False)
    # the restarted server answered from the artifact store: no XLA
    # trace on the request path, and the first response got faster
    assert result["zero_jit_after_warm"] is True, result
    assert result["warm"]["classes"] == warm_restart.N_CLASSES
    assert result["first_response_speedup"] > 1.0


def test_online_learning_example(tmp_path):
    result = online_learning.main(feedback_records=48, verbose=False,
                                  workdir=str(tmp_path))
    # deploy → live feedback → background gated swap → forced rollback:
    # three versions answered over HTTP, the last one a rollback
    assert result["versions"] == [1, 2, 3]
    assert result["rolled_back"] is True
    assert result["deploys"] >= 1


def test_replica_scaling_example(tmp_path):
    result = replica_scaling.main(workdir=str(tmp_path), verbose=False)
    # load ramp → autoscale → fan-out hot-swap → all-replica rollback:
    # the fleet grew, three versions served, nothing dropped or garbled
    assert result["replicas_grown_to"] >= 2
    assert result["versions"] == [1, 2, 3]
    assert result["rolled_back"] is True
    assert result["dropped"] == 0
    assert result["garbled"] == 0
    assert result["answered"] > 0


def test_fault_tolerant_training_example(tmp_path):
    drift = fault_tolerant_training.main(epochs=2, crash_at_step=11,
                                         checkpoint_dir=str(tmp_path),
                                         verbose=False)
    assert drift <= 1e-6


@pytest.mark.slow
def test_pipeline_parallel_bert_example():
    losses = pipeline_parallel_bert.main(steps=2, verbose=False)
    assert all(np.isfinite(l) for l in losses)
    assert losses[1] < losses[0]
