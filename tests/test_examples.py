"""Smoke the examples/ scripts end-to-end (tiny configs, CPU) so they
cannot rot — the role of the reference's tests/python/train tier +
example CI.  The image and generative examples are in
test_examples_vision.py: one file is one ``--dist loadfile`` group, and
all 25 examples in one group were the suite's longest (291 s)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(script, *args, timeout=420, env=None):
    merged = dict(os.environ, JAX_PLATFORMS="cpu")
    merged.update(env or {})
    env = merged
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, script), *args],
        capture_output=True, text=True, env=env, cwd=REPO,
        timeout=timeout)
    assert res.returncode == 0, (script, res.stdout[-2000:],
                                 res.stderr[-2000:])
    return res.stdout + res.stderr


def test_example_autograd_basics():
    out = _run("examples/autograd/autograd_basics.py")
    assert "recovered" in out


def test_example_sparse_linear():
    out = _run("examples/sparse/linear_classification.py",
               "--num-epochs", "3", "--num-examples", "512")
    assert "train-acc" in out


def test_example_recommender_mf():
    """Sparse at embedding scale (VERDICT r4 item 4): MF over
    row_sparse_pull / row_sparse push / sparse.sgd_update must learn
    (RMSE falls) and bucketing must bound the compile count."""
    import json

    out = _run("examples/recommenders/matrix_fact.py",
               "--num-epochs", "5", "--num-ratings", "20000",
               "--num-users", "1000", "--num-items", "500",
               "--nnz-buckets", "--bench")
    line = [l for l in out.splitlines() if l.startswith("{")][-1]
    res = json.loads(line)
    assert res["val_rmse"] < 1.05, res
    # power-of-two bucketing: compile count stays O(log nnz), far under
    # the one-shape-per-batch worst case (5 epochs x 5 batches x 8 pulls)
    assert res["distinct_sparse_shapes"] <= 16, res


def test_example_nce():
    """NCE head (reference example/nce-loss): logistic discrimination
    over 1+K candidates must shape the output table so the FULL-vocab
    argmax recovers the target."""
    out = _run("examples/nce-loss/toy_nce.py", "--num-epochs", "15",
               "--num-examples", "4096", "--vocab", "20")
    acc = float(out.split("argmax accuracy")[1].split()[0])
    assert acc > 0.9, out


def test_example_pipeline_transformer():
    out = _run("examples/model-parallelism/pipeline_transformer.py",
               "--num-epochs", "8",
               env={"XLA_FLAGS":
                        "--xla_force_host_platform_device_count=4"})
    assert "PIPELINE TRAINS OK" in out


def test_example_gluon_moe():
    out = _run("examples/gluon/moe_classifier.py", "--num-epochs", "12",
               "--num-examples", "128")
    assert "GLUON MOE TRAINS OK" in out


def test_example_reinforce():
    """Imperative policy-gradient rollouts: per-step recorded forwards,
    one backward per episode batch; the chain-walk policy must learn."""
    out = _run("examples/reinforcement-learning/reinforce.py",
               "--iters", "60")
    final = float(out.split("final mean-episode-reward")[1].split()[0])
    assert final > 0.8, out


def test_example_text_cnn():
    out = _run("examples/cnn_text_classification/text_cnn.py",
               "--num-epochs", "6", "--num-examples", "512")
    acc = float(out.split("train accuracy")[1].split()[0])
    assert acc > 0.95, out


def test_example_multitask():
    out = _run("examples/multi-task/multitask.py", "--num-epochs", "12")
    quad = float(out.split("quad accuracy")[1].split()[0])
    size = float(out.split("size accuracy")[1].split()[0])
    assert quad > 0.9 and size > 0.9, out


def test_example_bi_lstm_sort():
    out = _run("examples/bi-lstm-sort/bi_lstm_sort.py",
               "--num-epochs", "12", "--num-examples", "1024")
    acc = float(out.split("sort accuracy")[1].split()[0])
    assert acc > 0.9, out


def test_example_ctc_ocr():
    """CTC sequence training (reference example/warpctc): alignment-
    free digit-string OCR; greedy decode must recover exact strings."""
    out = _run("examples/warpctc/ctc_ocr.py", "--num-epochs", "12",
               "--num-examples", "768")
    acc = float(out.split("exact-string accuracy")[1].split()[0])
    assert acc > 0.85, out


def test_example_svm():
    out = _run("examples/svm_mnist/svm_mnist.py", "--num-epochs", "20")
    svm = float(out.split("svm acc")[1].split()[0])
    sm = float(out.split("softmax acc")[1].split()[0])
    assert svm > 0.95 and sm > 0.95, out


def test_example_numpy_ops():
    """Reference example/numpy-ops: a CustomOp whose forward AND
    backward are plain numpy trains inside a symbolic graph."""
    out = _run("examples/numpy-ops/numpy_softmax.py",
               "--num-epochs", "25")
    acc = float(out.split("numpy-op accuracy")[1].split()[0])
    assert acc > 0.95, out
