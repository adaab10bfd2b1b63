"""How `correct` comes out: true for a sound run, false for the control
(the next lower precision) and false when the timed path is broken
underneath.  Each case skips the harness's look for a chip (``--rehearse``)
and drives the rest of a run at the configurations' rehearsal sizes, on
the CPU, against the limits' ``rehearse`` group.  The limits of the cells
themselves were set from chip runs (``PERF.md`` section 2); these sizes
are what a test run can hold.
"""
import re

import pytest

import run

def execute(cell, seed, **keywords):
    result, _ = run.execute(["--workload", cell, "--seed", str(seed),
                             "--seconds", "1", "--trace", "0", "--rehearse"],
                            **keywords)
    return result


@pytest.mark.parametrize("cell,seed", [
    ("cgpt1.3b-fit", 1), ("cgpt1.3b-fit", 2 ** 31 + 11),
    ("cgpt1.3b-chat", 1), ("cgpt1.3b-chat", 2 ** 31 + 11)])
def test_sound_run_is_correct(cell, seed):
    result = execute(cell, seed)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0


@pytest.mark.parametrize("seed", [1, 2])
def test_fp8_training_is_not_correct(monkeypatch, seed):
    """The bf16 training cells' control: the program's own fp8 path."""
    monkeypatch.setenv("MXNET_FP8", "on")
    assert execute("cgpt1.3b-fit", seed)["correct"] is False


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_serving_is_not_correct(seed):
    """The float32 serving cell's control: the session's own weight-only
    int8 path (``control`` lays the traffic file's group of that name
    over the session's settings)."""
    assert execute("cgpt1.3b-chat", seed, control=True)["correct"] is False


def test_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from mxnet_tpu import optimizer

    def unchanged(self, weight, grad, state, lr, wd, t, rng):
        return weight, state

    monkeypatch.setattr(optimizer.SGD, "fused_update", unchanged)
    assert execute("cgpt1.3b-fit", 1)["correct"] is False


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_part_of_the_batch_left_out_is_not_correct(monkeypatch, capsys, seed):
    """The fault the loss's limit is held against: every batch's last
    sequence never reaches ``fit`` (the first takes its place)."""
    import mxnet_tpu as mx

    init = mx.io.NDArrayIter.__init__

    def short(self, data, label, batch_size, **keywords):
        data, label = data.copy(), label.copy()
        data[batch_size - 1::batch_size] = data[::batch_size]
        label[batch_size - 1::batch_size] = label[::batch_size]
        init(self, data, label, batch_size=batch_size, **keywords)

    monkeypatch.setattr(mx.io.NDArrayIter, "__init__", short)
    assert execute("cgpt1.3b-fit", seed)["correct"] is False
    failed = re.findall(r"check (\S+) .* FAILED", capsys.readouterr().out)
    assert any(name.startswith("loss_gap_step") for name in failed), failed


def test_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from mxnet_tpu import serve

    step = serve.InferenceSession.step

    def altered(self):
        tokens, logits = step(self)
        slot = min(tokens)
        tokens[slot] = (tokens[slot] + 1) % self.model.vocab_size
        return tokens, logits

    monkeypatch.setattr(serve.InferenceSession, "step", altered)
    assert execute("cgpt1.3b-chat", 1)["correct"] is False
