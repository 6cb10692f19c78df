"""The rest of a run, with the harness's look for a chip skipped (the
named rehearsal mode: tiny sizes, the CPU) and the timed path broken
underneath: ``correct`` has to come out false for each fault a cell can
have, and true for the sound program.  The control (the reference one
precision below the configuration's) is kept here too, at the test's
size."""
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, harness, traffic
from benchmark.drivers import serve, train
from benchmark.references import training

SEED = 2 ** 31 + 99


def _spec(cell):
    return harness.load_cell(cell, rehearse=True)


def _args(build=None, seconds=0.5):
    return types.SimpleNamespace(seed=SEED, seconds=seconds, trace=0,
                                 rehearse=True, build=build)


def _verdict(out):
    over = [n for n, v, lim in out["checks"] if not harness.within(v, lim)]
    assert harness.verdict(out["checks"]) == (not over)
    return not over, over


# ---------------------------------------------------------------- training
@pytest.fixture(scope="module")
def train_spec():
    return _spec("gpt3-1.3b.train")


def test_sound_training_run_is_correct(train_spec):
    import time
    out = train.run(train_spec, _args(), time.perf_counter(), {})
    ok, over = _verdict(out)
    assert ok, (over, out["checks"])
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["e2e"]["train_tokens_per_s"] > 0


class _StateUnchanged(train.TrainCell):
    """A step that returns its state unchanged: the loss is computed,
    the parameters are put back."""

    def one_step(self):
        saved = {n: jnp.copy(p._array)
                 for n, p in self.model.named_parameters()}
        loss = super().one_step()
        self.model.set_state_dict(saved)
        return loss


class _HalfBatch(train.TrainCell):
    """Half of the batch left out, the mean taken over the rest."""

    def feed(self, i):
        ids, labels = super().feed(i)
        return ids[: self.batch // 2], labels[: self.batch // 2]


@pytest.mark.parametrize("fault,number", [
    (_StateUnchanged, "change_norm_gap"), (_HalfBatch, "grad_norm_gap")])
def test_a_broken_training_step_is_not_correct(train_spec, fault, number):
    import time
    out = train.run(train_spec, _args(build=fault), time.perf_counter(), {})
    ok, over = _verdict(out)
    assert not ok and number in over, out["checks"]


def test_prove_judges_training_controls_by_the_cells_limits(capsys):
    """The control and both faults go through the cell's own limits and
    the harness's verdict, and each comes out not correct."""
    from benchmark import prove
    rc = prove.main(["--workload", "gpt3-1.3b.train", "--rehearse",
                     "--seeds", str(SEED)])
    out = capsys.readouterr().out
    assert rc == 0, out
    for tag in ("control_fp8", "fault_half_batch", "fault_state_unchanged"):
        line = [l for l in out.splitlines() if f" {tag}:" in l]
        assert len(line) == 1 and "correct=False as it has to be" in line[0]


def test_prove_fails_when_a_control_passes(train_spec, capsys):
    from benchmark import prove
    loose = dict(train_spec, mix=dict(train_spec["mix"], limits={
        n: 1e9 for n in train_spec["mix"]["limits"]}))
    assert not prove.judged(loose, SEED, "control", [
        (n, 0.5) for n in loose["mix"]["limits"]], False)
    assert "AND HAS TO BE False" in capsys.readouterr().out


# ----------------------------------------------------------------- serving
@pytest.fixture(scope="module")
def chat_spec():
    return _spec("gpt3-1.3b.chat")


def test_sound_serving_run_is_correct(chat_spec):
    import time
    out = serve.run(chat_spec, _args(seconds=2.0), time.perf_counter(), {})
    ok, over = _verdict(out)
    assert ok, (over, out["checks"])
    assert out["attempted"] > 0 and out["failed"] == 0
    for name in ("ttft_p95_ms", "tpot_p95_ms", "serve_tokens_per_s"):
        assert out["e2e"][name] > 0
    assert len(out["extra"]["queue_depth_by_third"]) == 3


@pytest.mark.parametrize("depths, want", [
    ([(0.5, 0), (1.5, 0), (2.5, 1)], [0.0, 0.0, 1.0]),         # below a knee
    ([(0.2, 2), (0.8, 4), (1.5, 9), (2.9, 20), (3.0, 99)], [3.0, 9.0, 20.0]),
    ([(-1.0, 50), (1.5, 2)], [0.0, 2.0, 0.0])])     # lead-in never counts
def test_queue_depth_is_the_mean_of_each_third_of_the_window(depths, want):
    t_open = 100.0
    got = serve.depth_by_thirds([(t_open + t, d) for t, d in depths],
                                t_open, t_open + 3.0)
    assert got == want


class _AlteredToken(serve.ServeCell):
    """A token altered where it is produced: every fifth logits row has
    its best token pushed to the bottom before the engine samples."""

    def __init__(self, spec, seed):
        super().__init__(spec, seed)
        emit, count = self.eng._emit, [0]

        def altered(req, row, now):
            count[0] += 1
            if count[0] % 5 == 0:
                row = np.array(row)
                row[int(np.argmax(row))] = row.min() - 1.0
            return emit(req, row, now)
        self.eng._emit = altered


def test_an_altered_token_is_not_correct(chat_spec):
    import time
    out = serve.run(chat_spec, _args(build=_AlteredToken, seconds=2.0),
                    time.perf_counter(), {})
    ok, over = _verdict(out)
    assert not ok and "served_logit_gap" in over, out["checks"]


def test_prove_judges_the_serving_control_by_the_cells_limit(capsys):
    """A short window at the rehearsal's load: the program's reading is
    correct by the cell's limit, the fp8 control's is not."""
    from benchmark import prove
    rc = prove.main(["--workload", "gpt3-1.3b.chat", "--rehearse",
                     "--seeds", str(SEED), "--seconds", "2"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "program: served_logit_gap=" in out
    assert "control_fp8: served_logit_gap=" in out
    assert out.count("as it has to be") == 2
