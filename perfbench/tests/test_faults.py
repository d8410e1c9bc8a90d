"""The rest of a run with the timed path broken underneath: the harness is
driven past its look for a chip (``--rehearse``: toy model, CPU) and has to
report ``correct`` false when a served token is altered where the scheduler
produces it, and true when nothing is broken. A served model's cells can have
one of the four faults: an answer altered where it is produced."""

import json

import pytest

import run as bench

CELLS = ["mistral-7b-int8.sweep", "qwen2-7b-int8.replay"]


def _last_line(capsys, argv):
    assert bench.main(argv) == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"
    assert out.err.strip().splitlines()[-1].startswith("compared: ")
    return line


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(capsys, cell):
    line = _last_line(capsys, ["--workload", cell, "--seed", "2147483999",
                               "--seconds", "2", "--trace", "0",
                               "--rehearse"])
    assert line["correct"] is True and line["failed"] == 0
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert "rehearsal.setup_s" in line["metrics"]


@pytest.mark.parametrize("cell", CELLS)
def test_altered_token_is_not_correct(capsys, monkeypatch, cell):
    from fairness_llm_tpu.serving.scheduler import ContinuousScheduler

    finish = ContinuousScheduler._finish

    def altered(self, slot, reason, stats):
        tokens = self.pool.get(slot).tokens
        if len(tokens) > 4:
            tokens[4] = (int(tokens[4]) + 7) % self.engine.config.vocab_size
        return finish(self, slot, reason, stats)

    monkeypatch.setattr(ContinuousScheduler, "_finish", altered)
    line = _last_line(capsys, ["--workload", cell, "--seed", "5", "--seconds",
                               "2", "--trace", "1", "--rehearse"])
    assert line["correct"] is False
    gap = line["compared"]["widest_gap"]
    assert gap["value"] > gap["limit"]


def test_unanswered_request_is_not_correct(capsys, monkeypatch):
    from perfbench.lib import sut

    generate = sut.ServedModel.generate

    def loses_one(self, specs, call_seed):
        out = generate(self, specs, call_seed)
        out[0] = (False, out[0][1][:0])
        return out

    monkeypatch.setattr(sut.ServedModel, "generate", loses_one)
    line = _last_line(capsys, ["--workload", CELLS[0], "--seed", "6",
                               "--seconds", "1", "--trace", "0",
                               "--rehearse"])
    assert line["correct"] is False and line["failed"] > 0
