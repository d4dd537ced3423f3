"""``correct`` has to come out false when it should.

The control: the reference computed in bfloat16, the nearest precision
below the float32 the configurations state, put in the program's place
has to fail the score comparison while the program itself passes it.

The faults: a whole run of ``run.py`` (the rehearsal rule stands in for
the look for a chip) with the timed path broken underneath, once for
each fault these cells can have. The break is made where the scheduler
takes the device program's answer (``scheduler/stack.py``'s
``KernelOut``), which every program of the served path passes:

- a step that returns its state unchanged: every step of an evaluation
  repeats the first step's node, as a scan whose carry never moves;
- half of the batch left out: the second half of an evaluation's steps
  report nothing found;
- an answer altered where it is produced: every second step's node is
  moved to its neighbour;
- half of the nodes masked: the upper half of the cluster's rows is
  taken out of every evaluation's feasibility mask before the device
  program sees it, as a program would that scans half the nodes. Every
  answer is then still a feasible node with a true score; only the
  reference's look at every node of the cluster shows a better one.

The exchange between chips does not exist in a one-chip cell.
"""

import contextlib
import io
import json

import numpy as np
import pytest

from benchmark import run as run_mod

CELLS = {
    "grid-10k-r75-spread.loop": ["--nodes", "2000"],
}


def drive(cell: str, seed: int, extra=(), size=None) -> tuple:
    """One rehearsal run; (result line, standard error)."""
    out, err = io.StringIO(), io.StringIO()
    argv = ["--workload", cell, "--seed", str(seed), "--seconds", "4",
            "--trace", "0", *(size or CELLS[cell]), *extra]
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        code = run_mod.main(argv)
    assert code == 0, err.getvalue()[-2000:]
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


def stderr_json(err: str, key: str):
    for line in err.splitlines():
        if line.startswith(key + ": "):
            return json.loads(line[len(key) + 2:])
    raise AssertionError(f"no {key!r} line in: {err[-1500:]}")


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct_and_the_control_is_not(cell):
    line, err = drive(cell, 31, ["--control", "bfloat16"])
    assert line["correct"] is True, err[-2000:]
    assert line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    sound = stderr_json(err, "numbers")
    control = stderr_json(err, "control_numbers")
    limit = line["compared"]["score_max_abs_diff"]["limit"]
    assert sound["score_max_abs_diff"] < limit / 3
    assert control["score_max_abs_diff"] > 3 * limit
    assert "control_correct: False" in err


def _arm_with_the_clients(monkeypatch) -> list:
    """The first job, alone, goes through whole: the fault is in the
    traffic's own path, warm-up and window."""
    from benchmark.loops import closed

    start_clients = closed.Loop.start_clients
    armed = []

    def start_and_arm(self):
        armed.append(True)
        start_clients(self)

    monkeypatch.setattr(closed.Loop, "start_clients", start_and_arm)
    return armed


def _broken_kernel_out(monkeypatch, breaker):
    from nomad_tpu.scheduler import stack

    real = stack.KernelOut
    armed = _arm_with_the_clients(monkeypatch)

    def broken(*fields):
        out = real(*fields)
        return breaker(out) if armed else out

    broken._fields = real._fields
    monkeypatch.setattr(stack, "KernelOut", broken)


def _half_nodes_masked(monkeypatch):
    from nomad_tpu.scheduler import stack

    real = stack.XLAGenericStack._build_eval_tensors
    armed = _arm_with_the_clients(monkeypatch)

    def masked(self, tg, exclude):
        if armed:
            exclude = exclude.copy()
            exclude[self.cluster.n_real // 2:] = True
        return real(self, tg, exclude)

    monkeypatch.setattr(stack.XLAGenericStack, "_build_eval_tensors", masked)


def _state_unchanged(out):
    chosen = np.array(out.chosen)
    chosen[:] = chosen[0]
    return out._replace(chosen=chosen)


def _half_left_out(out):
    found = np.array(out.found)
    found[len(found) // 2:] = False
    return out._replace(found=found)


def _answer_altered(out):
    chosen = np.array(out.chosen)
    chosen[1::2] = np.maximum(chosen[1::2] - 1, 0)
    return out._replace(chosen=chosen)


def _failing(line: dict) -> list:
    return [k for k, c in line["compared"].items()
            if (c["value"] > c["limit"] if c["kind"] == "max"
                else c["value"] < c["limit"])]


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("breaker", [_state_unchanged, _half_left_out,
                                     _answer_altered])
def test_a_broken_timed_path_is_not_correct(monkeypatch, cell, breaker):
    _broken_kernel_out(monkeypatch, breaker)
    # a broken path may finish no job: neither wait lasts long
    monkeypatch.setattr(run_mod, "DRAIN_S", 5.0)
    monkeypatch.setattr(run_mod, "WARMUP_MAX_S", 15.0)
    line, err = drive(cell, 32)
    assert line["correct"] is False, err[-2000:]
    assert _failing(line), line["compared"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_half_of_the_nodes_masked_is_not_correct(monkeypatch, cell):
    _half_nodes_masked(monkeypatch)
    # twice the other tests' nodes, so that the half left has room for
    # every job, as it has at the cell's own size
    line, err = drive(cell, 33, size=["--nodes", "4000"])
    assert line["correct"] is False, err[-2000:]
    # the answers are feasible nodes with true scores: what fails it is
    # the look at every node of the cluster (a wave that nothing
    # explains is then reported as one launch, so more may read off)
    assert "chosen_short_of_best" in _failing(line), line["compared"]
    assert not {"overcommitted_nodes", "constraint_violations",
                "alloc_count_wrong", "jobs_never_done"} & set(_failing(line))
