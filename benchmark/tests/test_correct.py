"""`correct` has been shown to fail: the controls (the reference in the next
precision down, float8 for bfloat16; the reference with a fault planted),
put in the program's place and held to its limits by the harness's own
comparison, come out not correct, and so does a run whose timed path is
broken underneath. Rehearsal sizes, on
the CPU; the harness's look for a chip is skipped (`--rehearse`), the rest
of a run is driven as it is."""

import json

import pytest

import run as bench_run


def run_cell(capsys, cell, *extra, exit_code=bench_run.REHEARSAL_EXIT):
    code = bench_run.main(["--workload", cell, "--seed", "7", "--seconds", "2",
                           "--rehearse", *extra])
    assert code == exit_code
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["gpt2-xl.chat-steady", "gpt2-xl.doc-batch"])
def test_serving_control_comes_out_not_correct(capsys, cell):
    line = run_cell(capsys, cell, "--control", "1")
    assert line["correct"] is True and line["checks"]["logit_gap_mean"]["ok"]
    assert line["control_correct"] == {"float8_reference": False}
    control = line["control_checks"]["float8_reference"]["logit_gap_mean"]
    assert control["ok"] is False and control["value"] > 3 * control["limit"]


def test_a_control_that_passes_fails_the_run(capsys, monkeypatch):
    """The control's verdict is the comparison's, not a constant: with the
    float8 rounding taken out of it, the control reads as the program does,
    comes out correct, and the run exits with an error."""
    from lib.cells import Cell

    real = Cell.module

    def module(self, kind, name):
        mod = real(self, kind, name)
        if kind == "references":
            mod.fp8 = mod.identity
        return mod

    monkeypatch.setattr(Cell, "module", module)
    line = run_cell(capsys, "gpt2-xl.doc-batch", "--control", "1",
                    exit_code=bench_run.CONTROL_PASSED_EXIT)
    assert line["correct"] is True
    assert line["control_correct"] == {"float8_reference": True}


@pytest.mark.parametrize("cell", ["gpt2-xl.chat-steady", "gpt2-xl.doc-batch"])
def test_a_token_altered_where_it_is_produced_is_not_correct(capsys, monkeypatch, cell):
    from elephas_tpu.serving import host_sync, scheduler

    real = host_sync.fetch_lanes
    calls = {"n": 0}

    def altered(tokens, lanes):
        out = real(tokens, lanes)
        calls["n"] += 1
        if calls["n"] % 5 == 0 and out:  # every fifth step, the first lane's token
            lane, tok = out[0]
            out[0] = (lane, (tok + 1) % 211)
        return out

    monkeypatch.setattr(scheduler.host_sync, "fetch_lanes", altered)
    line = run_cell(capsys, cell)
    assert calls["n"] > 5
    assert line["correct"] is False
    assert line["checks"]["logit_gap_mean"]["ok"] is False


SYNC = "resnet18-cifar10.fit-sync-1w"


NUMBERS = ("loss_gap", "median_grad_norm_gap", "update_norm_gap")


def test_training_control_and_planted_fault_come_out_not_correct(capsys):
    line = run_cell(capsys, SYNC, "--control", "1")
    assert line["correct"] is True
    assert all(line["checks"][name]["ok"] for name in NUMBERS)
    # the control and the fault each fail one of the cell's numbers at least
    assert line["control_correct"] == {"float8_reference": False, "half_batch": False}
    for control in line["control_checks"].values():
        assert sorted(control) == sorted(NUMBERS)
        assert all(control[n]["limit"] == line["checks"][n]["limit"] for n in NUMBERS)


def _break_step(monkeypatch, breaker):
    from elephas_tpu.engine import sync

    real = sync.make_train_step

    def broken(compiled, pmean_axis=None):
        return breaker(real(compiled, pmean_axis=pmean_axis))

    monkeypatch.setattr(sync, "make_train_step", broken)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(capsys, monkeypatch):
    def unchanged(step):
        def same(state, x, y):
            _, metrics = step(state, x, y)
            return state, metrics
        return same

    _break_step(monkeypatch, unchanged)
    line = run_cell(capsys, SYNC)
    assert line["correct"] is False
    assert line["checks"]["update_norm_gap"]["value"] > 0.9  # nothing moved


def test_half_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    def halved(step):
        def half(state, x, y):
            n = x.shape[0] // 2
            return step(state, x[:n], y[:n])
        return half

    _break_step(monkeypatch, halved)
    line = run_cell(capsys, SYNC)
    assert line["correct"] is False
