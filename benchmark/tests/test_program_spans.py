"""The per-layer metrics that read the program's own spans and counters
(ISSUE 27) are found by name and read something in every cell that lists
them, at rehearsal size on the CPU, beside every metric the cells read
before."""

import json

import pytest

import run as bench_run

READ_BEFORE = {
    "gpt2-xl.chat-steady": ["sched_occupancy.chat", "tpot_ms_p50.chat", "ttft_ms_p50.chat"],
    "gpt2-xl.doc-batch": ["sched_occupancy", "ttft_from_submit_ms_p50"],
    "resnet18-cifar10.fit-sync-1w": ["epoch_s_p50", "fit_fixed_s"],
}
NEW = {
    "gpt2-xl.chat-steady": ["itl_ms_p99.chat", "kv_block_occupancy.chat", "queue_wait_ms_p50.chat",
                            "sched_host_ms_p50.chat", "setup_compile_s"],
    "gpt2-xl.doc-batch": ["kv_block_occupancy", "queue_wait_ms_p50", "sched_host_ms_p50",
                          "setup_compile_s"],
    "resnet18-cifar10.fit-sync-1w": ["epoch_host_s_p50", "fit_compile_s", "fit_upload_s",
                                     "setup_compile_s"],
}


@pytest.mark.parametrize("cell", sorted(NEW))
def test_a_rehearsal_reads_the_new_metrics_beside_the_old(cell, capsys):
    code = bench_run.main(["--workload", cell, "--seed", "2147483999", "--seconds", "3",
                           "--trace", "1", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert code == bench_run.REHEARSAL_EXIT and line["correct"] is True
    assert line["notes"]["metrics_read"] == sorted(READ_BEFORE[cell] + NEW[cell])


def test_readers_read_nothing_from_a_program_without_the_spans():
    """As on the parent commit: no new field, no new span, and no raise."""
    from types import SimpleNamespace

    from lib.cells import Cell

    cell = Cell("gpt2-xl.chat-steady")
    old = SimpleNamespace(
        window=SimpleNamespace(t_open=1.0, steps=[{"step_seconds": 0.1, "active_slots": 2}]),
        sent=[SimpleNamespace(result=SimpleNamespace(ttft_s=0.5)), SimpleNamespace(result=None)],
        spans=[("compile/serving_decode", 0.5, 0.5), ("train/epoch", 2.0, 3.0), ("fit", 1.5, 4.0)])
    bench = json.load(open(cell.find("BENCHMARK.json")))
    for metric in bench["per_layer"]:
        if metric["name"] not in {n for names in NEW.values() for n in names}:
            continue
        spec = cell.metric_file(metric["name"])
        assert cell.module("readers", spec["reader"]).read(old, **spec["args"]) is None, metric
