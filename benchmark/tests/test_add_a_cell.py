"""A configuration, a traffic mix, a per-layer metric and its reader are
added as new files found by name: no file that is there is edited, and no
entry of `BENCHMARK.json` but by appending."""

import json
import os

import run as bench_run
from lib.cells import BENCH_DIR, CHECKOUT


def test_a_throw_away_cell_runs_from_files_alone(tmp_path, capsys):
    bench = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    before = json.dumps(bench["workloads"])
    xl = json.load(open(os.path.join(BENCH_DIR, "configs", "gpt2-xl.json")))
    tiny = {**xl, **{k: v for k, v in xl["rehearsal"].items()}, "rehearsal": {}}
    chat = json.load(open(os.path.join(BENCH_DIR, "traffic", "chat-steady.json")))
    trickle = {**chat, **chat["rehearsal"], "rehearsal": {},
               "arrivals": {"process": "poisson", "rate_per_s": 3.0}}
    files = {
        "benchmark/configs/gpt2-tiny.json": tiny,
        "benchmark/traffic/trickle.json": trickle,
        "benchmark/metrics/requests_seen.trickle.json": {"reader": "requests_seen", "args": {}},
        "benchmark/metrics/ttft_ms_p90.trickle.json": {
            "reader": "result_percentile", "args": {"series": "ttft_ms", "q": 90}},
    }
    for rel, content in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(content))
    reader = tmp_path / "benchmark/readers/requests_seen.py"
    reader.parent.mkdir(parents=True, exist_ok=True)
    reader.write_text("def read(run):\n    return float(len(run.sent)) or None\n")
    cell = "gpt2-tiny.trickle"
    bench["configs"].append({"name": "gpt2-tiny", "source": "test", "reduced": [],
                             "file": "benchmark/configs/gpt2-tiny.json", "why": "throw-away"})
    bench["workloads"].append({"name": cell, "config": "gpt2-tiny", "traffic": "trickle",
                               "chips": 1, "why": "throw-away"})
    next(m for m in bench["end_to_end"] if m["name"] == "tpot_ms_p95")["workloads"].append(cell)
    for name in ("requests_seen.trickle", "ttft_ms_p90.trickle"):
        bench["per_layer"].append({"name": name, "unit": "ms", "better": "lower",
                                   "source": "program_span", "layer": "serving.scheduler",
                                   "moves": "tpot_ms_p95", "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    code = bench_run.main(["--workload", cell, "--seed", "3", "--seconds", "2", "--trace", "1",
                           "--rehearse", "--overlay", str(tmp_path)])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert code == bench_run.REHEARSAL_EXIT
    assert line["correct"] is True and line["attempted"] > 0 and line["metrics"] == {}
    assert line["notes"]["metrics_read"] == ["requests_seen.trickle", "ttft_ms_p90.trickle"]
    # the real file's cells are as they were
    assert json.dumps(json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))["workloads"]) == before
