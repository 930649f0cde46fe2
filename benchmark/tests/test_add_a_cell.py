"""A configuration, a traffic mix, a per-layer metric and its reader are
added as new files found by name: no file that is there is edited, and no
entry of `BENCHMARK.json` but by appending. So is a language-model family:
`models/<family>.py`, its reference and its configuration, and nothing
under `lib/` or `drivers/` names a family."""

import json
import os
import re

import run as bench_run
from lib.cells import BENCH_DIR, CHECKOUT


def overlay(tmp_path, files, config, cell, per_layer):
    """Writes `files` (JSON by relative path, or text) under `tmp_path` with
    a `BENCHMARK.json` that appends one configuration, one cell of the
    `trickle` mix judged by `tpot_ms_p95`, and its per-layer metrics."""
    for rel, content in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(content if isinstance(content, str) else json.dumps(content))
    bench = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    bench["configs"].append({"name": config, "source": "test", "reduced": [],
                             "file": f"benchmark/configs/{config}.json", "why": "throw-away"})
    bench["workloads"].append({"name": cell, "config": config, "traffic": "trickle",
                               "chips": 1, "why": "throw-away"})
    next(m for m in bench["end_to_end"] if m["name"] == "tpot_ms_p95")["workloads"].append(cell)
    for name, unit in per_layer:
        bench["per_layer"].append({"name": name, "unit": unit, "better": "lower",
                                   "source": "program_span", "layer": "serving.scheduler",
                                   "moves": "tpot_ms_p95", "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))


def rehearse(capsys, tmp_path, cell, *extra):
    code = bench_run.main(["--workload", cell, "--seed", "2147483659", "--seconds", "2",
                           "--trace", "1", "--rehearse", "--overlay", str(tmp_path), *extra])
    assert code == bench_run.REHEARSAL_EXIT
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def trickle():
    chat = json.load(open(os.path.join(BENCH_DIR, "traffic", "chat-steady.json")))
    return {**chat, **chat["rehearsal"], "rehearsal": {}}


def test_a_throw_away_cell_runs_from_files_alone(tmp_path, capsys):
    before = open(os.path.join(CHECKOUT, "BENCHMARK.json")).read()
    xl = json.load(open(os.path.join(BENCH_DIR, "configs", "gpt2-xl.json")))
    overlay(tmp_path, {
        "benchmark/configs/gpt2-tiny.json": {**xl, **xl["rehearsal"], "rehearsal": {}},
        "benchmark/traffic/trickle.json": {**trickle(),
                                           "arrivals": {"process": "poisson", "rate_per_s": 3.0}},
        "benchmark/metrics/requests_seen.trickle.json": {"reader": "requests_seen", "args": {}},
        "benchmark/metrics/ttft_ms_p90.trickle.json": {
            "reader": "result_percentile", "args": {"series": "ttft_ms", "q": 90}},
        "benchmark/readers/requests_seen.py":
            "def read(run):\n    return float(len(run.sent)) or None\n",
    }, "gpt2-tiny", "gpt2-tiny.trickle", [("requests_seen.trickle", "ms"), ("ttft_ms_p90.trickle", "ms")])
    line = rehearse(capsys, tmp_path, "gpt2-tiny.trickle")
    assert line["correct"] is True and line["attempted"] > 0 and line["metrics"] == {}
    assert line["notes"]["metrics_read"] == ["requests_seen.trickle", "ttft_ms_p90.trickle"]
    # the real file is as it was
    assert open(os.path.join(CHECKOUT, "BENCHMARK.json")).read() == before


OTHER_FAMILY = '''
"""A throw-away family: `TransformerLM` under other key names, with weight
functions of its own."""
import jax
import jax.numpy as jnp

from lib.weights import seed_key

REFERENCE = "other_reference"


def shape(config):
    return {k: config[k] for k in ("depth", "width", "heads", "context", "vocab_size")}


def layers(cfg):
    return cfg["depth"]


def flax_module(cfg, dtype):
    from elephas_tpu.models import get_model

    return get_model("transformer_lm", dtype=dtype, vocab_size=cfg["vocab_size"],
                     d_model=cfg["width"], num_heads=cfg["heads"], num_layers=cfg["depth"],
                     max_seq_len=cfg["context"])


def _draw(key, shapes, dtype):
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(leaves))
    return treedef.unflatten([(0.05 * jax.random.normal(k, s)).astype(dtype)
                              for k, s in zip(keys, leaves)])


def block_at(seed, layer, cfg, dtype):
    d, h = cfg["width"], cfg["heads"]
    norm = {"scale": (d,), "bias": (d,)}
    tree = _draw(jax.random.fold_in(seed_key(seed), 100 + layer), {
        "LayerNorm_0": norm, "LayerNorm_1": norm,
        "SelfAttention_0": {"qkv": {"kernel": (d, 3, h, d // h), "bias": (3, h, d // h)},
                            "out": {"kernel": (d, d), "bias": (d,)}},
        "Dense_0": {"kernel": (d, 4 * d), "bias": (4 * d,)},
        "Dense_1": {"kernel": (4 * d, d), "bias": (d,)}}, dtype)
    for name in ("LayerNorm_0", "LayerNorm_1"):
        tree[name]["scale"] = tree[name]["scale"] + 1
    return tree


def top_at(seed, cfg, dtype):
    d, v = cfg["width"], cfg["vocab_size"]
    tree = _draw(seed_key(seed), {
        "tok_embed": {"embedding": (v, d)}, "pos_embed": (cfg["context"], d),
        "LayerNorm_0": {"scale": (d,), "bias": (d,)},
        "lm_head": {"kernel": (d, v), "bias": (v,)}}, dtype)
    tree["LayerNorm_0"]["scale"] = tree["LayerNorm_0"]["scale"] + 1
    return tree


def params(seed, cfg, dtype):
    out = dict(top_at(seed, cfg, dtype))
    for layer in range(cfg["depth"]):
        out[f"Block_{layer}"] = block_at(seed, layer, cfg, dtype)
    return out


def chunk_cost(cfg, start, valid):
    return 24.0 * cfg["depth"] * cfg["width"] ** 2 * valid, 2.0 * cfg["width"] * (start + valid)


def decode_cost(cfg, lengths):
    return chunk_cost(cfg, sum(lengths), len(lengths))
'''


def test_a_throw_away_family_runs_from_files_alone(tmp_path, capsys):
    overlay(tmp_path, {
        "benchmark/configs/other-tiny.json": {
            "model": "other", "depth": 2, "width": 32, "heads": 4, "context": 64,
            "vocab_size": 211, "dtype": "float32",
            "serving": {"max_slots": 4, "max_len": 64, "kv_block_size": 8, "prefill_chunk": 8}},
        "benchmark/traffic/trickle.json": trickle(),
        "benchmark/metrics/other_decode_roofline.json": {
            "reader": "serving_program_roofline",
            "args": {"program": "jit__paged_decode_impl", "kind": "decode"}},
        "benchmark/metrics/ttft_ms_p90.trickle.json": {
            "reader": "result_percentile", "args": {"series": "ttft_ms", "q": 90}},
        "benchmark/models/other.py": OTHER_FAMILY,
        "benchmark/references/other_reference.py":
            open(os.path.join(BENCH_DIR, "references", "gpt2.py")).read(),
    }, "other-tiny", "other-tiny.trickle", [("other_decode_roofline", "%"), ("ttft_ms_p90.trickle", "ms")])
    line = rehearse(capsys, tmp_path, "other-tiny.trickle", "--control", "1")
    assert line["correct"] is True and line["attempted"] > 0
    assert line["checks"]["logit_gap_mean"]["ok"] and line["notes"]["reference"]["tokens_compared"] > 0
    assert line["control_correct"] == {"float8_reference": False}
    # the roofline reads nothing without a device trace, and does not raise
    assert line["notes"]["metrics_read"] == ["ttft_ms_p90.trickle"]


def test_the_generic_roofline_reads_the_familys_costs():
    """The reader over a made-up trace and the throw-away family's costs."""
    from types import SimpleNamespace

    from lib.cells import Cell
    from lib.xplane import MARK_CLOSE, MARK_OPEN

    family = SimpleNamespace()
    exec(OTHER_FAMILY, family.__dict__)
    cfg = {"depth": 2, "width": 32}
    steps = [{"t": 1.0 + i, "step_seconds": 0.5, "lane_lengths": [3 + i, 9]} for i in range(4)]
    run = SimpleNamespace(
        family=family, cfg=cfg, extra={}, peak={"kind": "TPU v5 lite"},
        sink=SimpleNamespace(steps=steps), chunk_spans=[(2.2, 8, 8), (9.0, 0, 8)],
        capture=SimpleNamespace(marks={MARK_OPEN: 1.0, MARK_CLOSE: 4.0}),
        trace=SimpleNamespace(program_seconds=lambda p: 2e-9, calls=lambda p: [1e-9, 1e-9]))
    reader = Cell("gpt2-xl.chat-steady").module("readers", "serving_program_roofline")
    def least(flops, nbytes):
        return max(flops / 197e12, nbytes / 819e9)

    # decode steps begun in [1, 4]: stamped 1 .. 4 less 0.5 at their ends -> the last three
    needed = sum(least(24.0 * 2 * 32 ** 2 * 2, 2.0 * 32 * (3 + i + 9 + 2)) for i in (1, 2, 3))
    assert reader.read(run, "jit__paged_decode_impl", "decode") == \
        100.0 * needed * (2 / 3) / 2e-9
    assert run.extra["needed"]["jit__paged_decode_impl"]["calls"] == 3
    # the one chunk dispatched inside the marks
    assert reader.read(run, "jit__chunk_prefill_impl", "chunk") == \
        100.0 * least(24.0 * 2 * 32 ** 2 * 8, 2.0 * 32 * 16) * 2 / 2e-9


def test_no_file_of_the_harness_names_a_family():
    named = []
    for sub in ("lib", "drivers"):
        for root, _, names in os.walk(os.path.join(BENCH_DIR, sub)):
            for name in names:
                if name.endswith(".py") and re.search(
                        r"gpt2|transformer_lm", open(os.path.join(root, name)).read()):
                    named.append(os.path.join(sub, name))
    assert named == []
    source = "".join(open(os.path.join(root, name)).read()
                     for root, _, names in os.walk(BENCH_DIR) for name in names
                     if name.endswith(".py") and "tests" not in root)
    assert not re.search(r"(chunk_prefill_fn|decode_fn)\s*=[^=]", source)
    assert "ChunkCounter" not in source and "DecodeCounter" not in source
