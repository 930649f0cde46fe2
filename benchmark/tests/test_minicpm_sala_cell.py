"""`minicpm-sala.doc-mix-96k` at its rehearsal sizes, on the CPU: the cell runs
from its files through the harness as it is, both programs, and comes out
`correct`; its float8 control does not, nor does a program whose lightning
layers drop the state between chunks; the count of parameters is the cut's
and the published model's; every new metric's reader returns a number on a
traced rehearsal or `None` by its stated rule."""

import json

import run as bench_run

CELL = "minicpm-sala.doc-mix-96k"
NEW = ["lightning_chunk_roofline", "block_scores_roofline",
       "block_sparse_chunk_attention_roofline", "paged_decode_roofline.mix96k",
       "sparse_selected_share.mix96k"]
# accepted metrics whose readers find this cell's programs and events
SHARED = ["batch_decode_step_device_ms_p50", "batch_prefill_share", "prefill_chunk_roofline",
          "serve_step_mfu", "sched_occupancy", "sched_host_ms_p50", "queue_wait_ms_p50",
          "ttft_from_submit_ms_p50", "kv_block_occupancy", "setup_compile_s",
          "decode_copy_mb_per_step", "chunk_copy_mb_per_call"]
DEVICE = ["lightning_chunk_roofline", "block_scores_roofline",
          "block_sparse_chunk_attention_roofline", "paged_decode_roofline.mix96k"]


def rehearse(capsys, *extra):
    code = bench_run.main(["--workload", CELL, "--seed", "2147483659", "--seconds", "2",
                           "--rehearse", *extra])
    assert code == bench_run.REHEARSAL_EXIT
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cell_rehearses_and_its_control_is_not_correct(capsys):
    line = rehearse(capsys, "--trace", "1", "--control", "1")
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 4
    assert line["checks"]["job_tokens_committed"]["ok"]
    assert line["notes"]["chunk_calls"] > 8 and line["notes"]["steps"] > 8  # both programs
    assert line["notes"]["reference"]["tokens_compared"] > 20
    assert line["control_correct"] == {"float8_reference": False}
    control = line["control_checks"]["float8_reference"]["logit_gap_mean"]
    assert control["value"] > 3 * control["limit"]
    # the counters' metrics read; the device's read nothing without a chip
    read = set(line["notes"]["metrics_read"])
    assert {"sparse_selected_share.mix96k", "kv_block_occupancy", "sched_occupancy",
            "chunk_copy_mb_per_call", "decode_copy_mb_per_step"} <= read
    assert not set(DEVICE) & read


def test_a_state_dropped_between_chunks_is_not_correct(capsys, monkeypatch):
    """Every chunk's lightning layers start from a zero state, as though the
    slot's row were not handed over: the comparison sees it."""
    import jax
    import jax.numpy as jnp

    from elephas_tpu.ops import lightning

    chunk = lightning.lightning_chunk
    monkeypatch.setattr(lightning, "lightning_chunk",
                        lambda q, k, v, d, s0, *a, **kw: chunk(q, k, v, d, jnp.zeros_like(s0),
                                                               *a, **kw))
    jax.clear_caches()
    line = rehearse(capsys)
    assert line["correct"] is False and not line["checks"]["logit_gap_mean"]["ok"]


def test_the_configuration_is_the_published_row_but_for_the_cut():
    from lib.cells import Cell

    cell = Cell(CELL)
    config, family = cell.config, cell.module("models", cell.config["model"])
    assert cell.chips == 1 and config["model"] == "minicpm_sala" == family.REFERENCE
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 32}
    kinds = config["mixer_types"]
    assert (kinds.count("minicpm4"), kinds.count("lightning-attn")) == (8, 24)
    cfg = family.shape(config)
    assert cfg["mixer_types"] == ("minicpm4", "lightning-attn", "lightning-attn",
                                  "lightning-attn") and cfg["depth"] == 32
    widths = dict(hidden_size=4096, intermediate_size=16384, num_attention_heads=32,
                  num_key_value_heads=2, head_dim=128, lightning_nh=32, lightning_nkv=32,
                  lightning_head_dim=128, vocab_size=73448, scale_emb=12, scale_depth=1.4,
                  dim_model_base=256, rope_theta=10000, rms_norm_eps=1e-06)
    assert {k: config[k] for k in widths} == widths
    assert config["sparse_config"] == {"kernel_size": 32, "kernel_stride": 16,
                                       "block_size": 64, "topk": 64, "init_blocks": 1,
                                       "window_size": 2048, "dense_len": 8192}
    for name in ("sparse_config", "block_scores", "lightning_decay", "norms_and_gates",
                 "scalings", "weights"):
        assert len(config["assumed"][name]) > 40
    assert family.param_count(cfg) == 1_711_129_600
    assert family.param_count(cfg, {**config["published"], "mixer_types": kinds}) == \
        9_477_206_016
    assert family.kv_bytes_per_token(cfg) == 2 * 128 * 2 * 33 // 16 == 1_056
    assert family.state_bytes_per_slot(cfg) == 3 * 32 * 128 * 128 * 4
    s = config["serving"]
    tr = cell.traffic
    assert tr["driver"] == "closed_backlog"
    assert (tr["prompt_tokens"], tr["output_tokens"]) == (
        {"dist": "uniform", "min": 32768, "max": 98304},
        {"dist": "uniform", "min": 256, "max": 1024})
    assert tr["backlog"] == 16 == s["max_slots"] == tr["cycle"] and tr["pair_stride"] == 5
    assert tr["engine"]["queue_depth"] == 32 and tr["check"]["requests"] == 2
    assert tr["engine"]["prefill_chunks_per_step"] is None
    assert s["max_len"] == 98304 + 1024 and tr["engine"]["max_prompt_len"] == 98304
    assert s["kv_block_size"] == config["sparse_config"]["block_size"]  # a page is a block


def test_the_costs_count_the_selection_and_the_chunk_form():
    import jax
    import jax.numpy as jnp

    from lib.cells import Cell

    cell = Cell(CELL)
    family = cell.module("models", "minicpm_sala")
    small = family.shape(cell.sized(cell.config, True))
    drawn = family.params(2147483659, small, jnp.float32)
    assert family.param_count(small) == sum(
        leaf.size for leaf in jax.tree_util.tree_leaves(drawn))
    cfg = family.shape(cell.config)
    # past dense_len a query attends 64 pages of 64 columns, whatever its length;
    # what grows with the prompt is its compressed keys' scores alone
    near, far = (family.chunk_cost(cfg, start, 2048)[0] for start in (16384, 90112))
    scores = family.block_scores_cost(cfg, 90112, 2048)[0] - \
        family.block_scores_cost(cfg, 16384, 2048)[0]
    assert abs((far - near) - scores) < 1e-6 * far
    assert family.block_sparse_chunk_attention_cost(cfg, 16384, 2048, 2 * 2048 * 4096)[0] == \
        2 * 2048 * 4096 * 4.0 * 16 * 128
    _, nbytes = family.lightning_chunk_cost(cfg, 2048)
    assert nbytes == 3 * (2 * 4 * 2048 * 32 * 128 + 4 * 2 * 32 * 128 * 128)
    lanes = [40000, 90000]
    # a step reads every weight but the embedding's rows, and each lane's states twice
    assert family.decode_cost(cfg, lanes)[1] > family.weight_bytes(cfg) - 2 * 73448 * 4096 + \
        2 * 2 * family.state_bytes_per_slot(cfg)


def test_every_new_metric_is_a_file_with_a_reader_found_by_name():
    from lib.cells import Cell

    cell = Cell(CELL)
    listed = {m["name"] for m in cell.per_layer}
    assert set(NEW) | set(SHARED) <= listed
    for name in NEW:
        spec = cell.metric_file(name)
        reader = cell.module("readers", spec["reader"])
        assert callable(reader.read) and spec["moves"] == "serve_tokens_per_s"

    class NoTrace:  # a run without a device trace: each device reader reads nothing
        trace = None
        family = cell.module("models", "minicpm_sala")

    for name in DEVICE:
        spec = cell.metric_file(name)
        assert cell.module("readers", spec["reader"]).read(NoTrace(), **spec["args"]) is None
