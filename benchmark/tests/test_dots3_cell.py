"""`dots3-note-prev.doc-mix-32k` at its rehearsal sizes, on the CPU: the cell
runs from its files through the harness as it is, both programs, and comes
out `correct`; its float8 control does not, nor does a program whose
selection is dropped (every live column attended); the count of parameters
is the cut's and the published model's; every new metric's reader returns a
number on a traced rehearsal or `None` by its stated rule."""

import json

import run as bench_run

CELL = "dots3-note-prev.doc-mix-32k"
NEW = ["index_scores_roofline", "sparse_chunk_attention_roofline",
       "sparse_decode_roofline.mix32k", "select_share.mix32k",
       "sparse_selected_share.mix32k", "routed_decode_roofline.mix32k"]
# accepted metrics whose readers find this cell's counters and programs
SHARED = ["expert_held_share.doc16k", "expert_load_imbalance.doc16k",
          "latent_chunk_attention_roofline", "grouped_matmul_roofline"]


def rehearse(capsys, *extra):
    code = bench_run.main(["--workload", CELL, "--seed", "2147483659", "--seconds", "2",
                           "--rehearse", *extra])
    assert code == bench_run.REHEARSAL_EXIT
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cell_rehearses_and_its_control_is_not_correct(capsys):
    line = rehearse(capsys, "--trace", "1", "--control", "1")
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 8
    assert line["checks"]["job_tokens_committed"]["ok"]
    assert line["notes"]["chunk_calls"] > 8 and line["notes"]["steps"] > 8  # both programs
    assert line["notes"]["reference"]["tokens_compared"] > 20
    assert line["control_correct"] == {"float8_reference": False}
    control = line["control_checks"]["float8_reference"]["logit_gap_mean"]
    assert control["value"] > 3 * control["limit"]
    # the counters' metrics read; the device's read nothing without a chip
    read = set(line["notes"]["metrics_read"])
    assert {"sparse_selected_share.mix32k", "expert_held_share.doc16k",
            "expert_load_imbalance.doc16k"} <= read
    assert not {"index_scores_roofline", "sparse_chunk_attention_roofline",
                "sparse_decode_roofline.mix32k", "select_share.mix32k",
                "routed_decode_roofline.mix32k", "latent_chunk_attention_roofline",
                "grouped_matmul_roofline"} & read


def test_a_dropped_selection_is_not_correct(capsys, monkeypatch):
    """Every live column attended, as the first generation does: the
    comparison sees it."""
    import jax.numpy as jnp

    from elephas_tpu.ops import sparse_index

    def every_live_column(scores, last, k, body):
        cols = jnp.arange(scores.shape[1])[None]
        return (cols <= last[:, None]).astype(jnp.int8)

    monkeypatch.setattr(sparse_index, "select_columns", every_live_column)
    line = rehearse(capsys)
    assert line["correct"] is False and not line["checks"]["logit_gap_mean"]["ok"]


def test_the_configuration_is_the_published_row_but_for_the_cut():
    from lib.cells import Cell

    cell = Cell(CELL)
    config, family = cell.config, cell.module("models", cell.config["model"])
    assert cell.chips == 1 and config["model"] == "dots3" == family.REFERENCE
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    published = config["published"]
    assert {k: published[k] for k in config["reduced"]} == {
        "num_hidden_layers": 46, "n_routed_experts": 256, "vocab_size": 152064}
    kinds = published["layer_types"]
    assert (kinds.count("full_attention"), kinds.count("sliding_attention")) == (13, 33)
    # the file keeps the published list whole; the family cuts it to the depth
    assert config["layer_types"] == kinds
    assert list(family.shape(config)["layer_types"]) == kinds[:6] == [
        "full_attention", "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert "8 chips share each layer" in config["deployment"] and "stage 0" in config["deployment"]
    widths = dict(
        hidden_size=5120, intermediate_size=13824, moe_intermediate_size=1536,
        num_attention_heads=128, q_lora_rank=1024, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, swa_num_attention_heads=64,
        swa_q_lora_rank=1024, swa_kv_lora_rank=1024, swa_qk_nope_head_dim=192,
        swa_qk_rope_head_dim=64, swa_v_head_dim=128, index_n_heads=64, index_head_dim=128,
        index_topk=2048, sliding_window_size=513, num_experts_per_tok=8,
        n_shared_experts=1, router_experts=256, routed_scaling_factor=1,
        rope_theta=80000000, swa_rope_theta=50000, rms_norm_eps=1e-05)
    assert {k: config[k] for k in widths} == widths
    assert (config["n_routed_experts"], config["vocab_size"], config["num_hidden_layers"]) \
        == (32, 19008, 6)
    for name in ("apply_mla_qkv_lora_rescale", "attention_gate_type", "indexer",
                 "sliding_window_size", "router"):
        assert len(config["assumed"][name]) > 40
    cfg = family.shape(config)
    assert family.param_count(cfg) == 5_011_092_608
    assert family.param_count(cfg, published) == 279_551_724_928
    assert family.kv_bytes_per_token(cfg) == 3 * (576 + 128) * 2 == 4_224
    s = config["serving"]
    assert family.window_bytes_per_slot(cfg, s["prefill_chunk"], s["kv_block_size"]) == \
        3 * 2_560 * 1_088 * 2
    tr = cell.traffic
    assert tr["driver"] == "closed_backlog"
    assert (tr["prompt_tokens"], tr["output_tokens"]) == (
        {"dist": "uniform", "min": 8192, "max": 32768},
        {"dist": "uniform", "min": 128, "max": 512})
    assert tr["backlog"] == 16 == s["max_slots"] == tr["cycle"] and tr["pair_stride"] == 5
    assert tr["engine"]["queue_depth"] == 32 and tr["check"]["requests"] == 2
    assert tr["engine"]["prefill_chunks_per_step"] is None  # a prompt's chunks at admission
    assert s["max_len"] == 32768 + 512 and tr["engine"]["max_prompt_len"] == 32768


def test_the_drawn_weights_are_what_is_counted_and_the_costs_count_the_selection():
    import jax
    import jax.numpy as jnp

    from lib.cells import Cell

    cell = Cell(CELL)
    family = cell.module("models", "dots3")
    small = family.shape(cell.sized(cell.config, True))
    drawn = family.params(2147483659, small, jnp.float32)
    assert family.param_count(small) == sum(
        leaf.size for leaf in jax.tree_util.tree_leaves(drawn))
    cfg = family.shape(cell.config)
    # past column 2,048 a chunk's attention on the full layers stops growing
    # with the prompt: only the index scores do
    near, far = (family.chunk_cost(cfg, start, 2048)[0] for start in (8192, 30720))
    index = family.index_chunk_cost(cfg, 30720, 2048)[0] - family.index_chunk_cost(
        cfg, 8192, 2048)[0]
    assert abs((far - near) - index) < 1e-6 * far
    assert family.sparse_chunk_attention_cost(cfg, 8192, 2048, 3 * 2048 * 2048)[0] == \
        3 * 2048 * 2048 * 2.0 * 128 * (128 + 64 + 128)
    # a window layer's attention never passes 513 columns a query
    assert family.mla_chunk_attention_cost(cfg, 30720, 2048)[0] == \
        3 * 2048 * 513 * 2.0 * 64 * (192 + 64 + 128)
    lanes = [9000, 20000]
    flops, nbytes = family.sparse_decode_cost(cfg, lanes)
    assert nbytes == 3 * 2 * sum(128 * (c + 1) + 576 * 2048 for c in lanes)
    assert family.decode_cost(cfg, lanes)[1] > nbytes


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
        family = cell.module("models", "dots3")

    for name in ("index_scores_roofline", "sparse_chunk_attention_roofline",
                 "sparse_decode_roofline.mix32k", "select_share.mix32k",
                 "routed_decode_roofline.mix32k"):
        spec = cell.metric_file(name)
        assert cell.module("readers", spec["reader"]).read(NoTrace(), **spec["args"]) is None


def test_the_whole_decode_step_is_read_past_a_first_step_that_harvested_nothing():
    """The traced part is the whole job, so it holds the step that launched
    the first decode and had none to harvest: its event lacks the routed
    layer's counters. The accepted reader then reads nothing; this cell's
    leaves that step out and reads the others."""
    from types import SimpleNamespace

    from lib.cells import Cell
    from lib.xplane import MARK_CLOSE, MARK_OPEN

    cell = Cell(CELL)
    family = cell.module("models", "dots3")
    steps = [{"t": 1.0 + i, "step_seconds": 0.5, "lane_lengths": [9000, 20000],
              "moe_experts_touched": None if i == 0 else 20.0} for i in range(4)]
    run = SimpleNamespace(
        family=family, cfg=family.shape(cell.config), extra={}, peak={"kind": "TPU v5 lite"},
        sink=SimpleNamespace(steps=steps),
        capture=SimpleNamespace(marks={MARK_OPEN: 0.0, MARK_CLOSE: 10.0}),
        trace=SimpleNamespace(program_seconds=lambda program: 4 * 0.006,
                              calls=lambda program: [0.006] * 4))
    spec = cell.metric_file("routed_decode_roofline.mix32k")
    got = cell.module("readers", spec["reader"]).read(run, **spec["args"])
    flops, nbytes = family.decode_cost(run.cfg, [9000, 20000], touched=20.0)
    assert abs(got - 100.0 * (nbytes / 819e9) / 0.006) < 1e-6 * got and 40 < got < 100
    assert run.extra["needed"]["jit__paged_decode_impl/touched"]["calls"] == 3
    accepted = cell.metric_file("routed_decode_roofline.doc16k")
    assert cell.module("readers", accepted["reader"]).read(run, **accepted["args"]) is None
