"""`granite-4.0-h-small.gen-batch-2k` at its rehearsal sizes, on the CPU: the
cell runs from its files through the harness as it is, both programs, and
comes out `correct`; its float8 control does not, nor does a program whose
mixers drop the state between chunks;
the configuration is the published row but for the cut; the costs count
what the cut holds; every new metric's reader returns a number on a traced
rehearsal or `None` by its stated rule."""

import json

import run as bench_run

CELL = "granite-4.0-h-small.gen-batch-2k"
NEW = ["ssd_chunk_roofline", "ssd_step_roofline.gen2k", "paged_decode_roofline.gen2k",
       "routed_decode_roofline.gen2k", "state_slot_occupancy.gen2k",
       "ssd_state_gb_per_step.gen2k"]
# accepted metrics whose readers find this cell's programs and events
SHARED = ["batch_decode_step_device_ms_p50", "batch_prefill_share", "prefill_chunk_roofline",
          "serve_step_mfu", "sched_occupancy", "sched_host_ms_p50", "queue_wait_ms_p50",
          "ttft_from_submit_ms_p50", "kv_block_occupancy", "setup_compile_s",
          "decode_copy_mb_per_step", "chunk_copy_mb_per_call", "grouped_matmul_roofline"]
DEVICE = ["ssd_chunk_roofline", "ssd_step_roofline.gen2k", "paged_decode_roofline.gen2k",
          "routed_decode_roofline.gen2k"]


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
    assert {"state_slot_occupancy.gen2k", "ssd_state_gb_per_step.gen2k", "kv_block_occupancy",
            "sched_occupancy", "chunk_copy_mb_per_call", "decode_copy_mb_per_step"} <= read
    assert not set(DEVICE) & read


def _dropped_between_chunks(monkeypatch):
    import jax.numpy as jnp

    from elephas_tpu.ops import ssd

    chunk = ssd.ssd_chunk
    monkeypatch.setattr(ssd, "ssd_chunk", lambda x, d, a, b, c, s0, *r, **kw: chunk(
        x, d, a, b, c, jnp.zeros_like(s0), *r, **kw))


def test_a_state_dropped_between_chunks_is_not_correct(capsys, monkeypatch):
    """Every chunk's mixers start from a zero state, as though the slot's row
    were not handed over: the comparison sees it. (A state rounded to
    bfloat16 between steps moves the logits by less than it takes to change
    a served token in a rehearsal's 8-16 steps: `tests/test_granite_hybrid.py`
    compares logits, and the chip's runs read it in the traffic file.)"""
    import jax

    _dropped_between_chunks(monkeypatch)
    jax.clear_caches()
    line = rehearse(capsys)
    assert line["correct"] is False and not line["checks"]["logit_gap_mean"]["ok"]


def test_the_configuration_is_the_published_row_but_for_the_cut():
    from lib.cells import Cell

    cell = Cell(CELL)
    config, family = cell.config, cell.module("models", cell.config["model"])
    assert cell.chips == 1 and config["model"] == "granite_hybrid" == family.REFERENCE
    assert config["reduced"] == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 40, "num_local_experts": 72,
                                   "vocab_size": 100352}
    assert (config["num_hidden_layers"], config["num_local_experts"], config["vocab_size"],
            config["router_experts"], config["experts_first"]) == (10, 18, 25088, 72, 0)
    kinds = config["layer_types"]
    assert len(kinds) == 40 and [i for i, k in enumerate(kinds) if k == "attention"] == \
        [5, 15, 25, 35]
    cfg = family.shape(config)
    assert cfg["layer_types"] == tuple(["mamba"] * 5 + ["attention"] + ["mamba"] * 4)
    widths = dict(hidden_size=4096, intermediate_size=768, shared_intermediate_size=1536,
                  num_attention_heads=32, num_key_value_heads=8, mamba_n_heads=128,
                  mamba_d_head=64, mamba_d_state=128, mamba_d_conv=4, mamba_expand=2,
                  mamba_n_groups=1, mamba_chunk_size=256, num_experts_per_tok=10,
                  embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=16,
                  attention_multiplier=0.0078125, rms_norm_eps=1e-05,
                  max_position_embeddings=131072)
    assert {k: config[k] for k in widths} == widths
    assert config["position_embedding_type"] == "nope" and config["tie_word_embeddings"]
    for name in ("weights", "deployment_share", "max_slots", "prefill_chunk", "state"):
        assert len(config["assumed"][name]) > 40
    assert "v5e-16" in config["deployment"]
    s, tr = config["serving"], cell.traffic
    assert (s["max_slots"], s["kv_block_size"], s["prefill_chunk"]) == (64, 64, 512)
    assert s["max_len"] == 2048 + 1024 and tr["engine"]["max_prompt_len"] == 2048
    assert tr["driver"] == "closed_backlog" and tr["backlog"] == 128
    assert tr["engine"]["prefill_chunks_per_step"] is None
    assert (tr["prompt_tokens"], tr["output_tokens"]) == (
        {"dist": "uniform", "min": 256, "max": 2048},
        {"dist": "uniform", "min": 256, "max": 1024})


def test_the_costs_count_what_the_cut_holds():
    import jax
    import jax.numpy as jnp

    from lib.cells import Cell

    cell = Cell(CELL)
    family = cell.module("models", "granite_hybrid")
    small = family.shape(cell.sized(cell.config, True))
    drawn = family.params(2147483659, small, jnp.float32)
    assert family.param_count(small) == sum(
        leaf.size for leaf in jax.tree_util.tree_leaves(drawn))
    cfg = family.shape(cell.config)
    assert family.param_count(cfg) == 2_955_758_208
    assert family.param_count(cfg, {**cell.config["published"],
                                    "layer_types": cell.config["layer_types"]}) == \
        32_207_337_984
    assert family.kv_bytes_per_token(cfg) == 2 * 8 * 128 * 2
    assert family.ssd_state_bytes(cfg) == 128 * 64 * 128 * 4 == 4 << 20
    assert family.state_bytes_per_slot(cfg) == 9 * (4 * 2 ** 20 + 3 * 8448 * 2)
    # a step of 64 lanes touches every held expert in expectation, and reads
    # the state twice a lane: 11.2 GB, the cut's reckoning
    _, nbytes = family.decode_cost(cfg, [1500] * 64)
    assert 11.0e9 < nbytes < 11.4e9
    assert family.decode_cost(cfg, [1500] * 64, touched=0)[1] < nbytes - 2 * 170e6 * 9
    _, state = family.ssd_step_cost(cfg, [1500] * 64)
    assert 0.43 < state / nbytes < 0.45
    flops, chunk_bytes = family.ssd_chunk_cost(cfg, 512)
    assert chunk_bytes == 9 * (4 * 512 * (2 * 8192 + 2 * 128 + 2 * 128) + 2 * (4 << 20))
    assert flops == 9 * 512 * family.ssd_token_flops(cfg)


def test_every_new_metric_is_a_file_with_a_reader_found_by_name():
    from lib.cells import Cell

    cell = Cell(CELL)
    listed = {m["name"] for m in cell.per_layer}
    assert set(NEW) | set(SHARED) <= listed
    for name in NEW:
        spec = cell.metric_file(name)
        reader = cell.module("readers", spec["reader"])
        assert callable(reader.read) and spec["moves"] == "serve_tokens_per_s"
        if spec.get("args", {}).get("cost"):
            assert callable(getattr(cell.module("models", "granite_hybrid"),
                                    spec["args"]["cost"]))

    class NoTrace:  # a run without a device trace: each device reader reads nothing
        trace = None
        family = cell.module("models", "granite_hybrid")

    for name in DEVICE:
        spec = cell.metric_file(name)
        assert cell.module("readers", spec["reader"]).read(NoTrace(), **spec["args"]) is None
