"""`deepseek-v2.doc-batch-16k` at its rehearsal sizes, on the CPU: the cell
runs from its files through the harness as it is, comes out `correct`, its
float8 control does not, and a chunk program that rotates a chunk's tokens
at the chunk's offset is seen by the same comparison."""

import json

import run as bench_run

CELL = "deepseek-v2.doc-batch-16k"


def rehearse(capsys, *extra):
    code = bench_run.main(["--workload", CELL, "--seed", "2147483659", "--seconds", "2",
                           "--rehearse", *extra])
    assert code == bench_run.REHEARSAL_EXIT
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cell_rehearses_and_its_control_is_not_correct(capsys):
    line = rehearse(capsys, "--trace", "1", "--control", "1")
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 8
    assert line["checks"]["job_tokens_committed"]["ok"]
    assert line["notes"]["reference"]["tokens_compared"] > 30
    assert line["control_correct"] == {"float8_reference": False}
    control = line["control_checks"]["float8_reference"]["logit_gap_mean"]
    assert control["value"] > 3 * control["limit"]
    # the counters' metrics read; the device's read nothing without a chip
    read = line["notes"]["metrics_read"]
    assert {"expert_held_share.doc16k", "expert_load_imbalance.doc16k"} <= set(read)
    assert not {"routed_decode_roofline.doc16k", "latent_chunk_attention_roofline",
                "grouped_matmul_roofline"} & set(read)


def test_a_chunk_rotated_at_its_offset_is_not_correct(capsys, monkeypatch):
    import jax.numpy as jnp

    from elephas_tpu.models import latent_moe

    rotate = latent_moe.rotate

    def at_the_offset(x, positions, rope):
        if positions.shape[0] == 1 and positions.shape[1] > 1:  # a chunk, not a step
            positions = jnp.broadcast_to(positions[:, :1], positions.shape)
        return rotate(x, positions, rope)

    monkeypatch.setattr(latent_moe, "rotate", at_the_offset)
    line = rehearse(capsys)
    assert line["correct"] is False and not line["checks"]["logit_gap_mean"]["ok"]


def test_the_configuration_is_the_published_row_but_for_the_cut():
    from lib.cells import Cell

    cell = Cell(CELL)
    config, family = cell.config, cell.module("models", cell.config["model"])
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 60, "n_routed_experts": 160,
                                   "vocab_size": 102400}
    widths = dict(hidden_size=5120, num_attention_heads=128, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, q_lora_rank=1536, kv_lora_rank=512,
                  intermediate_size=12288, moe_intermediate_size=1536, n_shared_experts=2,
                  router_experts=160, n_group=8, topk_group=3, num_experts_per_tok=6,
                  routed_scaling_factor=16)
    assert {k: config[k] for k in widths} == widths and config["rope_scaling"]["factor"] == 40
    cfg = family.shape(config)
    assert family.param_count(cfg) == 5_163_975_680
    assert family.param_count(cfg, config["published"]) == 235_741_434_880
    assert family.kv_bytes_per_token(cfg) == 5_760
    tr = cell.traffic
    assert (tr["prompt_tokens"], tr["output_tokens"]) == (
        {"dist": "uniform", "min": 4096, "max": 16384},
        {"dist": "uniform", "min": 32, "max": 128})
    assert tr["backlog"] == 32 == 2 * config["serving"]["max_slots"]
    assert max(1, round(40 / tr["cycle_s"])) * tr["cycle"] >= 32
