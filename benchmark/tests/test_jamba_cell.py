"""`jamba2-3b.doc-batch-4k` at its rehearsal sizes, on the CPU: the cell
runs from its files through the harness as it is, comes out `correct`, its
float8 control does not, and a chunk program that forgets the state it was
handed is seen by the same comparison."""

import json

import run as bench_run

CELL = "jamba2-3b.doc-batch-4k"


def rehearse(capsys, *extra):
    code = bench_run.main(["--workload", CELL, "--seed", "2147483659", "--seconds", "2",
                           "--rehearse", *extra])
    assert code == bench_run.REHEARSAL_EXIT
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cell_rehearses_and_its_control_is_not_correct(capsys):
    line = rehearse(capsys, "--trace", "1", "--control", "1")
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 8
    assert line["checks"]["job_tokens_committed"]["ok"]
    assert line["notes"]["reference"]["tokens_compared"] > 50
    assert line["control_correct"] == {"float8_reference": False}
    control = line["control_checks"]["float8_reference"]["logit_gap_mean"]
    assert control["value"] > 3 * control["limit"]
    # the counters' metrics read; the device's read nothing without a chip
    assert "state_slot_occupancy.doc4k" in line["notes"]["metrics_read"]
    assert "selective_scan_roofline" not in line["notes"]["metrics_read"]


def test_a_chunk_program_that_forgets_its_state_is_not_correct(capsys, monkeypatch):
    import jax
    import jax.numpy as jnp

    from elephas_tpu.models.decode_cache import STATE, leaf_kind
    from elephas_tpu.serving import InferenceEngine

    whole = InferenceEngine._chunk_prefill_impl

    def amnesiac(self, params, cache, *rest):
        cache = jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.zeros_like(leaf) if leaf_kind(path) == STATE else leaf,
            cache)
        return whole(self, params, cache, *rest)

    monkeypatch.setattr(InferenceEngine, "_chunk_prefill_impl", amnesiac)
    line = rehearse(capsys)
    assert line["correct"] is False and not line["checks"]["logit_gap_mean"]["ok"]
