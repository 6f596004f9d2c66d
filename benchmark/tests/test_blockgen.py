"""The block-diffusion cells' driver and comparison, on the CPU at a toy
size: a sound run is correct, the fp8 control fails a limit, a store pass
that is skipped fails one, and what the new readers are given by a program
without the new spans reads as nothing."""
import json
import os

import numpy as np
import pytest

import run as bench_run
from harness import blockdiff_costs, blockgen

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_blockgen_manifest.json")


def run_cell(capsys, seed=7, trace=0, **measure):
    rc = bench_run.main(["--workload", "toy-blockgen", "--seed", str(seed),
                         "--seconds", "1.5", "--trace", str(trace)],
                        require_chip=False,
                        hooks={"manifest": TOY, "measure": measure})
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct_and_the_fp8_control_is_not(capsys):
    result = run_cell(capsys, seed=3000000011, control=True)
    assert result["correct"] is True and result["failed"] == 0
    got = result["compared"]
    for name in ("logit_gap_max", "logit_gap_mean", "confidence_gap_mean",
                 "unstated_storage_bytes"):
        assert got[name]["value"] <= got[name]["limit"]
    assert got["tokens_checked"]["value"] >= 16
    # the control: the same replay in fp8 chooses tokens and positions that
    # the float32 reference puts lower, past at least one limit
    assert got["control_fp8_fails"]["value"] == 1.0
    assert got["control_fp8_logit_gap_max"]["value"] \
        > got["logit_gap_max"]["limit"]
    assert result["metrics"]["batch_tpot_p50_ms"]["value"] > 0
    assert set(result["per_layer_host_clock"]) == {
        "batch_occupancy.sdar", "serve_tokens_per_s.sdar"}


def test_a_skipped_store_pass_is_not_correct(capsys, monkeypatch):
    """The index advances but the block's clean K/V is never written: what
    stays is the K/V of the last denoising step's noisy input.  Emulated
    where the step is given its tokens: a store row is handed the tokens
    its slot's last denoising step was handed."""
    from paddle_tpu.inference.block_diffusion import BlockDiffusionPool

    sound = BlockDiffusionPool._control
    before = {}

    def control(self):
        ctl = sound(self)
        bl = self._B
        for slot in range(self.slots):
            if ctl[slot, 2 * bl + 1] and slot in before:
                ctl[slot, :bl] = before[slot]
            elif ctl[slot, 2 * bl + 2]:
                before[slot] = ctl[slot, :bl].copy()
        return ctl

    monkeypatch.setattr(BlockDiffusionPool, "_control", control)
    result = run_cell(capsys, seed=11)
    assert result["correct"] is False
    got = result["compared"]
    assert got["logit_gap_max"]["value"] > got["logit_gap_max"]["limit"]


def test_traced_run_reads_the_spans_metrics(capsys):
    result = run_cell(capsys, seed=5, trace=1)
    assert result["correct"] is True
    m = result["metrics"]
    # 4 tokens a block over 2 denoising steps and a store: 1.33 and 33 %,
    # less where a request's last block is not stored
    assert 1.2 < m["tokens_per_forward.sdar"]["value"] < 2.0
    assert 15.0 < m["store_forward_share.sdar"]["value"] < 34.0
    # no device plane on the CPU: the device metrics are left out
    assert "block_step_roofline.sdar" not in m
    assert "moe_experts_roofline.sdar" not in m


def test_prompts_never_hold_the_mask_id():
    tr = dict(json.load(open(os.path.join(
        os.path.dirname(HERE), "traffic", "toy-blockgen.json"))),
        avoid_token_id=3)
    plain = blockgen.traffic_mod.Schedule(tr, 9).token_ids(0, 4000, 7)
    ids = blockgen.Schedule(tr, 9).token_ids(0, 4000, 8)
    assert 3 not in ids and set(ids) == {0, 1, 2, 4, 5, 6, 7}
    assert 3 in plain and len(ids) == 4000


def test_new_readers_read_nothing_from_a_program_without_the_spans():
    from readers import block_step_roofline, moe_experts_roofline

    class NoDevices:
        devices = []

    ctx = {"trace": NoDevices(), "records": [{"stamps": []}], "spans": [],
           "t0": 0.0, "t1": 1.0}
    assert block_step_roofline.read(ctx, {"pattern": "x"}) is None
    assert moe_experts_roofline.read(ctx, {"pattern": "x"}) is None


def test_costs_from_shapes_at_the_published_widths():
    cfg = json.load(open(os.path.join(os.path.dirname(HERE), "configs",
                                      "sdar-30b-a3b.json")))
    assert 127.9 < blockdiff_costs.expected_experts_touched(cfg, 128) < 128
    assert blockdiff_costs.expected_experts_touched(cfg, 1) \
        == pytest.approx(8.0)
    assert blockdiff_costs.expert_bytes(cfg, 2) == 3 * 2048 * 768 * 2
    assert blockdiff_costs.kv_bytes_per_position(cfg, 2) == 12288
    step = blockdiff_costs.step_min_bytes(cfg, 128, 32 * 1400, 2, 2)
    # 6 layers of experts and attention, the head, 0.55 GB of K/V
    assert 8.0e9 < step < 8.8e9
    assert blockdiff_costs.experts_min_bytes(cfg, 128, 2) < step
    assert blockdiff_costs.experts_flops(cfg, 128) \
        == 2 * 6 * 128 * 8 * 3 * 2048 * 768


def test_the_configuration_holds_the_published_numbers():
    cfg = json.load(open(os.path.join(os.path.dirname(HERE), "configs",
                                      "sdar-30b-a3b.json")))
    published = {"head_dim": 128, "hidden_size": 2048,
                 "intermediate_size": 6144, "moe_intermediate_size": 768,
                 "num_attention_heads": 32, "num_experts": 128,
                 "num_experts_per_tok": 8, "num_hidden_layers": 48,
                 "num_key_value_heads": 4, "vocab_size": 151936,
                 "max_position_embeddings": 32768, "rope_theta": 1000000}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_layers"] and cfg["num_layers"] == 6
    assert cfg["published"] == {"num_layers": 48}
    assert cfg["storage"]["dtypes"][0] == cfg["weights_dtype"] == "bfloat16"
    assert cfg["engine"]["cache_dtype"] == "bfloat16"
    assert cfg["mask_token_id"] == cfg["assumed"]["mask_token_id"]
    layer = 128 * 3 * 2048 * 768 + 2048 * 4096 * 2 + 2048 * 512 * 2 \
        + 2048 * 128 + 2 * 2048 + 2 * 128
    assert round(layer / 1e6, 1) == 623.1
