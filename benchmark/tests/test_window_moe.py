"""The window / global expert cell's driver and comparison, on the CPU at a
toy size: a sound run is correct and each of the four controls (fp8, the
window forgotten, the router on the post-attention norm, silu for relu)
fails a limit; a program that keeps its window layers on full-length pools
reads ``correct`` false by the census; a traced run reads the spans' metrics
and the new meta; the costs and the configuration hold the published
numbers."""
import json
import os

import pytest

import run as bench_run
from harness import window_moe, window_moe_costs as costs, \
    window_moe_reference

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_window_moe_manifest.json")
CONFIG = os.path.join(os.path.dirname(HERE), "configs",
                      "smallthinker-21b-a3b.json")
CELL = "smallthinker21b-batch-closed"


def run_cell(capsys, seed=7, trace=0, seconds=1.5, **measure):
    rc = bench_run.main(["--workload", "toy-window-moe", "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace)],
                        require_chip=False,
                        hooks={"manifest": TOY, "measure": measure})
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct_and_the_four_controls_are_not(capsys):
    result = run_cell(capsys, seed=3000000011,
                      controls=window_moe_reference.CONTROLS)
    assert result["correct"] is True and result["failed"] == 0
    got = result["compared"]
    for name in ("logit_gap_max", "logit_gap_mean",
                 "unstated_storage_bytes", "cache_pool_bytes_short",
                 "cache_pool_bytes_over"):
        assert got[name]["value"] <= got[name]["limit"], name
    assert "float32_state_bytes_short" not in got
    assert got["requests_checked"]["value"] >= 10
    # contexts past the window of 16 were served and compared
    assert got["longest_checked"]["value"] > 3 * 16
    for mode in window_moe_reference.CONTROLS:
        assert got["control_%s_fails" % mode]["value"] == 1.0, mode
        assert got["control_%s_logit_gap_max" % mode]["value"] \
            > got["logit_gap_max"]["limit"], mode
    assert result["metrics"]["batch_tpot_p50_ms"]["value"] > 0


def test_window_layers_on_full_length_pools_are_seen_by_the_census(
        capsys, monkeypatch):
    """A program whose window layers kept every position on the block
    table: its logits are the reference's (the band is still the mask), so
    only the census of what it holds can tell, and does: it holds more."""
    from paddle_tpu.nn.layer import transformer as tf

    ring = tf.GroupedQueryAttention.gen_decode_cache

    def whole_span(self, batch, max_length, dtype="float32", per_slot=False,
                   layout="dense", block_size=32, num_blocks=None,
                   planes=1):
        # a window entry whose "ring" spans max_len, in the pool too
        return ring(self, batch, max_length, dtype, per_slot, layout,
                    block_size, None if self.window else num_blocks, planes)
    monkeypatch.setattr(tf.GroupedQueryAttention, "gen_decode_cache",
                        whole_span)
    result = run_cell(capsys, seed=11, seconds=2.0)
    got = result["compared"]
    assert got["logit_gap_max"]["value"] <= got["logit_gap_max"]["limit"]
    assert got["cache_pool_bytes_over"]["value"] > 0
    assert result["correct"] is False


def test_pools_held_reads_short_and_over():
    cfg = json.load(open(CONFIG))
    pools = costs.pool_bytes(cfg)
    assert pools == {"paged": 3 * 1281 * 128 * 2048,
                     "window": 9 * 529 * 128 * 2048}
    weights = costs.weight_leaf_bytes(cfg, 2, 1 << 20)
    # every matrix but the routers (327,680 B each) and the norms
    assert weights == 2 * (12 * (20971520 + 377487360) + 2 * 151936 * 2560)
    need = sum(pools.values()) + weights
    assert window_moe.pools_held(cfg, {"by_type": {"bfloat16": need}}) \
        == {"short": 0, "over": 0}
    full = 9 * (1281 - 529) * 128 * 2048      # window layers on the table
    assert window_moe.pools_held(
        cfg, {"by_type": {"bfloat16": need + full}}) \
        == {"short": 0, "over": full}
    assert window_moe.pools_held(
        cfg, {"by_type": {"bfloat16": need - 4096}})["short"] == 4096


def test_traced_run_reads_the_spans_metrics_and_the_new_meta(capsys):
    seen = {}
    from harness import readctx
    traced = readctx.traced

    def keep(ctx, run, got):
        ctx = traced(ctx, run, got)
        seen["decode"] = [m for n, _, _, _, m in ctx["spans"]
                          if n == "tick.decode"]
        return ctx
    readctx.traced = keep
    try:
        result = run_cell(capsys, seed=5, trace=1)
    finally:
        readctx.traced = traced
    assert result["correct"] is True
    m = result["metrics"]
    assert 1.0 <= m["live_slots_per_step.jamba"]["value"] <= 4.0
    assert 0.0 < m["live_block_share.jamba"]["value"] <= 100.0
    for name in ("tick_decode_ms.jamba", "tick_prep_ms.jamba",
                 "tick_deliver_ms.jamba", "greedy_step_share.jamba"):
        assert name in m
    for name in ("decode_step_ms.jamba", "paged_attn_share.jamba",
                 "device_idle_share.jamba"):
        assert name not in m
    assert seen["decode"] and all(
        d["kv_entries"] == 1 and d["window_entries"] == 3
        and d["window"] == 16 and d["ring_blocks"] == 3
        and d["table_blocks"] == 4 * 16
        and d["window_live_blocks"] <= 3 * d["live"]
        and d["experts_held"] == 32 for d in seen["decode"])


def test_costs_from_shapes_at_the_published_widths():
    cfg = json.load(open(CONFIG))
    assert costs.kv_bytes_per_position(cfg) == 2048
    assert costs.layer_kinds(cfg) == (3, 9)
    assert costs.ring_blocks(cfg) == 33
    assert costs.expert_bytes(cfg, 2) == 11796480
    assert 50.7 < costs.experts_touched(cfg, 16) < 50.9
    assert costs.attention_weight_count(cfg) == 20971520
    # a row at position 12,000 reads 33 entries of a window layer (the
    # band's first position 7,905 lies in entry 61) and 94 of a global one
    assert costs.in_window_positions(cfg, [12000]) == 33 * 128
    assert costs.in_window_positions(cfg, [1000]) == 8 * 128
    step = costs.decode_step_min_bytes(cfg, 16, 16 * 55, 16 * 30, 2)
    experts = costs.moe_experts_min_bytes(cfg, 16, 2)
    assert 7.1e9 < experts < 7.3e9
    assert step == pytest.approx(
        experts + 12 * (20971520 + 163840) * 2 + 2560 * 151936 * 2
        + 16 * 55 * 128 * 2048 * 3 + 16 * 30 * 128 * 2048 * 9)
    assert 10.0e9 < step < 10.6e9
    # a banded prompt of 12,288 against a causal one
    causal = costs.prefill_attention_flops(cfg, 12288, False)
    band = costs.prefill_attention_flops(cfg, 12288, True)
    assert causal == 4.0 * 28 * 128 * 12288 * 12289 / 2
    assert band == 4.0 * 28 * 128 * (4096 * 4097 / 2 + 8192 * 4096)
    assert 0.55 < band / causal < 0.56


def test_the_configuration_holds_the_published_numbers():
    cfg = json.load(open(CONFIG))
    layout = [0, 1, 1, 1] * 13
    published = {
        "head_dim": 128, "hidden_size": 2560, "num_hidden_layers": 52,
        "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_layout": layout, "rope_scaling": None,
        "rope_theta": 1500000, "sliding_window_layout": layout,
        "sliding_window_size": 4096, "tie_word_embeddings": False,
        "vocab_size": 151936}
    assert {k: cfg[k] for k in published} == published
    # the depth is cut under the key the harness reads, as every accepted
    # configuration files it; the published key keeps the published 52
    assert cfg["reduced"] == ["num_layers"]
    assert (cfg["num_layers"], cfg["num_hidden_layers"]) == (12, 52)
    assert cfg["published"] == {"num_layers": 52}
    assert cfg["max_len"] == cfg["max_position_embeddings"]
    assert cfg["storage"]["dtypes"][0] == cfg["weights_dtype"] == "bfloat16"
    assert cfg["engine"] == {"cache_layout": "paged", "block_size": 128,
                             "slots": 16, "num_blocks": 1281,
                             "buckets": [2048, 4096, 8192, 12288],
                             "max_queue": 512, "cache_dtype": "bfloat16"}
    from harness import window_moe_weights as ww
    count = lambda shapes: sum(
        int(__import__("numpy").prod(s)) for s in shapes.values())
    assert count(ww.layer_shapes(cfg)) == 398627840
    assert count(ww.top_shapes(cfg)) == 2 * 151936 * 2560 + 2560
    # the release: 52 layers of it
    assert 52 * 398627840 + count(ww.top_shapes(cfg)) == 21506562560
    assert ww.layouts(cfg) == ((0, 1, 1, 1) * 3,) * 2
    for key in ("router_input", "no_qk_norm", "rotary", "band_edge",
                "initializer_std", "qk_initializer_std",
                "router_initializer_std", "initialisers_why", "engine_why"):
        assert key in cfg["assumed"], key
    assert set(cfg["departures"]) >= {"secondary_experts",
                                      "sparsity_predictors"}


def test_the_manifest_has_the_cell_the_issue_names():
    root = os.path.dirname(os.path.dirname(HERE))
    m = json.load(open(os.path.join(root, "BENCHMARK.json")))
    conf = {c["name"]: c for c in m["configs"]}["smallthinker-21b-a3b"]
    assert conf["reduced"] == ["num_layers"]
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("smallthinker-21b-a3b", "mixedctx-closed-64", 1)
    lists = [e["name"] for e in m["end_to_end"] + m["per_layer"]
             if CELL in e.get("workloads", [])]
    assert len(lists) == 18 and lists[0] == "batch_tpot_p50_ms" \
        and all(n.endswith(".jamba") for n in lists[1:])
    traffic = json.load(open(os.path.join(
        root, "benchmark", "traffic", "mixedctx-closed-64.json")))
    from harness import traffic as gen
    prompts = gen.length_multiset(traffic["prompt_tokens"], 16)
    outputs = gen.length_multiset(traffic["output_tokens"], 16)
    assert (min(prompts), max(prompts)) == (1376, 11936)
    assert [sum(lo < p <= hi for p in prompts) for lo, hi in
            ((0, 2048), (2048, 4096), (4096, 8192), (8192, 12288))] \
        == [1, 3, 6, 6]
    assert (min(outputs), max(outputs)) == (280, 1000)
    assert max(prompts) + max(outputs) <= 13312
    assert (traffic["clients"], traffic["block"], traffic["stagger_first"],
            traffic["check_requests"]) == (64, 16, 16, 8)


def test_the_rooflines_read_by_hand_from_a_traced_line(monkeypatch):
    import sys

    import calibrate_window_moe as cal

    cfg = json.load(open(CONFIG))
    line = {"device": {"kind": "TPU v5 lite"}, "metrics": {
        "live_block_share.jamba": {"value": 50.0},
        "live_slots_per_step.jamba": {"value": 16.0},
        "decode_step_ms.jamba": {"value": 15.0}}}
    monkeypatch.setattr(sys, "argv", [
        "x", "--meta", "16,880,480", "--windowed-ms", "2.0",
        "--experts-ms", "10.0", "--band-ms", "100", "--band-tokens",
        "12288"])
    got = cal.by_hand(line, cfg)
    step = costs.decode_step_min_bytes(cfg, 16, 880, 480, 2)
    assert got["decode_step_roofline"] == pytest.approx(
        100 * step / 819e9 / 0.015)
    assert 80.0 < got["decode_step_roofline"] < 90.0
    assert got["windowed_calls_roofline"] == pytest.approx(
        100 * 480 * 128 * 2048 * 9 / 819e9 / 0.002)
    assert got["moe_experts_roofline"] == pytest.approx(
        100 * costs.moe_experts_min_bytes(cfg, 16, 2) / 819e9 / 0.010)
    assert got["prefill_band_roofline"] == pytest.approx(
        100 * 9 * costs.prefill_attention_flops(cfg, 12288, True)
        / 197e12 / 0.1)
