"""The looped cell's driver and comparison, on the CPU at a toy size: a sound
run is correct and each of the three controls (fp8, one pass fewer, shared
planes) fails a limit; a program whose passes share one plane reads
``correct`` false; a traced run reads the spans' metrics and the new meta;
the costs and the configuration hold the published numbers."""
import json
import os

import pytest

import run as bench_run
from harness import looped_costs, looped_reference

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_looped_manifest.json")
CONFIG = os.path.join(os.path.dirname(HERE), "configs", "ouro-2p6b.json")
CELL = "ouro2p6b-batch-closed"


def run_cell(capsys, seed=7, trace=0, seconds=1.5, **measure):
    rc = bench_run.main(["--workload", "toy-looped", "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace)],
                        require_chip=False,
                        hooks={"manifest": TOY, "measure": measure})
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct_and_the_three_controls_are_not(capsys):
    result = run_cell(capsys, seed=3000000011,
                      controls=looped_reference.CONTROLS)
    assert result["correct"] is True and result["failed"] == 0
    got = result["compared"]
    for name in ("logit_gap_max", "logit_gap_mean",
                 "unstated_storage_bytes", "kv_planes_bytes_short"):
        assert got[name]["value"] <= got[name]["limit"]
    assert "float32_state_bytes_short" not in got
    assert got["requests_checked"]["value"] >= 20
    for mode in looped_reference.CONTROLS:
        assert got["control_%s_fails" % mode]["value"] == 1.0, mode
        assert got["control_%s_logit_gap_max" % mode]["value"] \
            > got["logit_gap_max"]["limit"], mode
    assert result["metrics"]["batch_tpot_p50_ms"]["value"] > 0
    assert set(result["per_layer_host_clock"]) == {
        "batch_occupancy.jamba", "serve_tokens_per_s.jamba",
        "tpot_p90_ms.jamba"}


def test_a_program_whose_passes_share_a_plane_is_not_correct(capsys,
                                                             monkeypatch):
    """Every pass writes and attends plane 0: the saving of three quarters
    of the cache, and what the served logits must tell from sound."""
    from paddle_tpu.models import looped_lm

    forward = looped_lm.LoopedDecoderLayer.forward

    def faulty(self, h, cache=None, plane=None):
        return forward(self, h, cache, None if plane is None else 0 * plane)
    monkeypatch.setattr(looped_lm.LoopedDecoderLayer, "forward", faulty)
    result = run_cell(capsys, seed=11, seconds=2.0)
    assert result["correct"] is False
    got = result["compared"]
    assert got["logit_gap_max"]["value"] > got["logit_gap_max"]["limit"]


def test_planes_stored_short_are_seen_by_the_census():
    from harness import looped

    cfg = json.load(open(CONFIG))
    need = 81 * 64 * 1572864
    sound = {"by_type": {"bfloat16": need + (5 << 30)}}
    assert looped.planes_held(cfg, sound)["ok"]
    one_plane = {"by_type": {"bfloat16": need // 4 + (5 << 30)}}
    got = looped.planes_held(cfg, one_plane)
    assert not got["ok"] and got["value"] == need - need // 4 - (5 << 30)


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
    assert 0.5 < m["launch_ahead_share.jamba"]["value"] <= 1.0
    assert 0.0 < m["live_block_share.jamba"]["value"] <= 100.0
    for name in ("tick_decode_ms.jamba", "tick_prep_ms.jamba",
                 "tick_deliver_ms.jamba", "greedy_step_share.jamba"):
        assert name in m
    # no device plane on the CPU: the device metrics are left out
    for name in ("decode_step_ms.jamba", "paged_attn_share.jamba",
                 "device_idle_share.jamba"):
        assert name not in m
    # the readers took what they know and left the keys they do not
    assert seen["decode"] and all(
        d["passes"] == 3 and d["kv_entries"] == 2 and d["kv_planes"] == 6
        and d["table_blocks"] == 4 * 8 for d in seen["decode"])


def test_costs_from_shapes_at_the_published_widths():
    cfg = json.load(open(CONFIG))
    assert looped_costs.layer_weight_count(cfg) \
        == 4 * 2048 ** 2 + 3 * 2048 * 5632 == 51380224
    assert looped_costs.plane_bytes_per_position(cfg) == 8192
    assert looped_costs.kv_bytes_per_position(cfg) == 1572864
    # 16 rows of 165 live positions reach 3 entries of 64 each
    calls = looped_costs.paged_calls_min_bytes(cfg, 16 * 3)
    assert calls == 48 * 64 * 1572864 == 4831838208
    step = looped_costs.decode_step_min_bytes(cfg, 16 * 3, 2)
    # four reads of 4.93 GB of layers, the head 0.20 GB, K/V 4.83 GB
    assert step == 4 * 48 * 51380224 * 2 + 2048 * 49152 * 2 + calls
    assert 24.7e9 < step < 24.9e9


def test_the_configuration_holds_the_published_numbers():
    cfg = json.load(open(CONFIG))
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["max_len"] and cfg["max_len"] == 320
    assert cfg["published"] == {"max_len": 65536}
    assert cfg["num_layers"] == cfg["num_hidden_layers"] == 48
    assert cfg["storage"]["dtypes"][0] == cfg["weights_dtype"] == "bfloat16"
    assert cfg["engine"] == {"cache_layout": "paged", "block_size": 64,
                             "slots": 16, "num_blocks": 81,
                             "buckets": [64, 128], "max_queue": 512,
                             "cache_dtype": "bfloat16"}
    # the parameters the configuration leads to: the release's 2.6 B
    from harness import looped_weights as lw
    count = lambda shapes: sum(
        int(__import__("numpy").prod(s)) for s in shapes.values())
    assert count(lw.layer_shapes(cfg)) == 51388416
    assert 48 * count(lw.layer_shapes(cfg)) + count(lw.top_shapes(cfg)) \
        == 2667974657
    for key in ("sandwich_norms", "final_norm_between_passes", "exit_gate",
                "kv_per_pass_and_layer", "no_bias", "rotary",
                "initializer_std", "engine_why"):
        assert key in cfg["assumed"], key
    assert "compute_saving_exit" in cfg["departures"]


def test_the_manifest_has_the_cell_where_the_issue_puts_it():
    root = os.path.dirname(os.path.dirname(HERE))
    m = json.load(open(os.path.join(root, "BENCHMARK.json")))
    assert m["configs"][-1]["name"] == "ouro-2p6b" \
        and m["configs"][-1]["reduced"] == ["max_len"]
    cell = m["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "ouro-2p6b", "reasonshort-closed-64", 1)
    lists = [e["name"] for e in m["end_to_end"] + m["per_layer"]
             if e.get("workloads", [])[-1:] == [CELL]]
    assert len(lists) == 18 and lists[0] == "batch_tpot_p50_ms" \
        and all(n.endswith(".jamba") for n in lists[1:])
    traffic = json.load(open(os.path.join(
        root, "benchmark", "traffic", "reasonshort-closed-64.json")))
    from harness import traffic as gen
    prompts = gen.length_multiset(traffic["prompt_tokens"], 16)
    outputs = gen.length_multiset(traffic["output_tokens"], 16)
    assert (min(prompts), max(prompts)) == (50, 126)
    assert sum(p <= 64 for p in prompts) == 3
    assert (min(outputs), max(outputs)) == (99, 189)
    assert max(prompts) + max(outputs) <= 320


def test_the_shares_read_by_hand_from_a_traced_line():
    import calibrate_looped

    cfg = json.load(open(CONFIG))
    line = {"device": {"kind": "TPU v5 lite"}, "metrics": {
        "live_block_share.jamba": {"value": 60.0},      # 48 of 80 entries
        "decode_step_ms.jamba": {"value": 40.0},
        "paged_attn_share.jamba": {"value": 25.0}}}
    got = calibrate_looped.by_hand(line, cfg)
    assert got["live_blocks"] == 48.0
    least = looped_costs.decode_step_min_bytes(cfg, 48, 2) / 819e9
    assert got["decode_step_roofline.ouro"] \
        == pytest.approx(100 * least / 0.040)
    assert 70.0 < got["decode_step_roofline.ouro"] < 80.0
    assert got["paged_calls_roofline.ouro"] == pytest.approx(
        100 * 4831838208 / 819e9 / 0.010)
