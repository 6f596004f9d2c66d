"""The latent-attention expert cell's driver and comparison, on the CPU at a
toy size: a sound run is correct and both controls (fp8 operands, the
router's group limit ignored) fail a limit; a program that leaves the
rotary key out of the cache, and one that routes without the group limit,
each read ``correct`` false; a traced run reads the spans' metrics; the new
reader reads nothing from a program without the spans; the costs and the
configuration hold the published numbers."""
import json
import os

import pytest

import run as bench_run
from harness import latent_costs

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_latent_manifest.json")
CONFIG = os.path.join(os.path.dirname(HERE), "configs", "ax-k1.json")


def run_cell(capsys, seed=7, trace=0, seconds=1.5, **measure):
    rc = bench_run.main(["--workload", "toy-latent", "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace)],
                        require_chip=False,
                        hooks={"manifest": TOY, "measure": measure})
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct_and_both_controls_are_not(capsys):
    result = run_cell(capsys, seed=3000000011,
                      controls=("fp8", "no_group_limit"))
    assert result["correct"] is True and result["failed"] == 0
    got = result["compared"]
    for name in ("logit_gap_max", "logit_gap_mean",
                 "unstated_storage_bytes"):
        assert got[name]["value"] <= got[name]["limit"]
    assert "float32_state_bytes_short" not in got
    assert got["requests_checked"]["value"] >= 20
    for control in ("fp8", "no_group_limit"):
        assert got["control_%s_fails" % control]["value"] == 1.0
        assert got["control_%s_logit_gap_max" % control]["value"] \
            > got["logit_gap_max"]["limit"]
    assert result["metrics"]["batch_tpot_p50_ms"]["value"] > 0


@pytest.mark.parametrize("fault", ["no_rotary_key", "no_group_limit"])
def test_a_faulty_program_is_not_correct(capsys, monkeypatch, fault):
    """``no_rotary_key``: the cache keeps zeros where the rotary key
    belongs, so a step's scores lose their position term (the prompt's
    expanded form does not read the cache and stays sound).
    ``no_group_limit``: the program's router takes the plain top-k."""
    import jax.numpy as jnp

    if fault == "no_rotary_key":
        from paddle_tpu.nn.layer import latent_attention as layer

        write = layer.LatentAttention._write
        monkeypatch.setattr(
            layer.LatentAttention, "_write",
            lambda self, cache, c, k_r, pos:
            write(self, cache, c, jnp.zeros_like(k_r), pos))
    else:
        from paddle_tpu.nn.functional import moe

        plain = moe.route_top_k
        monkeypatch.setattr(
            moe, "route_top_k",
            lambda logits, top_k, scoring, n_group, topk_group, scale:
            plain(logits, top_k, scoring, 1, 1, scale))
    result = run_cell(capsys, seed=11, seconds=2.0)
    assert result["correct"] is False
    got = result["compared"]
    assert got["logit_gap_max"]["value"] > got["logit_gap_max"]["limit"]


def test_traced_run_reads_the_spans_metrics(capsys):
    result = run_cell(capsys, seed=5, trace=1)
    assert result["correct"] is True
    m = result["metrics"]
    assert 1.0 <= m["live_slots_per_step.jamba"]["value"] <= 4.0
    assert 0.5 < m["launch_ahead_share.jamba"]["value"] <= 1.0
    assert 0.0 < m["live_block_share.jamba"]["value"] <= 100.0
    # no device plane on the CPU: the device metrics are left out
    for name in ("latent_attn_roofline.axk1", "decode_step_ms.jamba"):
        assert name not in m


def test_the_new_reader_reads_nothing_from_a_program_without_the_spans():
    from readers import latent_attn_roofline

    class NoDevices:
        devices = []

    ctx = {"trace": NoDevices(), "records": [{"stamps": []}], "spans": [],
           "t0": 0.0, "t1": 1.0}
    scoped = {"pattern": "x", "scope_pattern": "y", "trace_dir": "z"}
    assert latent_attn_roofline.read(ctx, scoped) is None


def test_costs_from_shapes_at_the_published_widths():
    cfg = json.load(open(CONFIG))
    assert latent_costs.latent_bytes_per_position(cfg) == 1152
    # 32 rows x 38.5 blocks of 128: 153,600 positions x 7 x 1,152 B
    positions = latent_costs.live_positions(cfg, 32, 32 * 38 + 32)
    assert positions == 32 * 38.5 * 128
    assert latent_costs.latent_attn_min_bytes(cfg, positions) \
        == 7 * positions * 1152
    # 64 heads x 2 x (192 + 128) a position a layer
    assert latent_costs.latent_attn_flops(cfg, 1.0) == 7 * 64 * 640
    # bound by bytes: 1,152 B a position at 819 GB/s against 40,960
    # operations at 197 TFLOP/s
    assert 1152 / 819e9 > 64 * 640 / 197e12
    # the program's own entry, from its own cache
    from paddle_tpu.nn import LatentAttention
    cache = LatentAttention(64, 2, 8, 512, 16, 64, 16).gen_decode_cache(
        1, 256, "bfloat16", layout="paged", block_size=128)
    # 512 + 64 values of content in an entry of 640 (whole lanes)
    assert cache.latent.shape == (3, 128, 640)
    assert cache.latent.nbytes // (3 * 128) == 1280


def test_the_configuration_holds_the_published_numbers():
    cfg = json.load(open(CONFIG))
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 7168,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "axk1",
        "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 61, "num_key_value_heads": 64,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "seq_aux": True, "tie_word_embeddings": False, "topk_group": 4,
        "topk_method": "none", "v_head_dim": 128}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_layers", "n_routed_experts",
                              "vocab_size", "max_len"]
    assert cfg["published"] == {"num_layers": 61, "n_routed_experts": 192,
                                "vocab_size": 163840, "max_len": 131072}
    assert (cfg["num_layers"], cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["max_len"]) == (7, 12, 20480, 9216)
    assert cfg["router_experts"] == 192 and cfg["first_held_expert"] == 0
    assert cfg["storage"]["dtypes"][0] == cfg["weights_dtype"] == "bfloat16"
    assert cfg["engine"] == {"cache_layout": "paged", "block_size": 128,
                             "slots": 32, "num_blocks": 2305,
                             "buckets": [2048, 4096, 8192],
                             "max_queue": 256, "cache_dtype": "bfloat16"}
    # the cut's arithmetic, from the shapes the weights are made of
    from harness import latent_weights as lw
    import math
    count = lambda shapes: sum(math.prod(s) for s in shapes.values())
    layers = [count(lw.layer_shapes(cfg, i)) for i in range(7)]
    assert layers[0] == 497500160 and set(layers[1:]) == {675037184}
    assert count(lw.top_shapes(cfg)) == 293608448
    assert sum(layers) + count(lw.top_shapes(cfg)) == 4841331712
    kwargs = lw.model_kwargs(cfg)
    assert kwargs["num_experts"] == 192 and kwargs["held_experts"] == (0, 12)
