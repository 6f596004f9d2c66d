"""The hybrid Mamba / attention cell's driver and comparison, on the CPU at a
toy size: a sound run is correct and the fp8 control fails a limit; a program
that holds the scan state in bfloat16, and one that drops the convolution's
state, each read ``correct`` false; a traced run reads the spans' metrics;
the new readers read nothing from a program without the spans; the costs and
the configuration hold the published numbers."""
import json
import os

import pytest

import run as bench_run
from harness import mamba_costs

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_mamba_manifest.json")
CONFIG = os.path.join(os.path.dirname(HERE), "configs", "jamba2-3b.json")


def run_cell(capsys, seed=7, trace=0, seconds=1.5, **measure):
    rc = bench_run.main(["--workload", "toy-mamba", "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace)],
                        require_chip=False,
                        hooks={"manifest": TOY, "measure": measure})
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct_and_the_fp8_control_is_not(capsys):
    result = run_cell(capsys, seed=3000000011,
                      controls=("fp8", "bf16_state"))
    assert result["correct"] is True and result["failed"] == 0
    got = result["compared"]
    for name in ("logit_gap_max", "logit_gap_mean",
                 "unstated_storage_bytes", "float32_state_bytes_short"):
        assert got[name]["value"] <= got[name]["limit"]
    assert got["requests_checked"]["value"] >= 20
    assert got["control_fp8_fails"]["value"] == 1.0
    assert got["control_fp8_logit_gap_max"]["value"] \
        > got["logit_gap_max"]["limit"]
    # a state rounded to bfloat16 moves a logit by 1e-5 at this size: it
    # is read, and fails only where a served token sat that close to a tie
    assert got["control_bf16_state_fails"]["value"] in (0.0, 1.0)
    assert got["control_bf16_state_logit_gap_max"]["value"] \
        <= got["control_fp8_logit_gap_max"]["value"]
    assert result["metrics"]["batch_tpot_p50_ms"]["value"] > 0
    assert set(result["per_layer_host_clock"]) == {
        "batch_occupancy.jamba", "serve_tokens_per_s.jamba",
        "tpot_p90_ms.jamba"}


@pytest.mark.parametrize("fault", ["stale_conv", "no_decay"])
def test_a_faulty_program_is_not_correct(capsys, monkeypatch, fault):
    """``stale_conv``: the decode step leaves the convolution's state as it
    found it, so every later token convolves the prompt's last inputs.
    ``no_decay``: the scan's step forgets nothing (``A = 0``)."""
    import jax.numpy as jnp

    from paddle_tpu.nn.layer import mamba as layer
    from paddle_tpu.ops import selective_scan as sound

    if fault == "stale_conv":
        forward = layer.MambaMixer.forward

        def faulty(self, x, cache=None):
            out = forward(self, x, cache)
            if cache is not None and x.shape[1] == 1:
                out = (out[0], out[1]._replace(conv=cache.conv))
            return out
        monkeypatch.setattr(layer.MambaMixer, "forward", faulty)
    else:
        class Faulty:
            def __getattr__(self, name):
                return getattr(sound, name)

            @staticmethod
            def selective_scan_step(dt, c, b, cm, a, d, state):
                return sound.selective_scan_step(dt, c, b, cm,
                                                 jnp.zeros_like(a), d, state)
        monkeypatch.setattr(layer, "ops", Faulty())
    result = run_cell(capsys, seed=11, seconds=2.0)
    assert result["correct"] is False
    got = result["compared"]
    assert got["logit_gap_max"]["value"] > got["logit_gap_max"]["limit"]


def test_a_state_stored_below_float32_is_seen_by_the_census():
    from harness import mamba

    cfg = json.load(open(CONFIG))
    need = 64 * 26 * 327680
    sound = {"by_type": {"float32": need, "bfloat16": 6 << 30}}
    assert mamba.state_held_in_float32(cfg, sound)["ok"]
    halved = {"by_type": {"float32": 1 << 20, "bfloat16": need // 2}}
    got = mamba.state_held_in_float32(cfg, halved)
    assert not got["ok"] and got["value"] == need - (1 << 20)


def test_traced_run_reads_the_spans_metrics(capsys):
    result = run_cell(capsys, seed=5, trace=1)
    assert result["correct"] is True
    m = result["metrics"]
    assert 1.0 <= m["live_slots_per_step.jamba"]["value"] <= 4.0
    assert 0.5 < m["launch_ahead_share.jamba"]["value"] <= 1.0
    assert 0.0 < m["live_block_share.jamba"]["value"] <= 100.0
    # no device plane on the CPU: the device metrics are left out
    for name in ("scan_step_roofline.jamba", "decode_step_roofline.jamba",
                 "prefill_scan_roofline.jamba", "mamba_share.jamba",
                 "decode_step_ms.jamba"):
        assert name not in m


def test_new_readers_read_nothing_from_a_program_without_the_spans():
    from readers import (mamba_decode_roofline, prefill_scan_roofline,
                         scan_step_roofline)

    class NoDevices:
        devices = []

    ctx = {"trace": NoDevices(), "records": [{"stamps": []}], "spans": [],
           "t0": 0.0, "t1": 1.0}
    scoped = {"pattern": "x", "scope_pattern": "y", "trace_dir": "z"}
    assert scan_step_roofline.read(ctx, scoped) is None
    assert prefill_scan_roofline.read(ctx, scoped) is None
    assert mamba_decode_roofline.read(
        ctx, {"pattern": "x", "weight_bytes": 2}) is None


def test_costs_from_shapes_at_the_published_widths():
    cfg = json.load(open(CONFIG))
    assert mamba_costs.mamba_layers(cfg) == 26
    assert mamba_costs.state_bytes_per_slot_layer(cfg) == (327680, 30720)
    assert mamba_costs.ssm_bytes_per_slot(cfg) == 26 * 327680
    assert mamba_costs.scan_step_min_bytes(cfg, 64) \
        == 2 * 64 * 26 * (327680 + 30720)
    assert mamba_costs.kv_bytes_per_position(cfg) == 1024
    # 26 x 104.2 M + 2 x 76.7 M + the embedding once: 3.03 B
    assert 3.02e9 < mamba_costs.weight_count(cfg) < 3.03e9
    step = mamba_costs.decode_step_min_bytes(cfg, 64, 64 * 9, 2)
    # weights 6.05 GB, states 1.19 GB, K/V of 9 blocks a row 0.08 GB
    assert 7.2e9 < step < 7.4e9
    # one prefill of 1,024 positions: 26 x 1,024 x 51,328 B
    assert 1.36e9 < mamba_costs.prefill_scan_min_bytes(cfg, 1024) < 1.40e9
    # the program's own layout, from its own cache
    from paddle_tpu.nn import MambaMixer
    mixer = MambaMixer(64, 5120, 16, 4, 8)
    cache = mixer.gen_decode_cache(1, 8)
    assert cache.ssm.shape == (1, 16, 5120) and cache.ssm.nbytes == 327680
    assert cache.conv.shape == (1, 3 * 5120)


def test_the_configuration_holds_the_published_numbers():
    cfg = json.load(open(CONFIG))
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
        "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None,
        "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["max_len"] and cfg["max_len"] == 2304
    assert cfg["published"] == {"max_len": 262144}
    assert cfg["num_layers"] == cfg["num_hidden_layers"] == 28
    assert cfg["storage"]["dtypes"][0] == cfg["weights_dtype"] == "bfloat16"
    assert cfg["engine"] == {"cache_layout": "paged", "block_size": 128,
                             "slots": 64, "buckets": [256, 512, 1024],
                             "max_queue": 512, "cache_dtype": "bfloat16"}
    from harness import mamba_weights as mw
    assert [i for i in range(28) if mw.is_attention(cfg, i)] == [7, 21]
    assumed = cfg["assumed"]
    assert assumed["head_dim"] == mw.head_dim(cfg) == 128
    from paddle_tpu.ops import selective_scan as ss
    assert ss.SCAN_BLOCK == assumed["scan_block"]
    assert ss.CHANNEL_TILE == assumed["channel_tile"]
