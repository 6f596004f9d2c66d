"""The CCA / routed-expert cell's driver and comparison, on the CPU at a toy
size: a sound run is correct and both controls (fp8 operands; the mixing
along the sequence left out) fail a limit; a program that drops the CCA state
at a resume, and one that takes the shifted value half from the current
token, each read ``correct`` false; a traced run reports the entries the cell
is appended to; the configuration holds the published numbers."""
import json
import math
import os

import pytest

import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_cca_manifest.json")
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG = os.path.join(os.path.dirname(HERE), "configs", "zaya1-8b.json")
CELL = "zaya8b-batch-closed"


def run_cell(capsys, seed=7, trace=0, seconds=1.5, **measure):
    rc = bench_run.main(["--workload", "toy-cca", "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace)],
                        require_chip=False,
                        hooks={"manifest": TOY, "measure": measure})
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct_and_both_controls_are_not(capsys):
    result = run_cell(capsys, seed=3000000011, controls=("fp8", "no_mix"))
    assert result["correct"] is True and result["failed"] == 0
    got = result["compared"]
    for name in ("logit_gap_max", "logit_gap_mean",
                 "unstated_storage_bytes", "cca_state_bytes_short"):
        assert got[name]["value"] <= got[name]["limit"]
    assert got["requests_checked"]["value"] >= 20
    for control in ("fp8", "no_mix"):
        assert got["control_%s_fails" % control]["value"] == 1.0
        assert got["control_%s_logit_gap_max" % control]["value"] \
            > got["logit_gap_max"]["limit"]
    assert result["metrics"]["batch_tpot_p50_ms"]["value"] > 0


@pytest.mark.parametrize("fault", ["state_dropped_at_resume",
                                   "value_from_current_token"])
def test_a_faulty_program_is_not_correct(capsys, monkeypatch, fault):
    """``state_dropped_at_resume``: the splice of a prefilled row into its
    slot leaves the slot's CCA state as it was (zeros, or the slot's last
    request's), so the first step's taps and shifted values read another
    history.  ``value_from_current_token``: the layer takes both value
    halves from the current token."""
    import jax.numpy as jnp

    if fault == "state_dropped_at_resume":
        from paddle_tpu.jit import cache

        monkeypatch.setattr(
            cache.RecurrentLayout, "insert_entry",
            lambda self, cp, cr, slot, length, blocks=None: cp._replace(
                index=cp.index.at[slot].set(jnp.asarray(length, jnp.int32))))
    else:
        from paddle_tpu.nn.layer import cca_attention as layer

        window, half = layer._window, 2 * 16 // 2      # the toy's K/V / 2

        def current_token(state, chunk, taps):
            """The value window (the one of the half's width) starts at
            the chunk itself: the 'token before' is the token."""
            if chunk.shape[-1] != half:
                return window(state, chunk, taps)
            return jnp.concatenate([chunk, chunk[:, -1:]], axis=1)

        monkeypatch.setattr(layer, "_window", current_token)
    result = run_cell(capsys, seed=11, seconds=2.0)
    assert result["correct"] is False
    got = result["compared"]
    assert got["logit_gap_max"]["value"] > got["logit_gap_max"]["limit"]


def test_traced_run_reports_the_appended_entries(capsys):
    result = run_cell(capsys, seed=5, trace=1)
    assert result["correct"] is True
    m = result["metrics"]
    assert 1.0 <= m["live_slots_per_step.jamba"]["value"] <= 4.0
    assert 0.5 < m["launch_ahead_share.jamba"]["value"] <= 1.0
    assert 0.0 < m["live_block_share.jamba"]["value"] <= 100.0
    for name in ("tick_decode_ms", "tick_prep_ms", "tick_deliver_ms",
                 "tick_unspanned_ms", "tick_decode_wait_ms",
                 "tick_host_wait_ms", "greedy_step_share"):
        assert name + ".jamba" in m
    # no device plane on the CPU: the device metrics are left out
    for name in ("paged_attn_share.jamba", "decode_step_ms.jamba",
                 "prefill_ms.jamba", "device_idle_share.jamba"):
        assert name not in m
    # the toy manifest lists what BENCHMARK.json lists the cell under
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    toy = json.load(open(TOY))
    assert sorted(p["name"] for p in toy["per_layer"]) == sorted(
        p["name"] for p in real["per_layer"] if CELL in p.get("workloads", []))
    assert len(toy["per_layer"]) == 17 and len(real["per_layer"]) == 128


def test_the_configuration_holds_the_published_numbers():
    cfg = json.load(open(CONFIG))
    rows = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if os.path.exists("/opt/skills/guides/model-configs") else []
    for row in rows:
        if row["name"] == "ZAYA1-8B":
            assert {k: cfg[k] for k in row["config"]} == row["config"]
            assert cfg["source"] == row["source_url"]
    published = {
        "attention_bias": False, "cca_time0": 2, "cca_time1": 2,
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "max_position_embeddings": 131072, "model_type": "zaya",
        "moe_intermediate_size": 2048, "num_attention_heads": 8,
        "num_experts": 16, "num_experts_per_tok": 1,
        "num_hidden_layers": 40, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
        "router_hidden_size": 256, "tie_word_embeddings": True,
        "vocab_size": 262272}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_parameters"]["hybrid"]["rope_theta"] == 5000000
    assert cfg["reduced"] == ["num_layers", "max_len"]
    assert cfg["published"] == {"num_layers": 40, "max_len": 131072}
    assert (cfg["num_layers"], cfg["max_len"]) == (20, 3072)
    assert cfg["storage"]["dtypes"][0] == cfg["weights_dtype"] == "bfloat16"
    assert cfg["engine"] == {"cache_layout": "paged", "block_size": 128,
                             "slots": 64, "num_blocks": 1537,
                             "buckets": [256, 512, 1024], "max_queue": 512,
                             "cache_dtype": "bfloat16"}
    assert set(cfg["departures"]) >= {"router_skip_output",
                                      "residual_scales",
                                      "router_balancing_bias"}
    # the cut's arithmetic, from the shapes the weights are made of
    from harness import cca_weights as cw
    count = lambda shapes: sum(math.prod(s) for s in shapes.values())
    layers = [count(cw.layer_shapes(cfg, i)) for i in range(20)]
    assert layers[0] == 207567106 - 256 and set(layers[1:]) == {207567106}
    assert count(cw.top_shapes(cfg)) == 262272 * 2048 + 2048
    assert 40 * 207567106 == 8302684240
    # a position's K/V and a slot's state, from the program's own cache
    from paddle_tpu.nn import CCAttention
    kv, state = CCAttention(64, 8, 2, 128, (2, 2)).gen_decode_cache(
        2, 256, "bfloat16", layout="paged", block_size=128)
    assert kv.k.shape == (5, 2, 128, 128)
    assert (kv.k.nbytes + kv.v.nbytes) // (5 * 128) == 1024
    assert sum(getattr(state, f).shape[1]
               for f in ("u", "c0", "v_next")) == 2688
    # the traffic the issue gives
    tr = json.load(open(os.path.join(os.path.dirname(HERE), "traffic",
                                     "reasonlong-closed-256.json")))
    assert (tr["clients"], tr["block"], tr["stagger_first"]) == (256, 64, 64)
    assert tr["prompt_tokens"] == {"dist": "uniform", "min": 128,
                                   "max": 1024}
    assert tr["output_tokens"] == {"dist": "uniform", "min": 512,
                                   "max": 2048}
    assert (tr["warmup_s"], tr["trace_s"], tr["drain_s"],
            tr["max_lateness_p90_ms"], tr["check_requests"]) \
        == (6.0, 10.0, 1.0, 20.0, 8)
