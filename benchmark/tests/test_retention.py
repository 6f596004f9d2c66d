"""The power-retention cell's driver and comparison, on the CPU at a toy
size: a sound run is correct and every control the calibration reads fails
a limit; a program that holds the state in bfloat16, and one that skips the
gate, each read ``correct`` false; a traced run reads the spans' metrics;
the new readers read nothing from a program without the spans; the costs and
the configuration hold the published numbers."""
import json
import os

import pytest

import run as bench_run
from harness import retention_costs

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_retention_manifest.json")
CONFIG = os.path.join(os.path.dirname(HERE), "configs", "brumby-14b.json")


def run_cell(capsys, seed=7, trace=0, seconds=1.5, **measure):
    rc = bench_run.main(["--workload", "toy-retention", "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace)],
                        require_chip=False,
                        hooks={"manifest": TOY, "measure": measure})
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct_and_every_control_is_not(capsys):
    result = run_cell(capsys, seed=3000000011,
                      controls=("fp8", "bf16_state", "no_gate"))
    assert result["correct"] is True and result["failed"] == 0
    got = result["compared"]
    for name in ("logit_gap_max", "logit_gap_mean",
                 "unstated_storage_bytes", "float32_state_bytes_short"):
        assert got[name]["value"] <= got[name]["limit"]
    assert got["tokens_checked"]["value"] >= 16
    assert got["requests_checked"]["value"] >= 50
    for mode in ("fp8", "no_gate"):
        assert got["control_%s_fails" % mode]["value"] == 1.0, mode
        assert got["control_%s_logit_gap_max" % mode]["value"] \
            > got["logit_gap_max"]["limit"], mode
    # a state rounded to bfloat16 moves a logit by 1e-3 at this size: it
    # is read, and fails wherever a served token sat that close to a tie
    # (most runs; which requests finish depends on the host's timing)
    assert got["control_bf16_state_fails"]["value"] in (0.0, 1.0)
    assert got["control_bf16_state_logit_gap_max"]["value"] \
        <= got["control_fp8_logit_gap_max"]["value"]
    assert result["metrics"]["batch_tpot_p50_ms"]["value"] > 0
    assert set(result["per_layer_host_clock"]) == {
        "batch_occupancy.brumby", "serve_tokens_per_s.brumby",
        "tpot_p90_ms.brumby"}


def _faulty(monkeypatch, fault):
    """The program's retention forms with a fault put in where the layer
    calls them."""
    import jax.numpy as jnp

    from paddle_tpu.nn.layer import retention as layer
    from paddle_tpu.ops import power_retention as sound

    class Faulty:
        def __getattr__(self, name):
            return getattr(sound, name)

        @staticmethod
        def _run(form, q, k, v, lg, *rest, **kw):
            if fault == "no_gate":
                lg = jnp.zeros_like(lg)
            y, state, norm = form(q, k, v, lg, *rest, **kw)
            if fault == "bf16_state":
                state, norm = (x.astype(jnp.bfloat16).astype(jnp.float32)
                               for x in (state, norm))
            return y, state, norm

        def power_retention_step(self, *a, **kw):
            return self._run(sound.power_retention_step, *a, **kw)

        def power_retention_chunked(self, *a, **kw):
            return self._run(sound.power_retention_chunked, *a, **kw)

        def power_retention_prefill(self, *a, **kw):
            return self._run(sound.power_retention_prefill, *a, **kw)

    monkeypatch.setattr(layer, "ops", Faulty())


@pytest.mark.parametrize("fault", ["bf16_state", "no_gate"])
def test_a_faulty_program_is_not_correct(capsys, monkeypatch, fault):
    """``bf16_state``: what every step and every prefill leaves in the
    state is rounded to bfloat16 (the census cannot see it here: the array
    stays float32, as a program that rounds inside its kernel would keep
    it).  ``no_gate``: the decay is skipped, every past position weighs as
    the last."""
    _faulty(monkeypatch, fault)
    # 4 s, not 1.5: a rounded state flips 3 tokens in 1,000 at this size,
    # and the run has to serve enough of them whatever the host's load
    result = run_cell(capsys, seed=11, seconds=4.0)
    assert result["correct"] is False
    got = result["compared"]
    assert got["logit_gap_max"]["value"] > got["logit_gap_max"]["limit"]


def test_a_state_stored_below_float32_is_seen_by_the_census():
    from harness import retention_correct

    cfg = json.load(open(CONFIG))
    need = 16 * 8 * 38043648
    sound = {"by_type": {"float32": need, "bfloat16": 8 << 30}}
    assert retention_correct.state_held_in_float32(cfg, sound)["ok"]
    halved = {"by_type": {"float32": 1 << 20, "bfloat16": need // 2}}
    got = retention_correct.state_held_in_float32(cfg, halved)
    assert not got["ok"] and got["value"] == need - (1 << 20)


def test_traced_run_reads_the_spans_metrics(capsys):
    result = run_cell(capsys, seed=5, trace=1)
    assert result["correct"] is True
    m = result["metrics"]
    assert 1.0 <= m["live_slots_per_step.brumby"]["value"] <= 4.0
    assert 0.5 < m["launch_ahead_share.brumby"]["value"] <= 1.0
    # no device plane on the CPU: the device metrics are left out
    for name in ("retention_step_roofline.brumby",
                 "decode_step_roofline.brumby", "retention_share.brumby",
                 "decode_step_ms.brumby"):
        assert name not in m


def test_new_readers_read_nothing_from_a_program_without_the_spans():
    from readers import retention_decode_roofline, retention_step_roofline

    class NoDevices:
        devices = []

    ctx = {"trace": NoDevices(), "records": [{"stamps": []}], "spans": [],
           "t0": 0.0, "t1": 1.0}
    assert retention_step_roofline.read(
        ctx, {"pattern": "x", "scope_pattern": "y", "trace_dir": "z"}) is None
    assert retention_decode_roofline.read(
        ctx, {"pattern": "x", "weight_bytes": 2}) is None


def test_costs_from_shapes_at_the_published_widths():
    cfg = json.load(open(CONFIG))
    assert retention_costs.phi_size(cfg) == 9216
    assert retention_costs.state_bytes_per_slot_layer(cfg) \
        == 8 * 9216 * 129 * 4 == 38043648
    assert retention_costs.state_bytes_per_slot(cfg) == 8 * 38043648
    assert retention_costs.layer_weights(cfg) == 330342400
    assert retention_costs.retention_step_min_bytes(cfg, 16) \
        == 2 * 16 * 8 * 38043648
    step = retention_costs.decode_step_min_bytes(cfg, 16, 2)
    # 8 layers and the head once (6.84 GB) and 2 x 4.87 GB of state
    assert 16.5e9 < step < 16.7e9
    assert 0.58 < retention_costs.retention_step_min_bytes(cfg, 16) / step \
        < 0.60
    # the program's own layout, from its own function
    from paddle_tpu.ops import power_retention as pr
    assert pr.state_bytes(8, 128, 128) \
        == retention_costs.state_bytes_per_slot_layer(cfg)


def test_the_configuration_holds_the_published_numbers():
    cfg = json.load(open(CONFIG))
    published = {"attention_bias": False, "head_dim": 128,
                 "hidden_act": "silu", "hidden_size": 5120,
                 "intermediate_size": 17408,
                 "max_position_embeddings": 32768, "max_window_layers": 40,
                 "model_type": "brumby", "num_attention_heads": 40,
                 "num_hidden_layers": 40, "num_key_value_heads": 8,
                 "rms_norm_eps": 1e-06, "rope_scaling": None,
                 "rope_theta": 1000000, "sliding_window": None,
                 "tie_word_embeddings": False, "use_sliding_window": False,
                 "vocab_size": 151936}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_layers"] and cfg["num_layers"] == 8
    assert cfg["published"] == {"num_layers": 40}
    assert cfg["storage"]["dtypes"][0] == cfg["weights_dtype"] == "bfloat16"
    assert cfg["engine"] == {"cache_layout": "recurrent", "slots": 16,
                             "buckets": [1024, 2048, 4096],
                             "max_queue": 256}
    assert cfg["max_len"] == 4864
    assumed = cfg["assumed"]
    assert assumed["degree"] == 2 and assumed["chunk_length"] == 128
    assert assumed["gate_memory"] == [64, 4096]
    from paddle_tpu.ops import power_retention as pr
    assert pr.CHUNK == assumed["chunk_length"]
    assert pr.PREFILL_BLOCK == assumed["prefill_block"]
    assert pr.STATE_CHUNK == assumed["state_chunk"]
    assert pr.EPS == assumed["normaliser_eps"]
