"""The comparison that decides ``correct`` is one that has been seen to
fail: the control (the reference in the nearest precision below the one the
configuration states) comes out as not correct at a size a test can hold,
and a run whose timed path is broken underneath reports ``correct`` false.
These drive ``run.main`` past its look for a chip, on the CPU, at toy
sizes; the limits they use are the toy configurations' own."""
import json
import os

import numpy as np
import pytest

import run as bench_run
from harness import correct, reference, serve, weights

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_manifest.json")


def run_cell(capsys, workload, seed=7, seconds=1.0, **hooks):
    rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", "0"],
                        require_chip=False, hooks=dict(hooks, manifest=TOY))
    assert rc == 0
    out = capsys.readouterr()
    result = json.loads(out.out.strip().splitlines()[-1])
    return result, out.err


def test_sound_training_run_is_correct_and_prints_what_it_compared(capsys):
    result, err = run_cell(capsys, "toy-pretrain")
    assert result["correct"] is True
    assert list(result)[-1] == "compared"
    for name in ("loss_step1_rel", "first_grad_norm_worst_leaf",
                 "param_change_norm_median_leaf"):
        assert result["compared"][name]["value"] <= \
            result["compared"][name]["limit"]
        assert "compared %s" % name in err
    assert result["metrics"]["train_tokens_per_s"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0


def frozen(step):
    """A step that returns its state unchanged: it runs, and then puts
    every parameter and optimizer state back."""
    def call(*batch):
        import jax
        import jax.numpy as jnp
        keep = lambda t: jax.tree.map(lambda x: jnp.array(x, copy=True), t)
        params = keep([p._value for p in step._binding.params])
        states = keep({k: dict(v)
                       for k, v in step._optimizer._states.items()})
        loss = step(*batch)
        for p, v in zip(step._binding.params, params):
            p._replace_value(v)
        step._optimizer._states.update(states)
        return loss
    return call


def half_batch(step):
    """A step that leaves out a part of the batch."""
    def call(ids, labels):
        import jax.numpy as jnp
        rows = ids.shape[0] // 2
        return step(jnp.concatenate([ids[:rows], ids[:rows]]),
                    jnp.concatenate([labels[:rows], labels[:rows]]))
    return call


@pytest.mark.parametrize("broken, fails", [
    (frozen, ("first_grad_norm_worst_leaf",
              "param_change_norm_median_leaf")),
    (half_batch, ("loss_step1_rel",)),
])
def test_broken_train_step_is_not_correct(capsys, broken, fails):
    result, err = run_cell(capsys, "toy-pretrain",
                           measure={"wrap_step": broken})
    assert result["correct"] is False
    for name in fails:
        c = result["compared"][name]
        assert c["value"] > c["limit"], name
    assert "NOT HELD" in err


def test_sound_serving_run_is_correct(capsys):
    result, _ = run_cell(capsys, "toy-closed", seconds=1.5)
    assert result["correct"] is True
    assert result["compared"]["tokens_checked"]["value"] >= 8
    assert result["failed"] == 0
    assert result["metrics"]["batch_tpot_p50_ms"]["value"] > 0


def test_token_altered_where_it_is_produced_is_not_correct(capsys,
                                                            monkeypatch):
    from paddle_tpu.inference.generation import GenerationPool
    sound = GenerationPool._deliver

    def altered(self, tok):
        tok = np.asarray(tok).copy()
        tok[::2] = (tok[::2] + 1) % 256     # every other slot's token
        return sound(self, tok)

    monkeypatch.setattr(GenerationPool, "_deliver", altered)
    result, err = run_cell(capsys, "toy-closed", seconds=1.5)
    assert result["correct"] is False
    c = result["compared"]["logit_gap_max"]
    assert c["value"] > c["limit"]
    assert "NOT HELD" in err


def test_programs_own_bfloat16_cache_is_not_correct(capsys, monkeypatch):
    """The control for a configuration that states float32 storage, as
    ``calibrate.py --engine cache_dtype=bfloat16`` runs it on the chip: the
    program with its own lower-precision path switched on.  Its tokens
    pass the logit limits (on the chip a float32 matmul multiplies
    bfloat16 operands anyway); what it stored does not."""
    sound = serve.build

    def build(cfg, seed):
        return sound(dict(cfg, engine=dict(cfg["engine"],
                                           cache_dtype="bfloat16")), seed)

    monkeypatch.setattr(serve, "build", build)
    result, err = run_cell(capsys, "toy-closed", seconds=1.5)
    assert result["correct"] is False
    c = result["compared"]["unstated_storage_bytes"]
    assert c["value"] > c["limit"] == 0
    assert "compared unstated_storage_bytes" in err and "NOT HELD" in err


def test_storage_census_counts_what_the_configuration_does_not_state():
    import jax
    import jax.numpy as jnp
    from harness import device
    stated = {"dtypes": ["float32", "int32"], "min_array_bytes": 1024}
    kept = [jnp.zeros((1024,), jnp.float32), jnp.zeros((4,), jnp.bfloat16)]
    before = device.storage_census(jax, stated)
    kept.append(jnp.zeros((1024,), jnp.bfloat16))
    kept.append(jnp.zeros((2048,), jnp.int8))
    after = device.storage_census(jax, stated)
    assert after["unstated_bytes"] - before["unstated_bytes"] == 4096
    assert after["by_type"]["bfloat16"] \
        - before["by_type"].get("bfloat16", 0) == 2048


def test_the_lower_precision_references_go_through_the_same_verdict():
    """``serving_numbers`` is what a run, ``calibrate.py`` and the chip's
    control readings all go through: gaps like the fp8 control's (chip, PR
    25: widest 0.33-0.51, mean 84-243e-4) fail gpt-1p3b's limits, gaps
    like the sound runs' (widest at most 0.034, mean at most 1.4e-4)
    hold, and so do gaps like the bfloat16 reference's (0.035-0.058,
    3.7-5.6e-4): the logit limits do not tell bfloat16 from the program,
    the census does."""
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "gpt-1p3b.json")) as f:
        lim = json.load(f)["limits"]
    fp32 = {"unstated_bytes": 0}
    numbers = lambda widest, mean, storage: correct.serving_numbers(
        [widest] + [(mean * 2000 - widest) / 1999] * 1999, 1960, storage,
        lim)
    assert correct.verdict(numbers(0.034, 1.4e-4, fp32))
    assert not correct.verdict(numbers(0.33, 84e-4, fp32))
    assert correct.verdict(numbers(0.058, 5.6e-4, fp32))
    low = numbers(0.058, 5.6e-4, {"unstated_bytes": 3221225472})
    assert not correct.verdict(low)
    assert [k for k, v in low.items() if not v["ok"]] == \
        ["unstated_storage_bytes"]


def test_traced_run_reads_the_client_where_nothing_traces(capsys):
    """With ``--trace 1`` the window the client's stamps are read over
    closes before the engine's tracer and the profiler are switched on;
    the spans and the trace cover the stretch after it."""
    rc = bench_run.main(["--workload", "toy-closed", "--seed", "9",
                         "--seconds", "1", "--trace", "1"],
                        require_chip=False, hooks={"manifest": TOY})
    assert rc == 0
    out = capsys.readouterr().out
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True and "breakdown" in result
    for name in ("tpot_p90_ms.batch", "serve_tokens_per_s.batch",
                 "batch_occupancy.batch", "submit_wait_p90_ms.batch"):
        assert result["metrics"][name]["value"] > 0, name
    assert result["metrics"]["batch_occupancy.batch"]["value"] <= 100.0
    assert result["device"]["window_s"] > 0


def test_untraced_run_also_reads_the_per_layer_metrics_of_the_host_clock(
        capsys):
    result, _ = run_cell(capsys, "toy-closed", seconds=1.5)
    assert set(result["per_layer_host_clock"]) == {
        "tpot_p90_ms.batch", "serve_tokens_per_s.batch",
        "batch_occupancy.batch"}
    assert list(result)[-1] == "compared"


def toy(name):
    loaded = bench_run.load_cell(name, TOY)
    return loaded["cfg"], loaded["traffic"]


def test_training_control_in_fp8_fails_one_number_at_least():
    """The reference in fp8 (e4m3 operands, e5m2 cotangents) against the
    float32 reference, as ``calibrate.py --control 1`` reads it on the chip:
    held to the limits, it is not correct."""
    from harness import train
    cfg, tr = toy("toy-pretrain")
    failed = 0
    for seed in (1, 2, 3):
        batches = train.make_batches(cfg, tr, seed)
        ref = correct.reference_training(cfg, tr, seed, batches)
        low = correct.reference_training(cfg, tr, seed, batches, mode="fp8")
        g, _, _ = correct.worst_leaf(low[1], ref[1])
        _, _, c = correct.worst_leaf(low[2], ref[2])
        loss = max(abs(a - b) / abs(b) for a, b in zip(low[0], ref[0]))
        lim = cfg["limits"]
        failed += (g > lim["first_grad_rel"] or c > lim["change_rel"]
                   or loss > lim["loss_rel"])
        assert g > 0 and c > 0
    assert failed == 3


def test_serving_control_in_bfloat16_reads_wider_gaps():
    """At each position of the same prompts and tokens, the token bfloat16
    puts first lies below the float32 reference's best by more than any
    token the sound path served (which, float32 on the CPU, lies at 0)."""
    cfg, tr = toy("toy-closed")
    import jax.numpy as jnp
    sizes = serve.model_sizes(cfg)
    widest = 0.0
    for seed in (1, 2, 3):
        w = weights.make_weights(sizes, seed)
        rng = np.random.default_rng(seed)
        ids = jnp.asarray(rng.integers(0, sizes["vocab_size"],
                                       reference.ROWS), jnp.int32)
        ref = reference.logits_rows(w, ids, 0, sizes["num_heads"],
                                    "float32")
        low = reference.logits_rows(w, ids, 0, sizes["num_heads"],
                                    "bfloat16")
        gap, _ = reference.gaps_below_best(ref, jnp.argmax(low, -1))
        widest = max(widest, float(jnp.max(gap[:128])))
        sound, same = reference.gaps_below_best(ref, jnp.argmax(ref, -1))
        assert float(jnp.max(sound)) == 0.0 and bool(jnp.all(same))
    assert widest > cfg["limits"]["logit_gap_max"]


def test_reference_is_the_programs_function_at_float32():
    """The plain reference and the program's uncached forward agree on the
    CPU, where both multiply in float32: the reference describes the model
    the program runs, with the benchmark's weights in both."""
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import TransformerLM
    cfg, _ = toy("toy-closed")
    sizes = serve.model_sizes(cfg)
    model = TransformerLM(**sizes, dropout=0.0)
    model.eval()
    serve.load_weights(model, cfg, 5)
    ids = np.random.default_rng(0).integers(0, 256, (1, 24)).astype(np.int32)
    with pt.no_grad():
        theirs = np.asarray(model(pt.to_tensor(ids)).value)[0]
    padded = np.zeros(reference.ROWS, np.int32)
    padded[:24] = ids[0]
    ours = np.asarray(reference.logits_rows(
        weights.make_weights(sizes, 5), jnp.asarray(padded), 0,
        sizes["num_heads"], "float32"))[:24]
    assert np.abs(ours - theirs).max() < 2e-5


def test_no_chip_means_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", "toy-pretrain", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       hooks={"manifest": TOY})
    assert e.value.code == 2
    out = capsys.readouterr()
    assert "refusing to run" in out.err
    assert not out.out.strip().startswith("{")
