"""chip_smoke.py at toy width on the CPU, and the ways it must fail.

The real run needs a TPU (``python chip_smoke.py`` through the chip
tool).  Here its serve, train and kernel phases run through the
explicit ``--cpu-toy`` argument — kernels under the Pallas interpreter,
a result line that says ``"platform": "cpu"`` — and each failure the
script promises is shown: no TPU, a request that is not ``DONE``, a
recovery the engine absorbed, a forced kernel missing from the compiled
decode program, a phase that raises.  The compile-cache helper and the
peaks table (``profiler.DEVICE_PEAKS``, held equal to the benchmark's
own) are unit-tested beside it.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke
from tools import compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "benchmark"))  # harness.device


def _last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


def test_toy_run_passes_and_says_cpu(capsys):
    rc = chip_smoke.main(["--cpu-toy", "--phases", "serve,train,kernels"])
    out = capsys.readouterr().out
    assert rc == 0, out
    result = _last_json(out)
    assert result["ok"] is True
    assert result["device"]["platform"] == "cpu"
    assert "under the interpreter" in out       # kernels: interpret mode
    assert "all DONE, 0 recoveries, 0 failed" in out
    assert "preempt -> disk spill" in out and "tokens identical" in out
    assert "refused by name" in out


def test_toy_retention_phase_checks_both_forms(capsys):
    rc = chip_smoke.main(["--cpu-toy", "--phases", "retention"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert _last_json(out)["phases"] == ["retention"]
    assert "chunked prefill of 24 positions" in out
    assert "from empty prefill of 24 positions" in out
    for route in ("auto", "composition"):
        assert "step, route %s" % route in out
    assert "retention" in chip_smoke.PHASES


def test_without_the_toy_argument_a_cpu_machine_fails():
    # the sandbox exports JAX_PLATFORMS=cpu: a machine that inherits it
    # must produce a failure naming the platform, never a CPU run
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=_REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode not in (0, None)
    assert "platform is 'cpu'" in proc.stderr
    assert "JAX_PLATFORMS='cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout            # no result line


def test_a_phase_that_raises_fails_the_run(monkeypatch, capsys):
    def boom(*_a):
        raise RuntimeError("chip-only fault")

    monkeypatch.setattr(chip_smoke, "phase_train", boom)
    rc = chip_smoke.main(["--cpu-toy", "--phases", "train"])
    result = _last_json(capsys.readouterr().out)
    assert rc != 0 and result["ok"] is False
    assert "chip-only fault" in result["failed"]["train"]


_DONE = {"request_id": "r", "state": "DONE", "new_tokens": 2,
         "tokens": [1, 2], "error": None}
_CLEAN = {"serving_recoveries_total": 0.0,
          "serving_requests_failed_total": 0.0,
          "serving_ticks_stalled_total": 0.0}


@pytest.mark.parametrize("results,metrics,match", [
    ([dict(_DONE, state="FAILED", error="boom")], _CLEAN, "not DONE"),
    ([dict(_DONE, new_tokens=1, tokens=[1])], _CLEAN, "wanted 2"),
    ([_DONE], dict(_CLEAN, serving_recoveries_total=1.0),
     "absorbed a fault"),
    ([_DONE], dict(_CLEAN, serving_requests_failed_total=2.0),
     "absorbed a fault"),
    ([_DONE], {}, "missing from /metrics"),
])
def test_serving_outcome_check_fails(results, metrics, match):
    chip_smoke.check_serving_outcome([_DONE], _CLEAN, 2)    # the clean case
    with pytest.raises(chip_smoke.SmokeFailure, match=match):
        chip_smoke.check_serving_outcome(results, metrics, 2)


def test_forced_kernel_absent_from_the_program_fails():
    # a session that decodes on the composition: its compiled decode
    # program holds no kernel, whatever a route string might claim
    import paddle_tpu as pt
    from paddle_tpu.jit import DecodeSession

    sz = chip_smoke.sizes(toy=True)
    model = chip_smoke.build_lm(pt, sz["kernel_lm"])
    sess = DecodeSession(model, max_len=32, buckets=[8],
                         route="composition")
    sess.generate(np.zeros((1, 4), np.int32), 2)
    text = chip_smoke._decode_program_text(sess)
    for platform in ("cpu", "tpu"):
        with pytest.raises(chip_smoke.SmokeFailure, match="route='pallas'"):
            chip_smoke.check_kernel_in_program(text, platform, "x")
    chip_smoke.check_kernel_in_program("... tpu_custom_call ...", "tpu", "x")


def test_margin_gates():
    logits = np.array([[0.0, 1.0], [0.51, 0.5], [2.0, 0.0]])
    # step 1 is a near-tie: either token passes; steps 0 and 2 may not
    assert chip_smoke.check_greedy_against_logits(
        [1, 1, 0], logits, 0.1, "x") == 2
    with pytest.raises(chip_smoke.SmokeFailure, match="step 2"):
        chip_smoke.check_greedy_against_logits([1, 0, 1], logits, 0.1, "x")
    margins = [1.0, 0.01, 2.0]
    assert chip_smoke.check_same_until_near_tie(
        [5, 6, 7], [5, 6, 7], margins, 0.1, "x") == 3
    assert chip_smoke.check_same_until_near_tie(
        [5, 9, 9], [5, 6, 7], margins, 0.1, "x") == 1
    with pytest.raises(chip_smoke.SmokeFailure, match="part at step 2"):
        chip_smoke.check_same_until_near_tie(
            [5, 6, 9], [5, 6, 7], margins, 0.1, "x")


@pytest.mark.parametrize("env_dir", ["/somewhere/else", None])
def test_compile_cache_helper(monkeypatch, env_dir):
    import jax

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if env_dir is None:
        # unset: the checkout's .jax_cache, the directory conftest uses
        monkeypatch.delenv(compile_cache.ENV_VAR)
        want = os.path.join(_REPO, ".jax_cache")
        assert compile_cache.ensure_compile_cache() == want
        assert updates == [("jax_compilation_cache_dir", want)]
    else:
        # set: jax reads the variable itself, nothing is set in code
        monkeypatch.setenv(compile_cache.ENV_VAR, env_dir)
        assert compile_cache.ensure_compile_cache() == env_dir
        assert updates == []


def test_peaks_table_is_shared_and_raises_for_unknown_devices():
    from paddle_tpu.core.errors import NotFoundError
    from paddle_tpu.profiler import (DEVICE_PEAKS, StepTimer,
                                     device_peak_flops, device_peaks)

    assert DEVICE_PEAKS["TPU v5 lite"] == {"bf16_flops": 197e12,
                                           "hbm_bytes_per_sec": 819e9}
    assert device_peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(NotFoundError, match="no published peaks"):
        device_peaks("TPU v9 imaginary")
    with pytest.raises(NotFoundError, match="'cpu'"):
        device_peak_flops()         # this machine's device is not listed
    # timing steps needs no peak; asking for MFU on this device raises
    t = StepTimer(flops_per_step=1e9)
    with t:
        pass
    with pytest.raises(NotFoundError):
        t.mfu


def test_program_peaks_equal_the_benchmarks_own_row():
    # two tables state the chip's peaks: the program's and the one the
    # benchmark keeps for its rooflines.  An edit of either fails here.
    from harness import device
    from paddle_tpu.profiler import DEVICE_PEAKS

    assert sorted(DEVICE_PEAKS) == sorted(device.PEAKS)
    ours, theirs = DEVICE_PEAKS["TPU v5 lite"], device.peaks("TPU v5 lite")
    assert ours["bf16_flops"] == theirs["bf16_flops_per_s"] == 197e12
    assert ours["hbm_bytes_per_sec"] == theirs["hbm_bytes_per_s"] == 819e9


def test_expert_route_cost_rule_streams_at_the_tables_hbm_rate():
    from paddle_tpu.nn.functional import moe
    from paddle_tpu.profiler import DEVICE_PEAKS

    assert moe._HBM_BYTES_PER_S \
        == DEVICE_PEAKS["TPU v5 lite"]["hbm_bytes_per_sec"]


def test_a_pool_shaped_copy_in_the_decode_program_fails_on_the_tpu():
    # two lines of the decode program the v5e's compiler made of the
    # scatter that kept H as a window dimension (PERF.md, PR 26), the
    # second inside a fusion; then the write that replaced it
    moved = """
  %copy.46 = f32[512,16,32,128]{3,1,2,0:T(8,128)} copy(%cache_0__k.1), sharding={replicated}
  %fusion.5 = f32[512,16,32,128]{3,1,2,0:T(8,128)} fusion(%copy.46, %fusion.322), kind=kCustom
  ROOT %transpose.9 = f32[512,16,32,128]{3,2,1,0} transpose(%param_0.3), dimensions={0,2,1,3}
  %copy.47 = f32[16,16,1,128]{3,2,1,0} copy(%bitcast.7)
"""
    clean = """
  ROOT %scatter.9 = f32[512,16,32,128]{3,2,1,0:T(8,128)} scatter(%param_0.15, %custom-call.7, %transpose.108), update_window_dims={2}
  %fusion.5 = f32[512,16,32,128]{3,2,1,0:T(8,128)} fusion(%cache_0__k.1, %reshape.558), kind=kCustom
  %copy-done.37 = s8[512,16,32,128]{3,2,1,0:T(8,128)(4,1)S(1)} copy-done(%copy-start.37)
"""
    shape = (512, 16, 32, 128)
    assert chip_smoke.pool_shaped_moves(moved, shape) == \
        ["copy.46", "transpose.9"]
    assert chip_smoke.pool_shaped_moves(moved, (16, 16, 1, 128)) == \
        ["copy.47"]
    assert chip_smoke.pool_shaped_moves(clean, shape) == []
    with pytest.raises(chip_smoke.SmokeFailure, match="2 time"):
        chip_smoke.check_no_pool_moves(moved, shape, "tpu", "x")
    # the CPU backend donates nothing: its copy is said, not judged
    chip_smoke.check_no_pool_moves(moved, shape, "cpu", "x")
    chip_smoke.check_no_pool_moves(clean, shape, "tpu", "x")
