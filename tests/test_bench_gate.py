"""Evidence discipline for the bench harness's leg records.

An early round published a ResNet leg that timed the host->device
transfer instead of the chip. These tests pin the structural fix:
``bench._leg_promotable`` refuses any leg record that cannot prove it
measured compute — no input-staging stamp, no transfer-bias note, or a
stale ResNet MFU convention.

Reference analog: the CI perf-gate discipline of
/root/reference/tools/test_model_benchmark.sh:22-44 (a PR number is only
comparable when measured under the same conditions as develop's).
"""
import pytest

import bench


def test_unstamped_leg_rejected():
    ok, why = bench._leg_promotable("mnist_lenet", {"imgs_per_sec": 5610.0})
    assert not ok and "input_staged" in why


def test_invalid_reason_rejected():
    ok, why = bench._leg_promotable(
        "mnist_lenet", {"imgs_per_sec": 5610.0, "input_staged": True,
                        "invalid_reason": "transfer-bound"})
    assert not ok and why == "transfer-bound"


def test_stale_resnet_convention_rejected():
    leg = {"imgs_per_sec": 985.0, "mfu": 0.09, "input_staged": True,
           "mfu_convention": 1}
    ok, why = bench._leg_promotable("resnet50", leg)
    assert not ok and "mfu_convention" in why


def test_staged_current_convention_resnet_promotes():
    leg = {"imgs_per_sec": 1483.2, "mfu": 0.1847, "input_staged": True,
           "mfu_convention": bench.RESNET_MFU_CONVENTION}
    ok, why = bench._leg_promotable("resnet50", leg)
    assert ok, why


def test_transfer_note_leg_promotes():
    # LM legs with negligible, documented transfer bias stand
    leg = {"tokens_per_sec": 120062.0, "mfu": 0.43,
           "transfer_note": "~8 ms of a 171 ms step; <5% bias"}
    assert bench._leg_promotable("bert", leg)[0]


def test_decode_leg_without_cache_layout_rejected():
    # a decode number that cannot say which cache layout it measured
    # (dense vs paged differ in reachable HBM by up to max_len/tokens)
    # must never be promoted
    leg = {"tokens_per_sec": 500.0, "transfer_note": "negligible",
           "batch1": {"per_token_s": 0.002, "decode_tokens_per_sec": 500.0}}
    ok, why = bench._leg_promotable("decode", leg)
    assert not ok and "cache_layout" in why


def test_decode_leg_without_cache_dtype_rejected():
    # the int8 analog of the layout rule: a decode number that cannot
    # say whether it streamed the fp32 or the quantized int8 cache
    # (~4x fewer HBM bytes per step) must never be promoted
    leg = {"tokens_per_sec": 500.0, "transfer_note": "negligible",
           "dense_batch1": {"per_token_s": 0.002, "cache_layout": "dense"}}
    ok, why = bench._leg_promotable("decode", leg)
    assert not ok and "cache_dtype" in why


def test_decode_leg_with_layout_and_dtype_promotes():
    leg = {"tokens_per_sec": 500.0, "transfer_note": "negligible",
           "dense_fp32_batch1": {"per_token_s": 0.002,
                                 "cache_layout": "dense",
                                 "cache_dtype": "float32"},
           "paged_int8_batch1": {"per_token_s": 0.002,
                                 "cache_layout": "paged",
                                 "cache_dtype": "int8"}}
    ok, why = bench._leg_promotable("decode", leg)
    assert ok, why


def test_decode_leg_no_timed_subleg_rejected():
    leg = {"tokens_per_sec": 500.0, "transfer_note": "negligible"}
    ok, why = bench._leg_promotable("decode", leg)
    assert not ok and "cache_layout" in why


def test_kernel_routed_leg_without_bandwidth_stamp_rejected():
    # a fused-kernel (§5l) number without its sustained-bandwidth stamp
    # (tok/s x compiler bytes/token) cannot say what the kernel bought
    # — the roofline figure it exists to move is its provenance
    leg = {"tokens_per_sec": 500.0, "transfer_note": "negligible",
           "paged_fp32_batch8_pallas": {"per_token_s": 0.002,
                                        "cache_layout": "paged",
                                        "cache_dtype": "float32",
                                        "decode_route": "pallas"}}
    ok, why = bench._leg_promotable("decode", leg)
    assert not ok and "bandwidth_util_bytes_per_sec" in why
    # a None stamp (cost analysis unavailable) is just as unpromotable
    leg["paged_fp32_batch8_pallas"][
        "bandwidth_util_bytes_per_sec"] = None
    ok, why = bench._leg_promotable("decode", leg)
    assert not ok and "bandwidth_util_bytes_per_sec" in why


def test_kernel_routed_leg_with_bandwidth_stamp_promotes():
    leg = {"tokens_per_sec": 500.0, "transfer_note": "negligible",
           "paged_fp32_batch8_pallas": {
               "per_token_s": 0.002, "cache_layout": "paged",
               "cache_dtype": "float32", "decode_route": "pallas",
               "cost_bytes_per_token": 1.0e6,
               "bandwidth_util_bytes_per_sec": 5.0e8}}
    ok, why = bench._leg_promotable("decode", leg)
    assert ok, why


def test_composition_routed_leg_needs_no_bandwidth_stamp():
    # the gate bites KERNEL-routed legs only: composition/auto legs
    # (and legacy records predating the stamp) promote as before
    leg = {"tokens_per_sec": 500.0, "transfer_note": "negligible",
           "dense_fp32_batch1": {"per_token_s": 0.002,
                                 "cache_layout": "dense",
                                 "cache_dtype": "float32",
                                 "decode_route": "auto"}}
    assert bench._leg_promotable("decode", leg)[0]


def test_kernel_routed_serving_and_speculative_gated_too():
    # the same stamp rule on the serving and speculative leg families
    serving = {"tokens_per_sec": 100.0, "transfer_note": "negligible",
               "batch8": {"ttft_p50_s": 0.01, "cache_layout": "dense",
                          "cache_dtype": "float32",
                          "decode_route": "pallas"}}
    ok, why = bench._leg_promotable("serving", serving)
    assert not ok and "bandwidth_util_bytes_per_sec" in why
    spec = {"tokens_per_sec": 100.0, "transfer_note": "negligible",
            "selfdraft_batch4": {"tokens_per_sec": 100.0,
                                 "cache_layout": "dense",
                                 "cache_dtype": "float32",
                                 "decode_route": "pallas",
                                 "acceptance_rate": 0.9}}
    ok, why = bench._leg_promotable("speculative", spec)
    assert not ok and "bandwidth_util_bytes_per_sec" in why


def test_serving_leg_without_cache_layout_rejected():
    # a serving TTFT/tokens-per-sec number inherits the decode leg's
    # provenance rule: no cache_layout stamp, no promotion
    leg = {"tokens_per_sec": 100.0, "transfer_note": "negligible",
           "batch1": {"ttft_p50_s": 0.01, "tokens_per_sec": 100.0}}
    ok, why = bench._leg_promotable("serving", leg)
    assert not ok and "cache_layout" in why


def test_serving_leg_without_cache_dtype_rejected():
    leg = {"tokens_per_sec": 100.0, "transfer_note": "negligible",
           "batch1": {"ttft_p50_s": 0.01, "cache_layout": "dense"}}
    ok, why = bench._leg_promotable("serving", leg)
    assert not ok and "cache_dtype" in why


def test_serving_leg_with_layout_and_dtype_promotes():
    leg = {"tokens_per_sec": 100.0, "transfer_note": "negligible",
           "batch1": {"ttft_p50_s": 0.01, "ttft_p95_s": 0.02,
                      "cache_layout": "dense",
                      "cache_dtype": "float32"}}
    ok, why = bench._leg_promotable("serving", leg)
    assert ok, why


def test_serving_leg_no_timed_subleg_rejected():
    leg = {"tokens_per_sec": 100.0, "transfer_note": "negligible"}
    ok, why = bench._leg_promotable("serving", leg)
    assert not ok and "cache_layout" in why


def test_serving_leg_trace_overhead_gate():
    # the §5g tracing contract measured, not asserted: a serving leg
    # whose tracing-on tick time exceeds tracing-off by >3% measured
    # the recorder, not the scheduler — unpromotable
    base = {"tokens_per_sec": 100.0, "transfer_note": "negligible",
            "batch8": {"ttft_p50_s": 0.01, "cache_layout": "dense",
                       "cache_dtype": "float32"}}
    ok, why = bench._leg_promotable(
        "serving", dict(base, trace_overhead_pct=1.4))
    assert ok, why
    ok, why = bench._leg_promotable(
        "serving", dict(base, trace_overhead_pct=3.0))
    assert ok, why  # the bound is inclusive: exactly 3% promotes
    ok, why = bench._leg_promotable(
        "serving", dict(base, trace_overhead_pct=7.2))
    assert not ok and "trace overhead" in why
    # legacy records predating the stamp keep promoting
    assert bench._leg_promotable("serving", base)[0]


def test_speculative_leg_missing_acceptance_rejected():
    # a speculative tokens/s number without its acceptance-rate stamp
    # cannot say whether it measured a draft that mostly landed or
    # mostly wasted work — unpromotable
    leg = {"tokens_per_sec": 800.0, "transfer_note": "negligible",
           "selfdraft_batch8": {"tokens_per_sec": 800.0,
                                "cache_layout": "dense",
                                "cache_dtype": "float32"}}
    ok, why = bench._leg_promotable("speculative", leg)
    assert not ok and "acceptance_rate" in why


def test_speculative_leg_missing_layout_rejected():
    leg = {"tokens_per_sec": 800.0, "transfer_note": "negligible",
           "selfdraft_batch8": {"tokens_per_sec": 800.0,
                                "acceptance_rate": 1.0}}
    ok, why = bench._leg_promotable("speculative", leg)
    assert not ok and "cache_layout" in why


def test_speculative_leg_with_stamps_promotes():
    # the plain_* baseline sub-leg drafts nothing and is exempt from
    # the acceptance stamp; speculative sub-legs carry it
    leg = {"tokens_per_sec": 900.0, "transfer_note": "negligible",
           "plain_batch8": {"tokens_per_sec": 700.0,
                            "cache_layout": "dense",
                            "cache_dtype": "float32"},
           "selfdraft_batch8": {"tokens_per_sec": 900.0,
                                "cache_layout": "dense",
                                "cache_dtype": "float32",
                                "acceptance_rate": 0.97}}
    ok, why = bench._leg_promotable("speculative", leg)
    assert ok, why


def test_speculative_leg_no_timed_subleg_rejected():
    leg = {"tokens_per_sec": 900.0, "transfer_note": "negligible"}
    ok, why = bench._leg_promotable("speculative", leg)
    assert not ok


@pytest.mark.slow
def test_live_speculative_leg_passes_its_own_gate():
    """The leg bench.py actually emits must satisfy the gate it ships
    with (a CPU-smoke run of the real leg, not a hand-built dict) —
    slow-marked: it builds three pools over two fresh models (~6s,
    over the conftest's 5s tier-1 line); the gate LOGIC stays covered
    by the fast hand-built-dict cases above."""
    import jax

    import paddle_tpu as pt

    leg = bench.bench_speculative(pt, jax, False)
    ok, why = bench._leg_promotable("speculative", leg)
    assert ok, why
    for key in ("selfdraft_batch4", "smalldraft_batch4"):
        sub = leg[key]
        assert 0.0 <= sub["acceptance_rate"] <= 1.0
        assert sub["tokens_per_sec"] > 0
        assert sub["draft_time_s"] >= 0 and sub["verify_time_s"] >= 0
    # the self-draft guesses ARE the target's continuations
    assert leg["selfdraft_batch4"]["acceptance_rate"] > 0.9


def test_serving_faults_leg_gate():
    """The robustness leg's structural gate: a recovery wall time whose
    greedy survivors lost tokens measured a BROKEN recovery and must
    never promote; missing cache stamps reject like every serving
    leg."""
    good = {"input_staged": False, "transfer_note": "host-side rebuild",
            "faulted": {"cache_layout": "paged",
                        "cache_dtype": "float32",
                        "recovery_wall_s": 0.01, "tokens_lost": 0}}
    ok, why = bench._leg_promotable("serving_faults", good)
    assert ok, why
    lossy = {"input_staged": False, "transfer_note": "x",
             "faulted": dict(good["faulted"], tokens_lost=3)}
    ok, why = bench._leg_promotable("serving_faults", lossy)
    assert not ok and "lost tokens" in why and "faulted" in why
    # a leg that never stamped tokens_lost cannot claim losslessness
    unstamped = {"input_staged": False, "transfer_note": "x",
                 "faulted": {"cache_layout": "paged",
                             "cache_dtype": "float32",
                             "recovery_wall_s": 0.01}}
    assert not bench._leg_promotable("serving_faults", unstamped)[0]
    # missing cache provenance rejects like the other serving legs
    nostamp = {"input_staged": False, "transfer_note": "x",
               "faulted": {"recovery_wall_s": 0.01, "tokens_lost": 0}}
    ok, why = bench._leg_promotable("serving_faults", nostamp)
    assert not ok and "cache_layout" in why


@pytest.mark.slow
def test_live_serving_faults_leg_passes_its_own_gate():
    """The leg bench.py actually emits must satisfy its own gate (a
    CPU-smoke run of the real leg) — slow-marked: it runs the traffic
    twice plus a recovery, several seconds of compile+decode."""
    import jax

    import paddle_tpu as pt

    leg = bench.bench_serving_faults(pt, jax, False)
    ok, why = bench._leg_promotable("serving_faults", leg)
    assert ok, why
    sub = leg["faulted"]
    assert sub["tokens_lost"] == 0
    assert sub["requests_recovered"] == sub["requests"]
    assert sub["requests_failed"] == 0
    assert sub["recovery_wall_s"] > 0
    assert sub["blocks_reclaimed"] is True


def test_resnet_mfu_formula_pinned():
    """The one shared MFU formula (2 FLOPs/MAC, fwd + ~2x bwd): batch
    128 at 0.0863 s a step against a 197 TFLOP/s peak evaluates to
    0.1847 — pinning the convention the gate enforces."""
    assert bench.RESNET50_FWD_FLOPS == 2 * 4.089e9
    got = bench.resnet50_mfu(128, 0.0863, 197e12)
    assert abs(got - 0.1847) < 2e-4, got


def test_serving_prefix_leg_gate():
    """The prefix-sharing leg's structural gate: a sharing-on sub-leg
    without its prefix_hit_rate stamp cannot tell a measured sharing
    win from plain chunked prefill and must never promote; the
    sharing-off sub-leg is exempt (its index is disabled by
    construction) but still needs the cache stamps."""
    good = {"input_staged": False, "transfer_note": "same traffic",
            "sharing_on": {"cache_layout": "paged",
                           "cache_dtype": "float32",
                           "ttft_p50_s": 0.01, "prefix_hit_rate": 0.6},
            "sharing_off": {"cache_layout": "paged",
                            "cache_dtype": "float32",
                            "ttft_p50_s": 0.02}}
    ok, why = bench._leg_promotable("serving_prefix", good)
    assert ok, why
    unhit = {"input_staged": False, "transfer_note": "x",
             "sharing_on": {"cache_layout": "paged",
                            "cache_dtype": "float32",
                            "ttft_p50_s": 0.01},
             "sharing_off": dict(good["sharing_off"])}
    ok, why = bench._leg_promotable("serving_prefix", unhit)
    assert not ok and "prefix_hit_rate" in why and "sharing_on" in why
    # missing cache provenance rejects like the other serving legs
    nostamp = {"input_staged": False, "transfer_note": "x",
               "sharing_on": {"ttft_p50_s": 0.01,
                              "prefix_hit_rate": 0.6}}
    ok, why = bench._leg_promotable("serving_prefix", nostamp)
    assert not ok and "cache_layout" in why


def test_serving_overload_leg_gate():
    """The overload leg's structural gate: the degraded sub-leg must
    say what the ladder DID (preempt/resume/spill stamps), both
    sub-legs must carry the SLO burn stamp, and the usual cache
    provenance applies — a closed-loop claim without the loop's own
    evidence must never promote."""
    sub = {"cache_layout": "paged", "cache_dtype": "float32",
           "ttft_p99_high_s": 0.02, "slo_ttft_burn_slow_max": 4.0}
    good = {"input_staged": False, "transfer_note": "same traffic",
            "degrade_on": dict(sub, preemptions=2, resumes=2,
                               spill_bytes_total=4096),
            "degrade_off": dict(sub)}
    ok, why = bench._leg_promotable("serving_overload", good)
    assert ok, why
    # degraded sub-leg without the ladder's own evidence: rejected
    unproven = {"input_staged": False, "transfer_note": "x",
                "degrade_on": dict(sub),
                "degrade_off": dict(sub)}
    ok, why = bench._leg_promotable("serving_overload", unproven)
    assert not ok and "preempt" in why and "degrade_on" in why
    # either sub-leg missing the burn stamp: rejected
    unburned = {"input_staged": False, "transfer_note": "x",
                "degrade_on": dict(good["degrade_on"]),
                "degrade_off": {"cache_layout": "paged",
                                "cache_dtype": "float32",
                                "ttft_p99_high_s": 0.03}}
    ok, why = bench._leg_promotable("serving_overload", unburned)
    assert not ok and "slo_ttft_burn_slow_max" in why
    # missing cache provenance rejects like every serving leg
    nostamp = {"input_staged": False, "transfer_note": "x",
               "degrade_on": {"ttft_p99_high_s": 0.02,
                              "preemptions": 1, "resumes": 1,
                              "spill_bytes_total": 1,
                              "slo_ttft_burn_slow_max": 1.0}}
    ok, why = bench._leg_promotable("serving_overload", nostamp)
    assert not ok and "cache_layout" in why


@pytest.mark.slow
def test_live_serving_overload_leg_passes_its_own_gate():
    """The leg bench.py actually emits must satisfy its own gate AND
    the §5j acceptance contract: high-priority p99 TTFT strictly
    better with degradation on, on identical traffic, with the ladder
    provably engaged — slow-marked (calibration + both modes)."""
    import jax

    import paddle_tpu as pt

    leg = bench.bench_serving_overload(pt, jax, False)
    ok, why = bench._leg_promotable("serving_overload", leg)
    assert ok, why
    on, off = leg["degrade_on"], leg["degrade_off"]
    # the ladder ENGAGED on: preemptions happened, and off did nothing
    assert on["preemptions"] >= 1
    assert off["preemptions"] == 0
    # the acceptance headline: strictly better high-priority p99 TTFT
    assert on["ttft_p99_high_s"] < off["ttft_p99_high_s"]
    assert leg["ttft_p99_high_improvement_pct"] > 0
    # the burn drop is stamped (the SLO plane saw the same story)
    assert "slo_burn_drop" in leg
    assert on["slo_ttft_burn_slow_max"] <= off["slo_ttft_burn_slow_max"]


@pytest.mark.slow
def test_live_serving_prefix_leg_passes_its_own_gate():
    """The leg bench.py actually emits must satisfy its own gate (a
    CPU-smoke run of the real leg) — slow-marked: it runs the zipf
    traffic three times (calibration + both modes)."""
    import jax

    import paddle_tpu as pt

    leg = bench.bench_serving_prefix(pt, jax, False)
    ok, why = bench._leg_promotable("serving_prefix", leg)
    assert ok, why
    on, off = leg["sharing_on"], leg["sharing_off"]
    # the zipf corpus MUST produce hits, and the off leg must not (its
    # index is disabled — a nonzero off hit rate means the flag leaks)
    assert on["prefix_hit_rate"] > 0
    assert off["prefix_hit_rate"] == 0
    assert on["prefix_blocks_saved_bytes"] > 0
    # both modes ran under the same calibrated TTFT promise
    assert leg["slo_ttft_threshold_s"] > 0
    assert "slo_ttft_burn_slow" in on and "slo_ttft_burn_slow" in off


def test_serving_sharded_leg_gate():
    """The sharded leg's structural gate: every mesh sub-leg must
    carry scaling_efficiency AND the per-shard compiler cost / HBM
    stamps (the mesh_1x1 baseline is exempt — its scaling is
    definitionally 1.0), and the usual cache provenance applies."""
    base = {"cache_layout": "paged", "cache_dtype": "float32",
            "tokens_per_sec": 1000.0}
    mesh = dict(base, scaling_efficiency=0.8,
                cost_flops_per_shard=1e6, cost_bytes_per_shard=1e6,
                cost_hbm_reserved_per_shard=1e6,
                kv_resident_bytes_per_shard=4096)
    good = {"input_staged": False, "transfer_note": "same loop per mesh",
            "mesh_1x1": dict(base), "mesh_2x1": dict(mesh)}
    ok, why = bench._leg_promotable("serving_sharded", good)
    assert ok, why
    # a mesh sub-leg without its scaling stamp: rejected
    unscaled = {"input_staged": False, "transfer_note": "x",
                "mesh_1x1": dict(base),
                "mesh_2x1": dict(mesh, scaling_efficiency=None)}
    ok, why = bench._leg_promotable("serving_sharded", unscaled)
    assert not ok and "scaling" in why and "mesh_2x1" in why
    # a mesh sub-leg without per-shard cost attribution: rejected
    uncosted = {"input_staged": False, "transfer_note": "x",
                "mesh_1x1": dict(base),
                "mesh_2x1": dict(mesh, cost_hbm_reserved_per_shard=None)}
    ok, why = bench._leg_promotable("serving_sharded", uncosted)
    assert not ok and "per-shard" in why
    # missing cache provenance rejects like every serving leg
    nostamp = {"input_staged": False, "transfer_note": "x",
               "mesh_2x1": {k: v for k, v in mesh.items()
                            if k != "cache_layout"}}
    ok, why = bench._leg_promotable("serving_sharded", nostamp)
    assert not ok and "cache_layout" in why
    # a baseline-only leg (1-device run skipped every real mesh)
    # measured no sharding at all: rejected, never a hollow record
    baseline_only = {"input_staged": False, "transfer_note": "x",
                     "mesh_1x1": dict(base)}
    ok, why = bench._leg_promotable("serving_sharded", baseline_only)
    assert not ok and "no sharded mesh sub-leg" in why
    # a QUANTIZED-collective sub-leg (§5r) must stamp its numeric
    # traced-shape wire bytes per token — the byte column is the
    # number's provenance
    qmesh = dict(mesh, collective_quant="int8",
                 collective_bytes_per_token=576.0,
                 collective_dense_bytes_per_token=2048.0)
    qgood = {"input_staged": False, "transfer_note": "x",
             "mesh_1x1": dict(base), "mesh_1x2_qint8": dict(qmesh)}
    ok, why = bench._leg_promotable("serving_sharded", qgood)
    assert ok, why
    for bad_bpt in (None, True):
        qbad = {"input_staged": False, "transfer_note": "x",
                "mesh_1x1": dict(base),
                "mesh_1x2_qint8": dict(
                    qmesh, collective_bytes_per_token=bad_bpt)}
        ok, why = bench._leg_promotable("serving_sharded", qbad)
        assert not ok and "collective_bytes_per_token" in why \
            and "mesh_1x2_qint8" in why
    # a DENSE mesh sub-leg carries no quantized-byte obligation (mp=1
    # meshes have no mp collectives at all): the plain gate above
    # already passed `good` without the column


@pytest.mark.slow
def test_live_serving_sharded_leg_passes_its_own_gate():
    """The leg bench.py actually emits must satisfy its own gate — a
    real subprocess run under 8 forced host devices; slow-marked (it
    compiles four pools in a cold child process)."""
    import jax

    import paddle_tpu as pt

    leg = bench.bench_serving_sharded(pt, jax, False)
    ok, why = bench._leg_promotable("serving_sharded", leg)
    assert ok, why
    # the child saw the forced devices and measured real meshes
    assert leg["devices_available"] >= 4
    assert "mesh_1x1" in leg and "mesh_2x2" in leg
    for name in ("mesh_2x1", "mesh_1x2", "mesh_2x2"):
        sub = leg[name]
        assert sub["scaling_efficiency"] is not None
        # per-shard HBM shrinks under dp (the block pool is split)
        if sub["mesh_dp"] > 1:
            assert sub["kv_resident_bytes_per_shard"] < \
                leg["mesh_1x1"]["kv_resident_bytes"]
    # the quantized sub-legs (§5r) ran the same traffic and stamped
    # traced wire bytes strictly below the dense ring's
    for name in ("mesh_1x2_qint8", "mesh_2x2_qint8"):
        sub = leg[name]
        assert sub["collective_quant"] == "int8"
        assert sub["collective_bytes_per_token"] \
            < sub["collective_dense_bytes_per_token"]
        dense_twin = leg[name.replace("_qint8", "")]
        assert sub["collective_bytes_per_token"] \
            < dense_twin["collective_bytes_per_token"]


def test_serving_restart_gate_structural_cases():
    """The §5m durability leg: an RTO whose survivors lost tokens, or
    that replayed an empty journal, is structurally unpromotable — and
    the usual cache-provenance stamps apply."""
    def leg(**over):
        sub = {"cache_layout": "paged", "cache_dtype": "float32",
               "restore_rto_s": 0.02, "requests_replayed": 8,
               "tokens_lost": 0}
        sub.update(over)
        return {"input_staged": False,
                "transfer_note": "host-side replay", "restart": sub}

    ok, why = bench._leg_promotable("serving_restart", leg())
    assert ok, why
    # lossy restore: byte-identity is the contract, never promotable
    ok, why = bench._leg_promotable("serving_restart",
                                    leg(tokens_lost=3))
    assert not ok and "lost tokens" in why
    # an UNSTAMPED tokens_lost defaults to lossy (absence of evidence
    # is not evidence of byte-identity)
    bad = leg()
    del bad["restart"]["tokens_lost"]
    ok, why = bench._leg_promotable("serving_restart", bad)
    assert not ok and "lost tokens" in why
    # an RTO over an empty journal measured file I/O, not recovery
    ok, why = bench._leg_promotable("serving_restart",
                                    leg(requests_replayed=0))
    assert not ok and "replayed no requests" in why
    # cache provenance applies like every serving leg
    bad = leg()
    del bad["restart"]["cache_dtype"]
    ok, why = bench._leg_promotable("serving_restart", bad)
    assert not ok and "cache_layout/cache_dtype" in why


def test_serving_disagg_gate_structural_cases():
    """The §5n disaggregation leg: a record missing either fused-vs-
    disagg improvement column, one whose hand-offs lost tokens, or one
    whose hand-off never fired is structurally unpromotable — and the
    usual cache-provenance stamps apply to both timed sub-legs."""
    def leg(**over):
        sub = {"cache_layout": "paged", "cache_dtype": "float32",
               "ttft_p95_s": 0.02, "itl_p95_s": 0.005}
        out = {"input_staged": False,
               "transfer_note": "identical traffic on both sub-legs",
               "fused": dict(sub), "disagg": dict(sub),
               "kv_transfers": 8, "kv_transfer_bytes": 1 << 20,
               "tokens_lost": 0,
               "ttft_p95_improvement_pct": 12.0,
               "itl_p95_improvement_pct": 7.5}
        out.update(over)
        return out

    ok, why = bench._leg_promotable("serving_disagg", leg())
    assert ok, why
    # a record that cannot compare against the fused engine claims
    # nothing — EITHER missing improvement column rejects
    ok, why = bench._leg_promotable(
        "serving_disagg", leg(ttft_p95_improvement_pct=None))
    assert not ok and "improvement" in why
    bad = leg()
    del bad["itl_p95_improvement_pct"]
    ok, why = bench._leg_promotable("serving_disagg", bad)
    assert not ok and "improvement" in why
    # a lossy hand-off broke the byte-identity contract
    ok, why = bench._leg_promotable("serving_disagg",
                                    leg(tokens_lost=2))
    assert not ok and "lost tokens" in why
    # an UNSTAMPED tokens_lost defaults to lossy
    bad = leg()
    del bad["tokens_lost"]
    ok, why = bench._leg_promotable("serving_disagg", bad)
    assert not ok and "lost tokens" in why
    # zero hand-offs measured two idle engines wearing the tier roles
    ok, why = bench._leg_promotable("serving_disagg",
                                    leg(kv_transfers=0))
    assert not ok and "no K/V hand-offs" in why
    # cache provenance applies to both timed sub-legs
    bad = leg()
    del bad["disagg"]["cache_dtype"]
    ok, why = bench._leg_promotable("serving_disagg", bad)
    assert not ok and "cache_layout/cache_dtype" in why


@pytest.mark.slow
def test_live_serving_disagg_leg_passes_its_own_gate():
    """The leg bench.py actually emits must satisfy its own gate AND
    the §5n acceptance contract: every request crossed the transfer,
    zero tokens lost vs the fused reference, both improvement columns
    stamped — slow-marked (it runs the zipf traffic through the fused
    engine AND the two-tier pair, compiling both tiers)."""
    import jax

    import paddle_tpu as pt

    leg = bench.bench_serving_disagg(pt, jax, False)
    ok, why = bench._leg_promotable("serving_disagg", leg)
    assert ok, why
    assert leg["tokens_lost"] == 0
    assert leg["kv_transfers"] == leg["disagg"]["requests"]
    assert leg["kv_transfer_bytes"] > 0
    assert leg["disagg"]["handoffs_degraded"] == 0
    assert isinstance(leg["ttft_p95_improvement_pct"], float)
    assert isinstance(leg["itl_p95_improvement_pct"], float)


def test_serving_fleet_gate_structural_cases():
    """The §5o fleet leg: a multi-engine sub-leg without its scaling
    stamp, a chaos sub-leg without its migration RTO (or that migrated
    nothing), any lost token, or a missing affinity hit rate is
    structurally unpromotable — and the cache-provenance stamps apply
    to every timed sub-leg."""
    def leg(**over):
        def sub(**s):
            d = {"cache_layout": "paged", "cache_dtype": "float32",
                 "tokens_per_sec": 500.0, "ttft_p95_s": 0.02}
            d.update(s)
            return d

        out = {"input_staged": False,
               "transfer_note": "identical traffic on every sub-leg",
               "engines_1": sub(),
               "engines_2": sub(scaling_efficiency=0.5, tokens_lost=0),
               "engines_4": sub(scaling_efficiency=0.3, tokens_lost=0),
               "chaos": sub(migration_rto_s=0.05, requests_migrated=3,
                            tokens_lost=0),
               "prefix_affinity_hit_rate": 0.6,
               "migration_rto_s": 0.05,
               "scaling_efficiency": 0.3,
               "tokens_lost": 0}
        out.update(over)
        return out

    ok, why = bench._leg_promotable("serving_fleet", leg())
    assert ok, why
    # a multi-engine sub-leg without measured-vs-ideal scaling
    # compared nothing (engines_1 is exempt: its scaling is the
    # definition of 1.0)
    bad = leg()
    del bad["engines_4"]["scaling_efficiency"]
    ok, why = bench._leg_promotable("serving_fleet", bad)
    assert not ok and "scaling_efficiency" in why
    # a chaos sub-leg without its RTO measured a fleet that cannot
    # survive the event the tier exists for
    bad = leg()
    del bad["chaos"]["migration_rto_s"]
    ok, why = bench._leg_promotable("serving_fleet", bad)
    assert not ok and "migration_rto_s" in why
    # ...and one that migrated nothing killed an idle engine
    bad = leg()
    bad["chaos"]["requests_migrated"] = 0
    ok, why = bench._leg_promotable("serving_fleet", bad)
    assert not ok and "migrated no requests" in why
    # any lost token breaks the routing/migration byte-identity
    # contract; an UNSTAMPED tokens_lost defaults to lossy
    ok, why = bench._leg_promotable("serving_fleet",
                                    leg(tokens_lost=1))
    assert not ok and "lost tokens" in why
    bad = leg()
    del bad["tokens_lost"]
    ok, why = bench._leg_promotable("serving_fleet", bad)
    assert not ok and "lost tokens" in why
    # a fleet that cannot show its router fired is N independent
    # caches wearing a fleet's name
    ok, why = bench._leg_promotable("serving_fleet",
                                    leg(prefix_affinity_hit_rate=None))
    assert not ok and "prefix_affinity_hit_rate" in why
    # cache provenance applies to every timed sub-leg, chaos included
    bad = leg()
    del bad["chaos"]["cache_dtype"]
    ok, why = bench._leg_promotable("serving_fleet", bad)
    assert not ok and "cache_layout/cache_dtype" in why


@pytest.mark.slow
def test_live_serving_fleet_leg_passes_its_own_gate():
    """The leg bench.py actually emits must satisfy its own gate AND
    the §5o acceptance contract: zero tokens lost across every
    sub-leg (chaos included — one engine hard-abandoned mid-burst),
    the scaling and RTO columns stamped, and the affinity router
    actually firing on the shared-prefix mix — slow-marked (it runs
    the zipf burst through four fleet sizes plus the chaos fleet)."""
    import jax

    import paddle_tpu as pt

    leg = bench.bench_serving_fleet(pt, jax, False)
    ok, why = bench._leg_promotable("serving_fleet", leg)
    assert ok, why
    assert leg["tokens_lost"] == 0
    assert leg["chaos"]["byte_identical"] is True
    assert leg["chaos"]["requests_migrated"] >= 1
    assert isinstance(leg["migration_rto_s"], float)
    assert isinstance(leg["scaling_efficiency"], float)
    assert leg["prefix_affinity_hit_rate"] > 0


def test_serving_lora_gate_structural_cases():
    """The §5q multi-LoRA leg: a timed sub-leg without its numeric
    adapters stamp, any compile (or cost_version movement) during
    traffic, a lossy shared-vs-dedicated comparison, or a hot load
    that compiled is structurally unpromotable — and the usual
    cache-provenance stamps apply to every timed sub-leg."""
    def leg(**over):
        def sub(**s):
            d = {"cache_layout": "dense", "cache_dtype": "float32",
                 "tokens_per_sec": 1100.0, "adapters": 8,
                 "compiles_during_traffic": 0,
                 "cost_version_changed": False}
            d.update(s)
            return d

        out = {"input_staged": False,
               "transfer_note": "identical traffic on every sub-leg",
               "adapters_1": sub(adapters=1),
               "shared_8": sub(),
               "dedicated_8": sub(tokens_per_sec=600.0),
               "tokens_lost": 0, "hot_load_compiles": 0,
               "hot_load_cost_version_changed": False,
               "weight_bytes_saved": 1 << 24,
               "weight_bytes_ratio": 0.14,
               "tokens_per_sec": 1100.0}
        out.update(over)
        return out

    ok, why = bench._leg_promotable("serving_lora", leg())
    assert ok, why
    # a sub-leg that cannot say how many fine-tunes it mixed claims
    # nothing; a BOOL adapters stamp is a bug wearing a number's type
    bad = leg()
    del bad["shared_8"]["adapters"]
    ok, why = bench._leg_promotable("serving_lora", bad)
    assert not ok and "adapters stamp" in why
    bad = leg()
    bad["dedicated_8"]["adapters"] = True
    ok, why = bench._leg_promotable("serving_lora", bad)
    assert not ok and "adapters stamp" in why
    # the exactly-two contract allows ZERO new executables mid-traffic
    bad = leg()
    bad["shared_8"]["compiles_during_traffic"] = 1
    ok, why = bench._leg_promotable("serving_lora", bad)
    assert not ok and "ZERO new executables" in why
    bad = leg()
    bad["adapters_1"]["cost_version_changed"] = True
    ok, why = bench._leg_promotable("serving_lora", bad)
    assert not ok and "ZERO new executables" in why
    # the bank moves the delta math, never the tokens; an UNSTAMPED
    # tokens_lost defaults to lossy
    ok, why = bench._leg_promotable("serving_lora", leg(tokens_lost=3))
    assert not ok and "lost tokens" in why
    bad = leg()
    del bad["tokens_lost"]
    ok, why = bench._leg_promotable("serving_lora", bad)
    assert not ok and "lost tokens" in why
    # a hot swap is a bank-row device write, never a retrace
    ok, why = bench._leg_promotable("serving_lora",
                                    leg(hot_load_compiles=2))
    assert not ok and "hot swap" in why
    # cache provenance applies to every timed sub-leg
    bad = leg()
    del bad["dedicated_8"]["cache_dtype"]
    ok, why = bench._leg_promotable("serving_lora", bad)
    assert not ok and "cache_layout/cache_dtype" in why


@pytest.mark.slow
def test_live_serving_lora_leg_passes_its_own_gate():
    """The leg bench.py actually emits must satisfy its own gate AND
    the §5q acceptance contract: zero tokens lost vs the dedicated
    engines, zero compiles (and no cost_version movement) during the
    mixed-adapter traffic AND across the hot load, and the weight-
    bytes comparison stamped — slow-marked (it compiles one shared
    engine plus eight dedicated ones)."""
    import jax

    import paddle_tpu as pt

    leg = bench.bench_serving_lora(pt, jax, False)
    ok, why = bench._leg_promotable("serving_lora", leg)
    assert ok, why
    assert leg["tokens_lost"] == 0
    assert leg["hot_load_compiles"] == 0
    assert leg["hot_load_cost_version_changed"] is False
    for sub in ("adapters_1", "shared_8", "dedicated_8"):
        assert leg[sub]["compiles_during_traffic"] == 0
        assert leg[sub]["cost_version_changed"] is False
    assert leg["weight_bytes_saved"] > 0
    assert 0.0 < leg["weight_bytes_ratio"] < 1.0
    assert leg["shared_8"]["adapter_bank_bytes"] > 0
