"""The hybrid Mamba / attention serving cells: ``POST /generate`` on
``ServingHTTPFrontend`` -> ``ServingEngine`` -> ``GenerationPool``, a decoder
whose cache list holds a recurrent state (convolution and selective scan) for
most layers and paged K/V for the rest, in one pool.

The loop is ``serve.py``'s, imported as ``retention.py`` imports it: the same
``drive``, ``drain``, ``Profiler``, warm-up, counters and ``summarize``, and
the generator that is there.  This module supplies the build (another model
class, weights made and loaded a layer at a time) and the comparison:
``retention_correct.compare_serving``'s statistic and sampling, called as it
is with this model's reference, weights and state census standing in for
brumby's."""
from __future__ import annotations

from . import mamba_costs, mamba_reference, mamba_weights, serve, weights
from .blockgen import _standing_in
from .correct import _held


def build(cfg: dict, seed: int):
    """The model and engine with the benchmark's weights.  The program
    initialises its own 6 GB first and the benchmark replaces them a layer
    at a time, so the two sets are never on the device together."""
    import paddle_tpu as pt
    from paddle_tpu.models import HybridMambaLM
    from paddle_tpu.serving import ServingEngine, ServingHTTPFrontend

    pt.seed(weights.seed32(seed))
    model = HybridMambaLM(**mamba_weights.model_kwargs(cfg))
    model.eval()
    mamba_weights.load_into(model, cfg, seed)
    engine = ServingEngine(model, max_len=cfg["max_len"], **cfg["engine"])
    return model, engine, ServingHTTPFrontend(engine)


def measure(run, jax, controls: tuple = ()) -> dict:
    """``serve.measure`` with this module's build.  ``controls``
    (``benchmark/calibrate_mamba.py``, the tests) makes ``compare`` read
    those controls beside the reference."""
    run["controls"] = tuple(controls)
    with _standing_in(serve, build=build):
        return serve.measure(run, jax)


summarize = serve.summarize


def state_held_in_float32(cfg: dict, storage: dict) -> dict:
    """By how many bytes the float32 arrays the program holds fall short of
    the scan state of every slot and Mamba layer (``mamba_costs``), held to
    0: the element type of the state by the census of what is stored,
    whatever the logit gaps can or cannot tell."""
    need = cfg["engine"]["slots"] * mamba_costs.ssm_bytes_per_slot(cfg)
    return _held(max(0, need - storage["by_type"].get("float32", 0)), 0)


def compare(run, got) -> dict:
    from . import retention_correct
    with _standing_in(retention_correct, ref=mamba_reference,
                      rw=mamba_weights,
                      state_held_in_float32=state_held_in_float32):
        return retention_correct.compare_serving(
            run["cfg"], run["seed"], got["schedule"], got["records"],
            run["traffic"]["check_requests"], got["storage"],
            controls=run.get("controls", ()))
