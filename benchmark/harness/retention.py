"""The power-retention serving cells: ``POST /generate`` on
``ServingHTTPFrontend`` -> ``ServingEngine`` -> ``GenerationPool`` with
``cache_layout='recurrent'``, an attention-free decoder whose layers keep a
state of constant size.

The loop is ``serve.py``'s, imported as ``blockgen.py`` imports it: the same
``drive``, ``drain``, ``Profiler``, warm-up, counters and ``summarize``, and
the generator that is there.  This module supplies the build (another model
class, weights made and loaded a layer at a time) and the comparison
(``retention_correct.py``: this model's reference)."""
from __future__ import annotations

from . import retention_weights, serve, weights
from .blockgen import _standing_in


def build(cfg: dict, seed: int):
    """The model and engine with the benchmark's weights.  The program
    initialises its own 8.4 GB first and the benchmark replaces them a
    layer at a time, so the two sets are never on the device together."""
    import paddle_tpu as pt
    from paddle_tpu.models import PowerRetentionLM
    from paddle_tpu.serving import ServingEngine, ServingHTTPFrontend

    pt.seed(weights.seed32(seed))
    model = PowerRetentionLM(**retention_weights.model_kwargs(cfg))
    model.eval()
    retention_weights.load_into(model, cfg, seed)
    engine = ServingEngine(model, max_len=cfg["max_len"], **cfg["engine"])
    return model, engine, ServingHTTPFrontend(engine)


def measure(run, jax, controls: tuple = ()) -> dict:
    """``serve.measure`` with this module's build.  ``controls``
    (``benchmark/calibrate_retention.py``, the tests) makes ``compare``
    read those controls beside the reference."""
    run["controls"] = tuple(controls)
    with _standing_in(serve, build=build):
        return serve.measure(run, jax)


summarize = serve.summarize


def compare(run, got) -> dict:
    from . import retention_correct
    return retention_correct.compare_serving(
        run["cfg"], run["seed"], got["schedule"], got["records"],
        run["traffic"]["check_requests"], got["storage"],
        controls=run.get("controls", ()))
