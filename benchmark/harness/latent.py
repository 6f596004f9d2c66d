"""The latent-attention expert decoder's serving cells: ``POST /generate`` on
``ServingHTTPFrontend`` -> ``ServingEngine`` -> ``GenerationPool``, a decoder
whose cache entries are latents (one a position a layer, shared by every
head), whose prompts run the expanded form of latent attention and whose
steps the absorbed form, and whose expert layers hold one chip's share of
the routed experts beside a shared expert.

The loop is ``serve.py``'s, imported as ``mamba.py`` imports it.  This module
supplies the build and the comparison: ``retention_correct.compare_serving``
as it is, with this model's reference and weights standing in."""
from __future__ import annotations

from . import latent_reference, latent_weights, serve, weights
from .blockgen import _standing_in
from .correct import _held


def build(cfg: dict, seed: int):
    """The model and engine with the benchmark's weights.  The program
    initialises its own 9.7 GB first and the benchmark replaces them a
    layer at a time, so the two sets are never on the device together."""
    import paddle_tpu as pt
    from paddle_tpu.models import LatentMoELM
    from paddle_tpu.serving import ServingEngine, ServingHTTPFrontend

    pt.seed(weights.seed32(seed))
    model = LatentMoELM(**latent_weights.model_kwargs(cfg))
    model.eval()
    latent_weights.load_into(model, cfg, seed)
    engine = ServingEngine(model, max_len=cfg["max_len"], **cfg["engine"])
    return model, engine, ServingHTTPFrontend(engine)


def measure(run, jax, controls: tuple = ()) -> dict:
    """``serve.measure`` with this module's build.  ``controls``
    (``benchmark/calibrate_latent.py``, the tests) makes ``compare`` read
    those controls beside the reference."""
    run["controls"] = tuple(controls)
    with _standing_in(serve, build=build):
        return serve.measure(run, jax)


summarize = serve.summarize


def compare(run, got) -> dict:
    from . import retention_correct

    cfg = run["cfg"]
    unheld = lambda cfg, storage: _held(0, 0)
    with _standing_in(retention_correct, ref=latent_reference,
                      rw=latent_weights, state_held_in_float32=unheld):
        out = retention_correct.compare_serving(
            cfg, run["seed"], got["schedule"], got["records"],
            run["traffic"]["check_requests"], got["storage"],
            controls=run.get("controls", ()))
    # no recurrent state here: the census line of the other configurations
    out.pop("float32_state_bytes_short", None)
    return out
