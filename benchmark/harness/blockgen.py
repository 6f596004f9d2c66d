"""The block-diffusion serving cells: ``POST /generate`` on
``ServingHTTPFrontend`` -> ``ServingEngine`` -> ``BlockDiffusionPool``, a
sparse-expert decoder that commits several tokens a step.

The loop is ``serve.py``'s, imported: the same ``drive``, ``drain``,
``Profiler``, warm-up, counters and ``summarize``.  This module supplies what
a configuration of this kind needs of its own: the build (another model
class, weights made and loaded a layer at a time), a generator that keeps
the mask id out of the prompts, and the comparison, which replays every
state the program's denoising steps went through (``blockdiff_correct.py``).
"""
from __future__ import annotations

import contextlib
import types

from . import blockdiff_weights, serve, traffic as traffic_mod, weights


class Schedule(traffic_mod.Schedule):
    """The generator that is there, with one id kept out of every prompt
    (the traffic file's ``avoid_token_id``: the model's mask id, which no
    tokenizer emits for text): ids are drawn from a range one shorter and
    those at or above the id move up by one."""

    def token_ids(self, index: int, n: int, vocab: int) -> list:
        avoid = self.traffic["avoid_token_id"]
        ids = super().token_ids(index, n, vocab - 1)
        return [t + 1 if t >= avoid else t for t in ids]


_TRAFFIC = types.SimpleNamespace(Schedule=Schedule,
                                 offered=traffic_mod.offered)


def build(cfg: dict, seed: int):
    """The model and engine with the benchmark's weights.  The program
    initialises its own 8.7 GB first and the benchmark replaces them a
    layer at a time, so the two sets are never on the device together."""
    import paddle_tpu as pt
    from paddle_tpu.models import BlockDiffusionMoELM
    from paddle_tpu.serving import ServingEngine, ServingHTTPFrontend

    pt.seed(weights.seed32(seed))
    model = BlockDiffusionMoELM(**blockdiff_weights.model_kwargs(cfg))
    model.eval()
    blockdiff_weights.load_into(model, cfg, seed)
    engine = ServingEngine(model, max_len=cfg["max_len"], **cfg["engine"])
    return model, engine, ServingHTTPFrontend(engine)


@contextlib.contextmanager
def _standing_in(module, **names):
    """``module``'s names replaced for the block: how ``serve.measure`` is
    given this module's build and generator without a copy of its body."""
    was = {k: getattr(module, k) for k in names}
    for k, v in names.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in was.items():
            setattr(module, k, v)


def measure(run, jax, control: bool = False) -> dict:
    """``serve.measure`` with this module's build and generator.
    ``control`` (``benchmark/calibrate_blockgen.py``) makes ``compare``
    read the fp8 control beside the reference."""
    run["control"] = bool(control)
    with _standing_in(serve, build=build, traffic_mod=_TRAFFIC):
        return serve.measure(run, jax)


summarize = serve.summarize


def compare(run, got) -> dict:
    from . import blockdiff_correct
    return blockdiff_correct.compare_serving(
        run["cfg"], run["seed"], got["schedule"], got["records"],
        run["traffic"]["check_requests"], got["storage"],
        controls=("fp8",) if run.get("control") else ())
