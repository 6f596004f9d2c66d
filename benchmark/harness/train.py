"""The training cells: ``TrainStep.__call__`` under bf16 O2 with AdamW,
built as ``chip_smoke.py`` builds it, fed masked-LM batches staged on the
device, timed over the whole window with the last step blocked on."""
from __future__ import annotations

import gc
import time

import numpy as np

from . import device, weights
from .serve import load_weights, model_sizes, say

MASK_ID = 103           # [MASK] in the BERT vocabulary
FIRST_WORD_ID = 1000    # ids below are special or unused tokens


def make_batches(cfg: dict, tr: dict, seed: int) -> list:
    """``distinct_batches`` masked-LM batches of ``(ids, labels)`` as host
    arrays, Devlin et al.'s recipe: 15 % of positions are predicted; of
    those 80 % show [MASK], 10 % a random word, 10 % the word itself.
    Labels are -100 everywhere else."""
    rng = np.random.default_rng([int(seed), 5])
    rows, seq = tr["batch_per_chip"], tr["sequence"]
    words = cfg["published_vocab_size"]
    out = []
    for _ in range(tr["distinct_batches"]):
        ids = rng.integers(FIRST_WORD_ID, words, (rows, seq), dtype=np.int32)
        pick = rng.random((rows, seq)) < tr["mask_share"]
        how = rng.random((rows, seq))
        labels = np.where(pick, ids, -100).astype(np.int32)
        shown = np.where(pick & (how < 0.8), MASK_ID, ids)
        shown = np.where(pick & (how >= 0.9),
                         rng.integers(FIRST_WORD_ID, words, (rows, seq)),
                         shown).astype(np.int32)
        out.append((shown, labels))
    return out


def build(cfg: dict, seed: int):
    """Model, optimizer and step exactly as ``chip_smoke.build_train_step``,
    with the benchmark's weights put in before the O2 cast."""
    import paddle_tpu as pt
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import TransformerLM, TransformerLMCriterion

    pt.seed(weights.seed32(seed))
    model = TransformerLM(**model_sizes(cfg), dropout=0.0)
    load_weights(model, cfg, seed)
    criterion = TransformerLMCriterion(shift_labels=False)
    hyper = cfg["optimizer"]
    opt = pt.optimizer.AdamW(hyper["lr"], beta1=hyper["beta1"],
                             beta2=hyper["beta2"], epsilon=hyper["epsilon"],
                             weight_decay=hyper["weight_decay"],
                             parameters=model.parameters())
    model, opt = pt.amp.decorate(model, opt, level="O2", dtype="bfloat16")

    def loss_fn(m, ids, labels):
        with pt.amp.auto_cast(level="O1", dtype="bfloat16"):
            return criterion(m(ids), labels)

    return model, opt, TrainStep(model, loss_fn, opt)


def stage(batches: list) -> list:
    import jax
    return [tuple(jax.device_put(a) for a in b) for b in batches]


def program_norms(cfg: dict, model, opt, seed: int, what: str) -> list:
    """Per leaf, in the reference's leaf order: ``first_grad``, the norm of
    the gradient the optimizer got at its first step (moment1 after one
    step is (1 - beta1) x it), or ``change``, the norm of the master
    weights' distance from the seeded start."""
    import jax
    import jax.numpy as jnp
    by_name = dict(model.named_parameters())
    sizes = model_sizes(cfg)

    def moment(name):
        return opt._states[by_name[name].name]["moment1"]

    def master(name):
        p = by_name[name]
        return opt._states[p.name].get("master_weight", p.value)

    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    if what == "first_grad":
        scale = 1.0 / (1.0 - cfg["optimizer"]["beta1"])
        tree = weights.from_program(sizes, moment)
        vals = jax.jit(lambda t: [norm(x) * scale
                                  for x in jax.tree.leaves(t)])(tree)
    else:
        tree = weights.from_program(sizes, master)
        start = weights.make_weights(sizes, seed)
        vals = jax.jit(lambda t, s: [
            norm(x.astype(jnp.float32) - y) for x, y in
            zip(jax.tree.leaves(t), jax.tree.leaves(s))])(tree, start)
    return [float(v) for v in vals]


def run_window(step, staged: list, done_steps: int, seconds: float):
    """Steps until ``seconds`` have passed, one step dispatched ahead of
    the one waited on, the last one blocked on.  Returns
    ``(steps, t_open, t_close, marks)``; ``marks`` are the instants at
    which each step was seen finished."""
    clock = time.perf_counter
    t_open, n, prev, marks = clock(), 0, None, []
    while True:
        loss = step(*staged[(done_steps + n) % len(staged)])
        n += 1
        if prev is not None:
            prev.value.block_until_ready()
            marks.append(clock())
        prev = loss
        if clock() - t_open >= seconds:
            break
    prev.value.block_until_ready()
    t_close = clock()
    marks.append(t_close)
    return n, t_open, t_close, marks


def measure(run, jax, wrap_step=None) -> dict:
    cfg, tr = run["cfg"], run["traffic"]
    t0 = time.perf_counter()
    model, opt, step = build(cfg, run["seed"])
    host_batches = make_batches(cfg, tr, run["seed"])
    staged = stage(host_batches)
    t_built = time.perf_counter()
    call = step if wrap_step is None else wrap_step(step)
    checked = tr["checked_steps"]
    losses, first, prof = [], None, None
    # the checked steps go through the window's own call and feed
    for i in range(checked):
        losses.append(float(np.asarray(call(*staged[i]).value)))
        if i == 0:
            first = program_norms(cfg, model, opt, run["seed"],
                                  "first_grad")
    change = program_norms(cfg, model, opt, run["seed"], "change")
    t_checked = time.perf_counter()
    if run["trace"]:
        from .serve import Profiler
        prof = Profiler(jax, run["trace_dir"])
        prof.begin()
        prof.started.wait()
        prof.mark()
    seconds = tr["trace_s"] if run["trace"] else run["seconds"]
    n, t_open, t_close, marks = run_window(call, staged, checked,
                                           seconds)
    if prof:
        prof.mark()
        prof.stop()
    syncs = prof.syncs if prof else None
    mem = device.memory(jax, run["chips"])

    def free():
        nonlocal model, opt, step, staged, call
        model = opt = step = staged = call = None
        gc.collect()

    return {"steps": n, "t_open": t_open, "t_close": t_close,
            "marks": marks,
            "tokens_per_step": tr["batch_per_chip"] * tr["sequence"],
            "losses": losses, "first_grad": first, "change": change,
            "host_batches": host_batches, "memory": mem, "syncs": syncs,
            "free": free, "spans": None,
            "setup_parts": {"build_s": t_built - t0,
                            "compile_and_checked_steps_s": t_checked
                            - t_built}}


def summarize(run, got) -> dict:
    length = got["t_close"] - got["t_open"]
    tokens = got["steps"] * got["tokens_per_step"]
    rate = tokens / length
    say("[train] %d steps of %d tokens in %.3f s: %.1f tokens/s, %.3f ms a "
        "step; checked-step losses %s"
        % (got["steps"], got["tokens_per_step"], length, rate,
           1e3 * length / got["steps"], got["losses"]))
    return {"statistics": {"tokens_per_s": rate},
            "attempted": got["steps"], "failed": 0}


def compare(run, got) -> dict:
    from . import correct
    return correct.compare_training(run["cfg"], run["traffic"], run["seed"],
                                    got)
