"""Manual BERT throughput sweep on the chip (it refuses to time the CPU).

Usage: python tools/bert_sweep.py [--seq N] [batch ...]   (defaults: 16 24 32 48)
Locates the throughput knee that bench.py's batch sweep centers on.
"""
import os, sys, numpy as np, jax

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import paddle_tpu as pt
from bench import _peak_flops, _time_steps
from tools.compile_cache import ensure_compile_cache
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import TransformerLM, TransformerLMCriterion, bert_base_config

def run(batch, seq=512, iters=10):
    pt.seed(0)
    cfg = bert_base_config()
    model = TransformerLM(**cfg, dropout=0.0)
    criterion = TransformerLMCriterion(shift_labels=False)
    opt = pt.optimizer.AdamW(1e-4, parameters=model.parameters())
    model, opt = pt.amp.decorate(model, opt, level="O2", dtype="bfloat16")
    def loss_fn(m, ids, labels):
        with pt.amp.auto_cast(level="O1", dtype="bfloat16"):
            return criterion(m(ids), labels)
    step = TrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg["vocab_size"], (batch, seq)).astype("int32")
    # _time_steps stages inputs on device and amortizes the end-of-loop
    # host fetch — the same timing convention as every bench.py leg
    dt, _ = _time_steps(step, (ids, ids), iters)
    flops = model.flops_per_token(seq) * batch * seq
    mfu = flops / dt / _peak_flops(jax, True)
    print(f"batch={batch} seq={seq}: {dt*1e3:.1f} ms  {batch*seq/dt:,.0f} tok/s  MFU={mfu:.4f}", flush=True)
    return mfu

if __name__ == "__main__":
    if jax.devices()[0].platform != "tpu":
        sys.exit("bert_sweep times the chip; jax found %r"
                 % jax.devices()[0].platform)
    ensure_compile_cache()
    argv = sys.argv[1:]
    seq = 512
    if "--seq" in argv:
        i = argv.index("--seq")
        try:
            seq = int(argv[i + 1])
        except (IndexError, ValueError):
            sys.exit("usage: bert_sweep.py [--seq N] [batch ...]")
        del argv[i:i + 2]
    try:
        batches = [int(a) for a in argv] or [16, 24, 32, 48]
    except ValueError:
        sys.exit("usage: bert_sweep.py [--seq N] [batch ...]")
    for b in batches:
        try:
            run(b, seq=seq)
        except Exception as e:
            print(f"batch={b}: FAILED {str(e)[:120]}", flush=True)
