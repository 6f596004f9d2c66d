"""Model FLOP/s utilisation, in %: the window's tokens per second
times the forward-and-backward FLOPs a token requires (from shapes), over
chips times the published bf16 peak.  An end-to-end utilisation, not a
kernel's share of its roofline."""
from harness import device


def read(ctx, params):
    rate = ctx["summary"]["statistics"].get("tokens_per_s")
    if rate is None:
        return None
    flops = device.train_flops_per_token(ctx["cfg"],
                                         ctx["traffic"]["sequence"])
    return 100.0 * rate * flops / (ctx["chips"]
                                   * ctx["peaks"]()["bf16_flops_per_s"])
