"""The latent decode-attention kernels of a step against their roofline, in
%: the larger of the least bytes they read (``harness/latent_costs
.latent_attn_min_bytes``: every live position's latent and rotary key once a
layer) over the chip's HBM bandwidth and their least operations over its
bfloat16 peak, over the device time of the operations under the
``latent_attn`` scope inside a step.  That time is the ``scope_share``
reader's share times the runs' time.  Live rows and blocks come from the
program's ``tick.decode`` spans (``live``, ``live_blocks``).  None where no
operation runs under such a scope or the program writes no such spans."""
from harness import latent_costs, xplane
from readers import scope_share
from readers.mamba_decode_roofline import mean_meta


def read(ctx, params):
    share = scope_share.read(ctx, params)
    rows, blocks = mean_meta(ctx, "live"), mean_meta(ctx, "live_blocks")
    if share is None or rows is None or blocks is None:
        return None
    runs = xplane.module_runs(ctx["trace"], params["pattern"], ctx["t0"],
                              ctx["t1"])
    seconds = share / 100.0 * sum(e - s for s, e in runs) / len(runs)
    positions = latent_costs.live_positions(ctx["cfg"], rows, blocks)
    peaks = ctx["peaks"]()
    least = max(
        latent_costs.latent_attn_min_bytes(ctx["cfg"], positions)
        / peaks["hbm_bytes_per_s"],
        latent_costs.latent_attn_flops(ctx["cfg"], positions)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least / seconds
