"""1 - the union of device-operation intervals over the traced window,
averaged over the chips used, in %."""


def read(ctx, params):
    if not ctx["trace"].devices:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
