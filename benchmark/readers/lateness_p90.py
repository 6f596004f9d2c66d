"""Send instant minus due instant on the generator's clock, 90th
percentile over the requests due in the window, in ms."""
from harness import window


def read(ctx, params):
    if not ctx["records"]:
        return None
    late = window.lateness_ms(ctx["records"], *ctx["client_window"])
    return window.percentile(late, 0.9) if late else None
