"""Every output token whose line reached the client inside the whole
window over the window's length, whether or not its request ended, or
began, inside: tokens/s on the client's clock."""
from harness import window


def read(ctx, params):
    if not ctx["records"]:
        return None
    a, b = ctx["client_window"]
    return window.tokens_in_window(ctx["records"], a, b) / (b - a)
