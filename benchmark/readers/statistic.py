"""A number the cell's driver computed itself over the whole window
(``summarize``'s ``statistics``), by ``name``."""


def read(ctx, params):
    return ctx["summary"]["statistics"].get(params["name"])
