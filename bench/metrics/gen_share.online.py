"""Share of the window the client spent generating its chunks (host clock)."""


def read(ctx):
    host = ctx["host"]
    return 100.0 * host["gen_s"] / host["window_s"]
