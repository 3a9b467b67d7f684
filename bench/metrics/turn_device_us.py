"""Device busy time per scan turn over the traced window (microseconds)."""


def read(ctx):
    turns = ctx["host"]["turns"]
    return 1e6 * ctx["trace"].busy_s / turns if turns else None
