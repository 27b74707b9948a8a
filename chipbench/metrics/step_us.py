"""Device busy time per scan step (one request on every lane) in the
traced window."""


def read(ctx):
    tr = ctx["traced"]
    if tr is None or not tr["steps"] or tr["busy_s"] <= 0:
        return None
    return 1e6 * tr["busy_s"] / tr["steps"]
