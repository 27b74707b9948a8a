"""Device operations that ran in the traced window per scan step, on each
chip (``tracereduce``: averaged over the chips that ran any): the launches
that a time loop inside one kernel would save."""


def read(ctx):
    tr = ctx["traced"]
    if tr is None or not tr["steps"] or not tr["ops"]:
        return None
    return tr["ops"] / tr["steps"]
