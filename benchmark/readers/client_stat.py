"""A statistic of the client's own samples (``params.key`` of the
generator's ``client`` block)."""


def read(ctx: dict, params: dict):
    return ctx["client"].get(params["key"])
