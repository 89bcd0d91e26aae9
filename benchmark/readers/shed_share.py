"""Frames shed by the scheduler inside the window, as a percentage of the
frames due (``/healthz`` shed counts over the client's ``attempted``)."""


def read(ctx: dict, params: dict):
    def shed(snap):
        return sum(snap["healthz"]["scheduler"]["shed"].values())
    if not ctx["attempted"]:
        return None
    return 100.0 * (shed(ctx["after"]) - shed(ctx["before"])) / ctx["attempted"]
