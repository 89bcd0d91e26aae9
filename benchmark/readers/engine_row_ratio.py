"""A ratio of two fields of the ``/engines`` rows as they stand at the
window's end: ``params.scale`` x sum of ``params.num`` over sum of
``params.den``, over the rows that have both (a gauge, not a delta: what
an engine HOLDS). No row has them: None."""


def read(ctx: dict, params: dict):
    num = den = 0.0
    for row in ctx["after"]["engines"].values():
        if params["num"] in row and params["den"] in row:
            num += row[params["num"]]
            den += row[params["den"]]
    if den <= 0:
        return None
    return params.get("scale", 1.0) * num / den
