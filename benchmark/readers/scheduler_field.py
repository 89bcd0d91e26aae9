"""One field of ``/scheduler`` as read after the window
(``params.field``)."""


def read(ctx: dict, params: dict):
    value = ctx["after"]["scheduler"].get(params["field"])
    return None if value is None else float(value)
