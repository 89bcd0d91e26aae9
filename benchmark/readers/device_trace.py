"""Numbers of the profiler trace: ``params.field`` is ``idle_share`` (100 x
(1 - busy over the traced window), averaged over the chips used) or
``ms_per_step`` (device-busy milliseconds per executed program)."""


def read(ctx: dict, params: dict):
    tr = ctx.get("device_trace")
    if not tr or tr["window_s"] <= 0:
        return None
    if params["field"] == "idle_share":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    if params["field"] == "ms_per_step":
        return 1e3 * tr["busy_s"] / tr["steps"] if tr["steps"] else None
    raise KeyError(params["field"])
