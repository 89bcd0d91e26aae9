"""Another reader, shown only the ``/engines`` rows whose key starts with
``params.prefix``: a server that runs two engines (the detector's batch
engine beside the generate engine) has two rows, and ``batch_fill`` and
``stage_clock`` sum over all they are shown. ``params.reader`` names the
reader, ``params.params`` are its own."""

import importlib

SNAPSHOTS = ("before", "after", "trace_before", "trace_after")


def read(ctx: dict, params: dict):
    shown = dict(ctx)
    for name in SNAPSHOTS:
        snap = ctx.get(name)
        if snap:
            shown[name] = dict(snap, engines={
                key: row for key, row in snap["engines"].items()
                if key.startswith(params["prefix"])})
    if not shown["after"]["engines"]:
        return None
    reader = importlib.import_module(f"benchmark.readers.{params['reader']}")
    return reader.read(shown, params.get("params", {}))
