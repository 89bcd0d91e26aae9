"""A share or a rate from ``/metrics`` between the two snapshots:
``params.scale`` x delta of ``params.num`` over delta of ``params.den``.

``num`` and ``den`` each name one series, ``{"series": <name as on the
wire, with its _total or _sum>, "labels": {<key>: <value>, ...}}``, and
stand for the SUM of every line of that name whose labels hold the given
pairs (one engine's threads whatever the engine is called; every
generation of the collector). ``den`` may instead be ``"window_s"``, the
seconds between the snapshots. Where the server has no line of ``num``'s
name at all, as a build from before the series has not, there is nothing
to read: None, and the metric is left out of the line."""

import re

from benchmark.readers.common import window_snapshots

_LABEL = re.compile(r'(\w+)="([^"]*)"')


def summed(snap: dict, spec: dict):
    want = spec.get("labels", {})
    total = None
    for key, value in snap["metrics"].items():
        name, _, labels = key.partition("{")
        if name != spec["series"]:
            continue
        have = dict(_LABEL.findall(labels))
        if all(have.get(k) == v for k, v in want.items()):
            total = (total or 0.0) + value
    return total


def delta(before: dict, after: dict, spec):
    if spec == "window_s":
        return after["t"] - before["t"]
    end = summed(after, spec)
    return None if end is None else end - (summed(before, spec) or 0.0)


def read(ctx: dict, params: dict):
    before, after = window_snapshots(ctx, params)
    num = delta(before, after, params["num"])
    den = delta(before, after, params["den"])
    if num is None or not den or den < 0:
        return None
    return params.get("scale", 1.0) * num / den
