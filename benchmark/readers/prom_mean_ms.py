"""Mean milliseconds of a ``/metrics`` timing series between the two
snapshots: delta of ``<series>_sum`` over delta of ``<series>_count``
(``params.series``, with ``params.labels`` written as on the wire, e.g.
``{stage="destination"}``). The series' own since-boot mean includes cold
batches; the delta over the window does not."""

from benchmark.readers.common import prom_delta, window_snapshots


def read(ctx: dict, params: dict):
    before, after = window_snapshots(ctx, params)
    labels = params.get("labels", "")
    n = prom_delta(before, after, f"{params['series']}_count{labels}")
    if n <= 0:
        return None
    s = prom_delta(before, after, f"{params['series']}_sum{labels}")
    return 1e3 * s / n
