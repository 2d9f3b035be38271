"""Scheduler: 95th percentile of the time requests waited in the queue,
from each request's arrival to its admission (the program's request
trace, ``queue_s``), over the requests that arrived in the window before
its traced part: the profiler slows the host while it traces and holds it
for seconds when it stops, and the queue would measure that."""


def read(ctx):
    from stats import percentile
    return percentile([s.queue_s for s in ctx.requests
                       if s.queue_s is not None
                       and s.arrival_s < ctx.traced_from], 95)
