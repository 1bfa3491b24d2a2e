"""Device time of the stream program per scanned step: the stream
module's device time inside the window (from the trace) over the steps
the window's points scanned."""


def read(run):
    steps = sum(p.steps for p in run.points)
    if run.trace is None or not run.trace.stream_device_s or not steps:
        return None
    return 1e3 * run.trace.stream_device_s / steps
