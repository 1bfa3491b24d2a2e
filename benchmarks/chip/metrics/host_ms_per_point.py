"""Host time per point that the device did not spend in the stream
program: the ``bench.run_stream`` span of each point less the stream
module's device time inside it (state set-up, dispatch, read-back)."""


def read(run):
    if run.trace is None or not run.trace.point_spans:
        return None
    spans = run.trace.point_spans
    return 1e3 * sum(host - dev for host, dev in spans) / len(spans)
