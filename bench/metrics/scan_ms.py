"""The engine program's span in one sweep, on the host clock: an
untraced sweep's host time less the host time before the program's first
op on the device and after its last, both read from the device trace
(``trace_reduce.combine``: ``span_s``)."""


def read(run):
    red = run["trace"]
    if not red or not red["span_s"] > 0:
        return None
    return 1000.0 * red["span_s"]
