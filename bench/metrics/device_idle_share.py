"""Share of a sweep in which no operation ran on the device: the host
time before and after the program, plus the idle share that the two
profiled ends show inside the program, over its span
(``trace_reduce.combine``: ``busy_s`` of ``window_s``)."""


def read(run):
    red = run["trace"]
    if not red or not red["window_s"] > 0 or not red["busy_s"] > 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
