"""Time-word operations in one cell's grid step: ``spans.Call.time_ops``
(add, subtract, compare, max/min, argmin and sort on the engine's time
words, counted once when the program was traced), median over the
sweeps.  Nothing is read from a program that keeps no such counter."""
import engine_log


def read(run):
    def ops(c):
        n = getattr(c, "time_ops", None)
        return float(n) if n else None
    return engine_log.median_over(run, ops)
