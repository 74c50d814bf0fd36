"""Device-scan time per grid step: the engine's ``engine.scan`` span
(dispatch until the outputs are ready) over the grid steps the call
executed (``spans.Call.steps``), median over the run's sweeps."""
import engine_log


def read(run):
    return engine_log.median_over(run, lambda c: (
        engine_log.span_ns(c, ("engine.scan",)) / 1e3 / c.steps
        if c.steps > 0 else None))
