"""Host time per sweep in the engine's stages around the device scan:
``engine.stack``, ``engine.plan_runs``, ``engine.lower_configs``,
``engine.put``, ``engine.fetch`` and ``engine.unpack``, summed per call,
median over the run's sweeps."""
import engine_log


def read(run):
    return engine_log.median_over(run, lambda c: engine_log.span_ns(
        c, engine_log.HOST_STAGES) / 1e6)
