"""Share of grid steps on which the macro gate opened and the macro
replay ran: ``spans.Call.macro_gate_steps`` over ``spans.Call.steps``,
median over the sweeps.  Nothing is read from a program that keeps no
such counter."""
import engine_log


def read(run):
    def share(c):
        gate = getattr(c, "macro_gate_steps", None)
        if gate is None or c.steps <= 0:
            return None
        return 100.0 * gate / c.steps
    return engine_log.median_over(run, share)
