"""Share of the grid's cell-steps spent after that cell had drained its
streams: the early-exit loop runs until the slowest cell is done, so 1
less the sum of each cell's ``CHUNK``-step segments over the cell count
times the largest (``spans.Call.segments``), median over the sweeps."""
import engine_log


def read(run):
    def waste(c):
        top = int(c.segments.max()) if c.segments.size else 0
        if top <= 0:
            return None
        return 100.0 * (1.0 - float(c.segments.sum()) / (c.segments.size
                                                         * top))
    return engine_log.median_over(run, waste)
