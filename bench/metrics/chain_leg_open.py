"""Share of the switch chain's leg runs that the grid-wide gates let
through: ``spans.Call.chain_leg_steps`` (grid steps on which the victim
leg and the policy-drain leg ran) summed over the two legs, over twice
``spans.Call.steps``, median over the sweeps.  Nothing is read from a
program that keeps no such counter or has no chain."""
import engine_log


def read(run):
    def share(c):
        legs = getattr(c, "chain_leg_steps", None)
        if not legs or c.steps <= 0:
            return None
        return 100.0 * sum(legs) / (len(legs) * c.steps)
    return engine_log.median_over(run, share)
