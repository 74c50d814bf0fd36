"""Resource model: PM device banks and the PBC service port.

Every shared resource is a scalar "next-free time".  A requester that
arrives at ``ready`` starts service at ``max(next_free, ready)`` and
holds the resource for its *occupancy* (device-internal pipelining lets
a PM bank accept the next request before the requester observes its
response, so occupancy < latency).

The PBC is a single FIFO front: persists and PI-routed reads serialize
on ``pbc_busy``; the head-of-line blocking of reads behind stalled
writes (the paper's Fig. 6b mechanism) falls out of this scalar.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.engine import timebase as tb


def bank_of(addr, n_banks: int):
    """Static interleave of cache lines across independent PM banks."""
    return addr % n_banks


def service_start(busy, bank, ready):
    """When bank ``bank`` can begin serving a request arriving at ``ready``."""
    return tb.maximum(busy[bank], ready)


def reserve(busy, bank, start, occ):
    """Hold the bank from ``start`` for ``occ`` ns; returns updated vector."""
    return busy.at[bank].set(tb.add(start, occ))


def pbc_start(pbc_busy, arrival, proc_ns):
    """PBC FIFO service start + processing for one packet."""
    return tb.add(tb.maximum(pbc_busy, arrival), proc_ns)


def pbc_hold(pbc_busy, arrival, occ_ns):
    """Advance the PBC next-free time past one packet's issue interval."""
    return tb.add(tb.maximum(pbc_busy, arrival), occ_ns)


def fifo_service(busy, arrivals, active, occ_ns):
    """Batch FIFO service of a deep-hop PBC / inter-switch channel.

    ``arrivals`` (Q,) are packet arrival times in channel order (batch
    order == wire order); ``active`` masks live packets.  Service start
    of packet q is ``max(arrival_q, start_{q-1} + occ)`` with the
    channel busy until ``busy`` — the standard FIFO recurrence, solved
    in closed form with a cumulative max:

        start_q = occ*rank_q + max(busy, max_{i<=q}(arr_i - occ*rank_i))

    A negative ``arr_i - occ*rank_i`` never wins against ``busy >= 0``,
    so it is taken as 0 (``tb.monus``).  Returns ``(starts (Q,),
    busy_after ())``; inactive packets get INF starts and do not advance
    the channel.
    """
    rank = jnp.cumsum(active.astype(jnp.int32)) - 1
    wait = tb.mul(occ_ns, jnp.maximum(rank, 0))
    adj = jnp.where(active, tb.monus(arrivals, wait), tb.NEG)
    run = tb.cummax(adj)
    starts = jnp.where(active, tb.add(wait, tb.maximum(run, busy)), tb.INF)
    busy_after = tb.max(jnp.where(active, tb.add(starts, occ_ns), busy))
    return starts, tb.maximum(busy_after, busy)
