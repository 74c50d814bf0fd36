"""Traffic generator: every mix under ``bench/traffic/`` is data read here.

A traffic file names its streams and the parameters of each; this module
holds the only code that turns them into the engine's input type
(``repro.core.Trace``).  The stream kinds are copies of the program's
own generators, kept here so that no change to the program can change
the benchmark's inputs:

* ``fft``, ``lu`` and ``signature`` are the Splash-4-signature
  generators of ``core/traces.py`` (``fft_trace``, ``_lu_trace``,
  ``_signature_trace``), copied with their arithmetic unchanged;
* ``probe`` is the one-core persist/read probe of
  ``benchmarks/fig1_switch_depth.py`` (``_probe_trace``).

How ``--seed`` enters is part of each mix (``seed_enters``), and it never
changes a size: every seed of a mix yields the same streams, op counts,
shapes and mean gaps, so the work of a run is the same from seed to
seed.

* ``relabel_pm_lines``: each trace's persistent-memory lines are
  renamed by a random bijection of the lines the trace touches.  Every
  reuse, coalescing and read-after-persist relation stays as generated;
  only which PM bank a line falls in changes, and with it the queueing.
* ``permute_gaps``: each core's compute gaps are replaced by a fixed
  evenly spaced set around the stream's nominal gap, in an order drawn
  from the seed (same count, same mean, other arrivals).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import List

import numpy as np

from repro.core import Op, Trace

# Heap (persistent) lines live below DRAM_BASE; volatile lines above it.
DRAM_BASE = 1 << 24


class _LLCFilter:
    """LRU filter approximating the per-core view of the cache hierarchy."""

    def __init__(self, capacity_lines: int = 4096):
        self.capacity = capacity_lines
        self._lru: "OrderedDict[int, None]" = OrderedDict()

    def access(self, line: int) -> bool:
        """True when the access misses (must go to memory)."""
        if line in self._lru:
            self._lru.move_to_end(line)
            return False
        self._lru[line] = None
        if len(self._lru) > self.capacity:
            self._lru.popitem(last=False)
        return True

    def invalidate(self, line: int) -> None:
        self._lru.pop(line, None)


class _CoreStream:
    """One core's op stream with an LLC filter attached."""

    def __init__(self, llc_lines: int = 4096):
        self.ops: List[int] = []
        self.addrs: List[int] = []
        self.gaps: List[float] = []
        self._pending_gap = 0.0
        self.llc = _LLCFilter(llc_lines)

    def compute(self, ns: float) -> None:
        self._pending_gap += ns

    def emit(self, op: Op, addr: int) -> None:
        self.ops.append(int(op))
        self.addrs.append(int(addr))
        self.gaps.append(self._pending_gap)
        self._pending_gap = 0.0

    def read_pm(self, line: int) -> None:
        if self.llc.access(line):
            self.emit(Op.PM_READ, line)
        else:
            self.compute(1.0)  # L1/L2 hit cost

    def persist(self, line: int) -> None:
        # clflush evicts the line from the hierarchy and pushes it to PM
        self.llc.invalidate(line)
        self.emit(Op.PERSIST, line)

    def barrier(self) -> None:
        self.emit(Op.BARRIER, 0)


def _pack(streams: List[_CoreStream], name: str) -> Trace:
    bars = {sum(1 for o in s.ops if o == int(Op.BARRIER)) for s in streams}
    if len(bars) > 1:
        raise ValueError(f"inconsistent barrier counts in {name}: {bars}")
    lengths = np.array([len(s.ops) for s in streams], dtype=np.int32)
    C, L = len(streams), int(lengths.max())
    ops = np.zeros((C, L), np.int32)
    addrs = np.zeros((C, L), np.int32)
    gaps = np.zeros((C, L), np.float32)
    for c, s in enumerate(streams):
        n = lengths[c]
        ops[c, :n] = s.ops
        addrs[c, :n] = s.addrs
        gaps[c, :n] = s.gaps
    return Trace(ops=ops, addrs=addrs, gaps=gaps, lengths=lengths, name=name)


# ---------------------------------------------------------------- streams
def fft(name: str, seed: int, persist_budget: int, *, n_cores: int,
        m: int) -> Trace:
    """Radix-2 FFT (Splash-4 FFT, ``-m``): epoch flushes of the lines each
    core modified, neighbour-boundary exchange reads, stage barriers.
    The address stream is deterministic (``seed`` is unused)."""
    del seed
    n = 1 << m
    points_per_line = 4
    streams = [_CoreStream() for _ in range(n_cores)]
    budget = persist_budget
    epoch = 8  # butterflies between checkpoint flushes
    for stage in range(m):
        half = 1 << stage
        flushes: List[List[List[int]]] = []
        spans = []
        for c in range(n_cores):
            lo = (n // 2) * c // n_cores
            hi = (n // 2) * (c + 1) // n_cores
            spans.append((lo, hi))
            eps: List[List[int]] = []
            dirty: "OrderedDict[int, None]" = OrderedDict()
            for j, b in enumerate(range(lo, hi)):
                top = (b // half) * (2 * half) + (b % half)
                bot = top + half
                dirty[top // points_per_line] = None
                dirty[bot // points_per_line] = None
                if (j + 1 + 3 * c) % epoch == 0:
                    eps.append(list(dirty))
                    dirty.clear()
            if dirty:
                eps.append(list(dirty))
            flushes.append(eps)
        for c in range(n_cores):
            s = streams[c]
            lo, hi = spans[c]
            e_idx = 0
            for j, b in enumerate(range(lo, hi)):
                top = (b // half) * (2 * half) + (b % half)
                bot = top + half
                l_top, l_bot = top // points_per_line, bot // points_per_line
                s.read_pm(l_top)
                if l_bot != l_top:
                    s.read_pm(l_bot)
                s.compute(3800.0)  # flops, twiddles, transposes, sync slack
                if (j + 1 + 3 * c) % epoch == 0 or b == hi - 1:
                    for ln in flushes[c][e_idx]:
                        if budget > 0:
                            s.persist(ln)
                            budget -= 1
                        s.compute(3.0)
                    prev = flushes[(c - 1) % n_cores]
                    if e_idx < len(prev) and prev[e_idx]:
                        for ln in prev[e_idx][:2]:
                            s.read_pm(ln)
                    e_idx += 1
        for s in streams:
            s.barrier()
    return _pack(streams, name)


def lu(name: str, seed: int, persist_budget: int, *, n_cores: int, n: int,
       block: int, contiguous: bool) -> Trace:
    """Blocked right-looking LU (Splash-4 LU, ``-n``): pivot-block
    factor, panel updates re-reading the freshly persisted pivot, and
    the trailing update, separated by barriers.  ``seed`` jitters the
    dgemm compute gaps only."""
    rng = np.random.default_rng(seed)
    nb = n // block
    elems_per_line = 8
    streams = [_CoreStream() for _ in range(n_cores)]
    budget = persist_budget

    def block_lines(bi: int, bj: int) -> np.ndarray:
        if contiguous:
            base = (bi * nb + bj) * (block * block // elems_per_line)
            return np.arange(base, base + block * block // elems_per_line)
        rows = bi * block + np.arange(block)
        start = rows * (n // elems_per_line) + (bj * block) // elems_per_line
        width = max(block // elems_per_line, 1)
        return (start[:, None] + np.arange(width)[None, :]).ravel()

    def persist_block(s: _CoreStream, lines: np.ndarray,
                      repeat: int = 1, group_sz: int = 2) -> None:
        nonlocal budget
        for group in np.array_split(lines, max(len(lines) // group_sz, 1)):
            for _ in range(repeat):
                for ln in group:
                    s.read_pm(int(ln))
                    s.compute(30.0)
                    if budget > 0:
                        s.persist(int(ln))
                        budget -= 1

    for k in range(nb):
        owner = k % n_cores
        persist_block(streams[owner], block_lines(k, k),
                      repeat=1 if contiguous else 2)
        for s in streams:
            s.barrier()
        panels = [(k, j) for j in range(k + 1, nb)] + \
                 [(i, k) for i in range(k + 1, nb)]
        for p_idx, (bi, bj) in enumerate(panels):
            s = streams[p_idx % n_cores]
            for ln in block_lines(k, k):
                s.read_pm(int(ln))
                s.compute(4.0)
            persist_block(s, block_lines(bi, bj),
                          repeat=1 if contiguous else 2)
        for s in streams:
            s.barrier()
        trailing = [(i, j) for i in range(k + 1, nb) for j in range(k + 1, nb)]
        for t_i, (bi, bj) in enumerate(trailing):
            s = streams[bj % n_cores]
            s.compute((2800.0 if contiguous else 1500.0)
                      * float(rng.exponential(1.0)))
            for ln in block_lines(bi, k):
                s.read_pm(int(ln))
            for ln in block_lines(k, bj):
                s.read_pm(int(ln))
            persist_block(s, block_lines(bi, bj),
                          repeat=2 if (t_i % 4 == 0 or not contiguous) else 1)
        for s in streams:
            s.barrier()
        if budget <= 0:
            break
    return _pack(streams, name)


def signature(name: str, seed: int, persist_budget: int, *, n_cores: int,
              n_iters: int, hot_lines: int, cold_lines: int,
              p_persist: float, p_hot_write: float, reads_per_iter: float,
              p_read_recent: float, compute_ns: float,
              recent_window: int = 8, zipf_a: float = 1.4,
              persist_burst: int = 1, p_read_mid: float = 0.0,
              mid_window: int = 256, p_shared: float = 1.0,
              recent_global: bool = False) -> Trace:
    """A workload's published locality signature: Zipf hot-set persists
    (coalescing), reads of recently persisted lines (read forwarding),
    mid-distance reads and bursty persists."""
    rng = np.random.default_rng(seed)
    streams = [_CoreStream() for _ in range(n_cores)]
    budget = persist_budget
    shared_recent: List[int] = []
    recent: List[List[int]] = [shared_recent] * n_cores if recent_global \
        else [[] for _ in range(n_cores)]
    mid: List[int] = []
    ranks = np.arange(1, hot_lines + 1, dtype=np.float64)
    zipf_p = ranks ** (-zipf_a)
    zipf_p /= zipf_p.sum()
    next_cold = hot_lines
    slice_sz = max(hot_lines // n_cores, 1)

    def pick_persist_line(c: int) -> int:
        nonlocal next_cold
        if rng.random() < p_hot_write:
            z = int(rng.choice(hot_lines, p=zipf_p))
            if rng.random() < p_shared:
                return z
            return (c * slice_sz + z % slice_sz) % hot_lines
        next_cold += 1
        return hot_lines + (next_cold % cold_lines)

    for _ in range(n_iters):
        if budget <= 0:
            break
        for c in range(n_cores):
            s = streams[c]
            s.compute(compute_ns * float(rng.exponential(1.0)))
            n_reads = rng.poisson(reads_per_iter)
            for _ in range(n_reads):
                r = recent[c]
                u = rng.random()
                if r and u < p_read_recent:
                    line = r[rng.integers(len(r))]
                elif mid and u < p_read_recent + p_read_mid:
                    line = mid[rng.integers(len(mid))]
                else:
                    line = hot_lines + int(rng.integers(cold_lines))
                s.read_pm(line)
            if rng.random() < p_persist and budget > 0:
                for _ in range(persist_burst):
                    if budget <= 0:
                        break
                    line = pick_persist_line(c)
                    s.persist(line)
                    budget -= 1
                    recent[c].append(line)
                    if len(recent[c]) > recent_window:
                        mid.append(recent[c].pop(0))
                        if len(mid) > mid_window:
                            mid.pop(0)
    return _pack(streams, name)


def probe(name: str, seed: int, persist_budget: int, *, n_ops: int,
          gap_ns: float, read_base: int) -> Trace:
    """Fig. 1's latency probe: one core alternating a persist of line i
    and a partner read of line ``read_base + i``, ``gap_ns`` of compute
    before every op.  ``persist_budget`` is unused: the probe's size is
    ``n_ops`` persist/read pairs."""
    del seed, persist_budget
    ops, addrs = [], []
    for i in range(n_ops):
        ops += [int(Op.PERSIST), int(Op.PM_READ)]
        addrs += [i, read_base + i]
    return Trace(ops=np.array([ops], np.int32),
                 addrs=np.array([addrs], np.int32),
                 gaps=np.full((1, len(ops)), gap_ns, np.float32),
                 lengths=np.array([len(ops)], np.int32), name=name)


KINDS = {"fft": fft, "lu": lu, "signature": signature, "probe": probe}


# ---------------------------------------------------------- seed entries
def _rng(seed: int, k: int) -> np.random.Generator:
    # seeds reach past 2**31: feed the generator 32-bit words
    s = seed % (1 << 64)
    return np.random.default_rng([s & 0xFFFFFFFF, s >> 32, k])


def relabel_pm_lines(tr: Trace, rng: np.random.Generator) -> Trace:
    """Rename the trace's PM lines by a random bijection of themselves."""
    ops, addrs = tr.ops, tr.addrs.copy()
    live = np.arange(ops.shape[1])[None, :] < tr.lengths[:, None]
    pm = live & np.isin(ops, (int(Op.PM_READ), int(Op.PERSIST))) \
        & (addrs < DRAM_BASE)
    lines = np.unique(addrs[pm])
    perm = rng.permutation(lines)
    addrs[pm] = perm[np.searchsorted(lines, addrs[pm])]
    return Trace(ops=ops, addrs=addrs, gaps=tr.gaps, lengths=tr.lengths,
                 name=tr.name)


def permute_gaps(tr: Trace, rng: np.random.Generator, spread: float
                 ) -> Trace:
    """Give each core an evenly spaced set of gaps around its own mean
    gap (``mean * (1 +- spread)``), shuffled by the seed."""
    gaps = tr.gaps.copy()
    for c in range(tr.n_cores):
        n = int(tr.lengths[c])
        if n == 0:
            continue
        mean = float(np.mean(tr.gaps[c, :n], dtype=np.float64))
        fixed = mean * (1.0 + spread * np.linspace(-1.0, 1.0, n))
        gaps[c, :n] = rng.permutation(fixed).astype(np.float32)
    return Trace(ops=tr.ops, addrs=tr.addrs, gaps=gaps, lengths=tr.lengths,
                 name=tr.name)


def build(traffic: dict, seed: "int | None", persist_budget: int
          ) -> List[Trace]:
    """The traces of one traffic mix under a configuration's persist
    budget (its ROI cap on persists per workload, all cores together);
    ``seed=None`` leaves out the seed's entry (the streams exactly as
    their generators make them)."""
    budget = int(persist_budget)
    entry = traffic["seed_enters"]
    out = []
    for k, spec in enumerate(traffic["streams"]):
        tr = KINDS[spec["kind"]](spec["name"], int(spec["gen_seed"]), budget,
                                 **spec["params"])
        if seed is not None:
            rng = _rng(seed, k)
            if entry["kind"] == "relabel_pm_lines":
                tr = relabel_pm_lines(tr, rng)
            elif entry["kind"] == "permute_gaps":
                tr = permute_gaps(tr, rng, float(entry["spread"]))
            else:
                raise ValueError(f"unknown seed entry {entry['kind']!r}")
        out.append(tr)
    return out
