"""Plain reference of the timed persistent-switch model.

A straightforward sequential simulator of one {trace x configuration}
cell: one op at a time, in issue-time order, over Python lists.  It
follows the model the timed engine implements (paper Sec. V: the PB /
PBC / PBCS state machine with Empty, Dirty and Drain entries, LRU
victims, the PB drain-immediately and PB_RF threshold/preset drain
policies, write coalescing, read forwarding, per-switch buffers along a
chain of CXL switches, a power loss and the Sec. V-D4 recovery pass),
restricted to what the benchmark's configurations use: one tenant, the
default allocation policy, no fabric, no schedule, no latency target and
no address tracking.  It imports nothing of the program and takes
nothing the program made: the latencies come from the configuration
file, the traces from the benchmark's own generator.

Every time is held in the float type ``ftype`` (float64 as the
configurations state; float32 is the control).  The result is a dict
with the fields of the program's ``SimResult``.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

# op kinds, schemes and entry states as the model defines them
COMPUTE, DRAM_READ, DRAM_WRITE, PM_READ, PERSIST, BARRIER = range(6)
NOPB, PB, PB_RF = 0, 1, 2
SCHEMES = {"NOPB": NOPB, "PB": PB, "PB_RF": PB_RF}
EMPTY, DIRTY, DRAIN = 0, 1, 2
INF = 1e30

# per-persist ack-latency histogram: bin 0 below 256 ns, then sqrt(2)
# spaced bins, the last open above
LAT_HIST_MIN_NS = 256.0
N_LAT_BINS = 28

# per-switch statistics columns
H_FWD_SUM, H_FWD_CNT, H_COALESCES, H_BYPASS, H_READ_HITS = range(5)


def lat_bin(lat: float) -> int:
    x = math.floor(math.log2(max(float(lat), 1.0) / LAT_HIST_MIN_NS) * 2.0)
    return min(max(int(x) + 1, 0), N_LAT_BINS - 1)


class Params:
    """The latencies and policy counts of one cell, in ``ftype``."""

    def __init__(self, machine: dict, grid_row: dict, crash_at: float,
                 ftype):
        F = ftype
        lat = machine["latency"]
        self.F = F
        self.scheme = SCHEMES[grid_row["scheme"]]
        n_sw = int(grid_row["n_switches"])
        self.n_switches = n_sw
        self.n_pbe = int(machine["n_pbe"])
        self.banks = int(machine["pm_banks"])
        link, pipe = lat["link_ns"], lat["switch_pipe_ns"]
        # one-way paths through a chain of n_sw switches (0 = direct)
        if n_sw == 0:
            ow_cpu_pm, ow_cpu_sw1, ow_sw1_pm = lat["cpu_link_ns"], \
                lat["cpu_link_ns"], 0.0
        else:
            ow_cpu_pm = (n_sw + 1) * link + n_sw * pipe
            ow_cpu_sw1 = link + pipe
            ow_sw1_pm = n_sw * link + (n_sw - 1) * pipe
        scale = math.sqrt(max(self.n_pbe, 1) / 16.0)
        self.ow_cpu_pm = F(ow_cpu_pm)
        self.ow_cpu_sw1 = F(ow_cpu_sw1)
        self.ow_sw1_pm = F(ow_sw1_pm)
        self.hop_ns = F(link + pipe)
        self.link_ns = F(link)
        self.tag_ns = F(lat["pb_tag_ns"] * scale)
        self.data_ns = F(lat["pb_data_ns"] * scale)
        for k in ("pbc_proc_ns", "pbc_occ_ns", "pbc_read_ns",
                  "pbc_read_occ_ns", "nvm_read_ns", "nvm_write_ns",
                  "nvm_read_occ_ns", "nvm_write_occ_ns", "dram_ns",
                  "fwd_margin_ns", "switch_pipe_ns"):
            setattr(self, k, F(lat[k]))
        self.thr = max(1, math.ceil(machine["drain_threshold"] * self.n_pbe))
        self.pre = max(0, math.floor(machine["drain_preset"] * self.n_pbe))
        self.low_water = int(machine["low_water_drains"])
        self.empty_slack = int(machine["empty_slack"])
        self.crash = F(min(crash_at, INF))
        self.INF = F(INF)
        self.zero = F(0.0)


class Batch:
    """Packets on the wire between two switches, in wire order."""

    def __init__(self, active, addr, emit, ohop, oslot):
        self.active, self.addr, self.emit = active, addr, emit
        self.ohop, self.oslot = ohop, oslot


class Machine:
    """The whole simulated machine at one instant."""

    def __init__(self, p: Params, n_cores: int):
        P, D, z = p.n_pbe, max(p.n_switches - 1, 0), p.zero
        self.p = p
        self.clock = [z] * n_cores
        self.ptr = [0] * n_cores
        self.blocked = [False] * n_cores
        self.bcount = 0
        self.tag = [-1] * P
        self.state = [EMPTY] * P
        self.lru = [z] * P
        self.dd = [z] * P
        self.pm_busy = [z] * p.banks
        self.pbc_busy = z
        # deep switches 2..n_switches, row j = switch j + 2
        self.rows = [dict(tag=[-1] * P, state=[EMPTY] * P, lru=[z] * P,
                          ddd=[z] * P, wt=[z] * P) for _ in range(D)]
        self.hpbc = [z] * D
        self.hop_stats = [[z] * 5 for _ in range(D + 1)]
        self.s = dict(persist_sum=z, persist_cnt=0, read_sum=z, read_cnt=0,
                      read_hits=0, coalesces=0, pm_writes=0, stall=z,
                      pi_detours=0, victims=0, acked=0, durable=0)
        self.hist = [0] * N_LAT_BINS

    # ------------------------------------------------------------ chain
    def _place(self, j: int, rows, hpbc_j, batch: Batch, hop_stats):
        """Commit a batch into switch j + 2, then run its drain-down."""
        p = self.p
        P = p.n_pbe
        row = rows[j]
        Q = len(batch.active)
        act = batch.active
        # FIFO service of the switch's PBC, packet by packet
        starts = [p.INF] * Q
        busy = hpbc_j
        rank = -1
        run = -p.INF
        for q in range(Q):
            if act[q]:
                rank += 1
                run = max(run, batch.emit[q] + p.hop_ns - p.pbc_occ_ns * rank)
                starts[q] = p.pbc_occ_ns * rank + max(run, hpbc_j)
                busy = max(busy, starts[q] + p.pbc_occ_ns)
        hpbc_j = max(busy, hpbc_j)
        classify = [starts[q] + p.pbc_proc_ns + p.tag_ns for q in range(Q)]
        commit = [classify[q] + p.data_ns for q in range(Q)]
        live = [classify[q] for q in range(Q) if act[q]]
        t0 = min(live) if live else -p.INF
        state0 = [EMPTY if (row["state"][s] == DRAIN and row["ddd"][s] <= t0)
                  else row["state"][s] for s in range(P)]
        tag1, state1 = list(row["tag"]), list(state0)
        lru1, wt1 = list(row["lru"]), list(row["wt"])
        empties = [s for s in range(P) if state0[s] == EMPTY]
        n_alloc = 0
        ended = [False] * Q
        bypass = [False] * Q
        hs = [list(r) for r in hop_stats]
        fwd_sum = p.zero
        for q in range(Q):
            if not act[q]:
                continue
            gate = commit[q] <= p.crash
            co = [s for s in range(P)
                  if state0[s] == DIRTY and row["tag"][s] == batch.addr[q]]
            if co:
                s = co[0]
                ended[q] = True
                if gate:
                    lru1[s] = wt1[s] = commit[q]
                    hs[j + 1][H_COALESCES] += 1
            elif n_alloc < len(empties):
                s = empties[n_alloc]
                n_alloc += 1
                ended[q] = True
                if gate:
                    tag1[s], state1[s] = batch.addr[q], DIRTY
                    lru1[s] = wt1[s] = commit[q]
            else:
                n_alloc += 1
                bypass[q] = True
                if gate:
                    hs[j + 1][H_BYPASS] += 1
            if ended[q] and gate:
                hs[j + 1][H_FWD_CNT] += 1
                fwd_sum += commit[q] - batch.emit[q]
        hs[j + 1][H_FWD_SUM] += fwd_sum
        dd_vals = [commit[q] + ((j + 2) - (batch.ohop[q] + 1)) * p.hop_ns
                   for q in range(Q)]
        # this switch's own drain-down: PB forwards everything it holds,
        # PB_RF drains down to the preset once the threshold is reached
        dirty = [s for s in range(P) if state1[s] == DIRTY]
        nd = len(dirty)
        if p.scheme == PB:
            k = nd
        else:
            k = nd - p.pre if nd >= p.thr else 0
        order = sorted(dirty, key=lambda s: lru1[s])
        drained = order[:k]
        state2 = list(state1)
        for s in drained:
            state2[s] = DRAIN
        t_row = max([commit[q] for q in range(Q)
                     if ended[q] and commit[q] <= p.crash] + [-p.INF])
        t_row = max(t_row, p.zero)
        nxt = Batch(
            active=bypass + [True] * len(drained),
            addr=list(batch.addr) + [tag1[s] for s in drained],
            emit=[classify[q] if bypass[q] else p.zero for q in range(Q)]
            + [t_row] * len(drained),
            ohop=list(batch.ohop) + [j + 1] * len(drained),
            oslot=list(batch.oslot) + drained)
        new_row = dict(tag=tag1, state=state2, lru=lru1, wt=wt1,
                       ddd=row["ddd"])
        return new_row, hpbc_j, hs, dd_vals, ended, nxt

    def _pm_land(self, pos: int, batch: Batch, pm_busy):
        """Packets past the last switch write through to PM."""
        p = self.p
        rem = max(p.n_switches - pos, 0)
        path_down = p.link_ns + rem * p.hop_ns
        seen = [0] * p.banks
        busy_after = [None] * p.banks
        dd_vals = [p.zero] * len(batch.active)
        n = 0
        for q, a in enumerate(batch.active):
            if not a:
                continue
            b = batch.addr[q] % p.banks
            start = max(pm_busy[b], batch.emit[q] + path_down) \
                + seen[b] * p.nvm_write_occ_ns
            seen[b] += 1
            up = p.link_ns + max(p.n_switches - (batch.ohop[q] + 1), 0) \
                * p.hop_ns
            dd_vals[q] = start + p.nvm_write_ns + up
            end = start + p.nvm_write_occ_ns
            busy_after[b] = end if busy_after[b] is None \
                else max(busy_after[b], end)
            n += 1
        pm_busy = [pm_busy[b] if busy_after[b] is None
                   else max(pm_busy[b], busy_after[b])
                   for b in range(p.banks)]
        return pm_busy, dd_vals, n

    @staticmethod
    def _scatter(dd1, rows, batch: Batch, vals, mask):
        """Each packet that ended acks its origin entry (the last packet
        from one origin entry wins)."""
        dd1 = list(dd1)
        ddd = [list(r["ddd"]) for r in rows]
        for q in range(len(batch.active)):
            if not mask[q]:
                continue
            if batch.ohop[q] == 0:
                dd1[batch.oslot[q]] = vals[q]
            else:
                ddd[batch.ohop[q] - 1][batch.oslot[q]] = vals[q]
        rows = [dict(r, ddd=d) for r, d in zip(rows, ddd)]
        return dd1, rows

    def forward_chain(self, rows, hpbc, hop_stats, batch: Batch, dd1,
                      pm_busy):
        """Carry a drain batch of switch 1 down the chain to PM."""
        p = self.p
        rows, hpbc = list(rows), list(hpbc)
        for j in range(p.n_switches - 1):
            row, hpbc_j, hop_stats, vals, ended, nxt = self._place(
                j, rows, hpbc[j], batch, hop_stats)
            rows[j] = row
            hpbc[j] = hpbc_j
            dd1, rows = self._scatter(
                dd1, rows, batch, vals,
                [a and e for a, e in zip(batch.active, ended)])
            batch = nxt
        pm_busy, vals, n = self._pm_land(p.n_switches, batch, pm_busy)
        dd1, rows = self._scatter(dd1, rows, batch, vals, batch.active)
        return dd1, rows, hpbc, hop_stats, pm_busy, n

    # ------------------------------------------------------------- ops
    def pm_read(self, c: int, t, addr: int):
        p, s = self.p, self.s
        bank = addr % p.banks
        if p.scheme == NOPB:
            start = max(self.pm_busy[bank], t + p.ow_cpu_pm)
            resp = start + p.nvm_read_ns + p.ow_cpu_pm
            self.pm_busy[bank] = start + p.nvm_read_occ_ns
        else:
            start_dir = max(self.pm_busy[bank], t + p.ow_cpu_pm)
            resp_dir = start_dir + p.nvm_read_ns + p.ow_cpu_pm
            self.state = [EMPTY if (st == DRAIN and d <= t) else st
                          for st, d in zip(self.state, self.dd)]
            live = [i for i in range(p.n_pbe)
                    if self.tag[i] == addr and self.state[i] != EMPTY]
            has = bool(live)
            dirty = [i for i in live if self.state[i] == DIRTY]
            idx = dirty[0] if dirty else (live[0] if live else 0)
            arr = t + p.ow_cpu_sw1
            pbc_start = max(self.pbc_busy, arr) \
                + (p.pbc_read_ns + p.tag_ns)
            served = has and (self.state[idx] == DIRTY or (
                self.state[idx] == DRAIN
                and self.dd[idx] > pbc_start + p.fwd_margin_ns))
            deep_hit, resp_deep, deep_at = False, None, None
            if not has and p.n_switches >= 2:
                deep_hit, resp_deep, deep_at = self._deep_read(t, addr)
            if has:
                self.pbc_busy = max(self.pbc_busy, arr) + p.pbc_read_occ_ns
                s["pi_detours"] += 1
                if served:
                    resp = pbc_start + p.data_ns + p.ow_cpu_sw1
                    self.lru[idx] = t
                    self.hop_stats[0][H_READ_HITS] += 1
                else:
                    fwd = max(self.pm_busy[bank], pbc_start
                              + p.switch_pipe_ns + p.ow_sw1_pm)
                    resp = fwd + p.nvm_read_ns + p.ow_cpu_pm
                    self.pm_busy[bank] = fwd + p.nvm_read_occ_ns
            elif deep_hit:
                resp = resp_deep
                j, slot = deep_at
                self.rows[j]["lru"][slot] = t
                self.hop_stats[j + 1][H_READ_HITS] += 1
            else:
                resp = resp_dir
                self.pm_busy[bank] = start_dir + p.nvm_read_occ_ns
            if (has and served) or deep_hit:
                s["read_hits"] += 1
        s["read_sum"] += resp - t
        s["read_cnt"] += 1
        self.clock[c] = resp

    def _deep_read(self, t, addr: int):
        """The shallowest deeper switch holding a servable copy."""
        p = self.p
        for j, row in enumerate(self.rows):
            arr = t + p.ow_cpu_sw1 + (j + 1.0) * p.hop_ns
            ok = [i for i in range(p.n_pbe)
                  if row["tag"][i] == addr and row["state"][i] != EMPTY
                  and row["wt"][i] <= t
                  and (row["state"][i] == DIRTY
                       or row["ddd"][i] > arr + p.fwd_margin_ns)]
            if ok:
                dirty = [i for i in ok if row["state"][i] == DIRTY]
                slot = dirty[0] if dirty else ok[0]
                resp = arr + p.pbc_read_ns + p.tag_ns + p.data_ns \
                    + p.ow_cpu_sw1 + (j + 1.0) * p.hop_ns
                return True, resp, (j, slot)
        return False, None, None

    def persist(self, c: int, t, addr: int):
        p, s = self.p, self.s
        if p.scheme == NOPB:
            bank = addr % p.banks
            start = max(self.pm_busy[bank], t + p.ow_cpu_pm)
            ack = start + p.nvm_write_ns + p.ow_cpu_pm
            ok = ack <= p.crash
            self.pm_busy[bank] = start + p.nvm_write_occ_ns
            s["pm_writes"] += 1
            s["acked"] += ok
            s["durable"] += ok
        else:
            ack = self._persist_buffered(t, addr)
        s["persist_sum"] += ack - t
        s["persist_cnt"] += 1
        self.hist[lat_bin(ack - t)] += 1
        self.clock[c] = ack

    def _persist_buffered(self, t, addr: int):
        p, s = self.p, self.s
        P, B, crash = p.n_pbe, p.banks, p.crash
        is_rf = p.scheme == PB_RF
        chain = p.n_switches >= 2
        bank = addr % B
        arr = t + p.ow_cpu_sw1
        pbc_prev = self.pbc_busy
        pbc_start = max(pbc_prev, arr) + (p.pbc_proc_ns + p.tag_ns)
        state1 = [EMPTY if (st == DRAIN and d <= pbc_start) else st
                  for st, d in zip(self.state, self.dd)]
        match = [i for i in range(P)
                 if self.tag[i] == addr and state1[i] == DIRTY]
        coalesce = is_rf and bool(match)

        def oldest(states, key):
            cand = [i for i in range(P) if state1[i] in states]
            if not cand:
                return False, 0
            return True, min(cand, key=lambda i: (key[i], i))

        any_empty, empty_idx = oldest((EMPTY,), self.lru)
        any_dirty, victim = oldest((DIRTY,), self.lru)
        _, earliest = oldest((DRAIN,), self.dd)
        needs_victim = not coalesce and not any_empty and any_dirty
        vic_emit = needs_victim and pbc_start <= crash
        vbank = self.tag[victim] % B
        v_start = max(self.pm_busy[vbank], pbc_start + p.ow_sw1_pm)
        victim_dd = v_start + p.nvm_write_ns + p.ow_sw1_pm
        rows, hpbc, hop_stats = self.rows, self.hpbc, self.hop_stats
        if chain:
            # the victim packet leaves the PBC first, down the chain
            vb = Batch([vic_emit], [self.tag[victim]], [pbc_start], [0],
                       [victim])
            dd_v, rows, hpbc, hop_stats, pmb_v, w_v = self.forward_chain(
                rows, hpbc, hop_stats, vb, self.dd, self.pm_busy)
            vic_wait = dd_v[victim] if vic_emit else victim_dd
        else:
            vic_wait = victim_dd
        if any_empty:
            slot, ta = empty_idx, pbc_start
        elif any_dirty:
            slot, ta = victim, vic_wait
        else:
            slot, ta = earliest, max(pbc_start, self.dd[earliest])
        pm_busy1 = list(self.pm_busy)
        state2, dd2 = list(state1), list(self.dd)
        if needs_victim:
            pm_busy1[vbank] = v_start + p.nvm_write_occ_ns
            state2[victim] = DRAIN
            dd2[victim] = victim_dd
        wslot = match[0] if coalesce else slot
        t_written = (pbc_start if coalesce else ta) + p.data_ns
        ack = t_written + p.ow_cpu_sw1
        state3, tag3, lru3 = state2, list(self.tag), list(self.lru)
        state3[wslot], tag3[wslot], lru3[wslot] = DIRTY, addr, t_written
        dd4, pm_busy2 = list(dd2), list(pm_busy1)
        state4 = list(state3)
        if is_rf:
            dirty = [i for i in range(P) if state3[i] == DIRTY]
            n_empty = sum(1 for i in range(P) if state3[i] == EMPTY)
            k_thresh = len(dirty) - p.pre if len(dirty) >= p.thr else 0
            k_low = min(p.low_water, len(dirty)) \
                if n_empty <= p.empty_slack else 0
            k = max(k_thresh, k_low)
            drained = sorted(dirty, key=lambda i: (lru3[i], i))[:k]
            seen = [0] * B
            for i in drained:
                b = tag3[i] % B
                start = max(pm_busy1[b], t_written + p.ow_sw1_pm) \
                    + seen[b] * p.nvm_write_occ_ns
                seen[b] += 1
                state4[i] = DRAIN
                dd4[i] = start + p.nvm_write_ns + p.ow_sw1_pm
                pm_busy2[b] = max(pm_busy2[b], start + p.nvm_write_occ_ns)
            policy_writes = k
        else:
            start = max(pm_busy1[bank], t_written + p.ow_sw1_pm)
            state4[wslot] = DRAIN
            dd4[wslot] = start + p.nvm_write_ns + p.ow_sw1_pm
            pm_busy2[bank] = start + p.nvm_write_occ_ns
            drained = [wslot]
            policy_writes = 1
        commit = t_written <= crash
        if commit:
            self.state, self.tag, self.lru = state4, tag3, lru3
            dd5, pm_busy3 = dd4, pm_busy2
        else:
            self.state = [DRAIN if (vic_emit and i == victim) else st
                          for i, st in enumerate(self.state)]
            dd5 = list(self.dd)
            if vic_emit:
                dd5[victim] = victim_dd
            pm_busy3 = pm_busy1
        writes = int(vic_emit) + (policy_writes if commit else 0)
        if chain:
            # the policy's drains leave together at t_written, in LRU
            # order; the chain's acks replace the PM-path values
            out = sorted(drained, key=lambda i: (lru3[i], i)) \
                if commit else []
            pb = Batch([True] * len(out), [tag3[i] for i in out],
                       [t_written] * len(out), [0] * len(out), out)
            dd5, rows, hpbc, hop_stats, pm_busy3, w_c = self.forward_chain(
                rows, hpbc, hop_stats, pb, dd4 if commit else dd_v, pmb_v)
            writes = w_v + w_c
            self.rows, self.hpbc = rows, hpbc
            self.hop_stats = hop_stats
        self.dd, self.pm_busy = dd5, pm_busy3
        hs0 = self.hop_stats[0]
        if commit:
            hs0[H_FWD_CNT] += 1
            hs0[H_FWD_SUM] += t_written - arr
            hs0[H_COALESCES] += coalesce
        stall = p.zero if coalesce else ta - pbc_start
        hold = max(pbc_prev, arr) + p.pbc_occ_ns
        self.pbc_busy = hold if (coalesce or ta <= pbc_start) \
            else max(hold, ta)
        s["victims"] += (not coalesce) and (not any_empty)
        s["coalesces"] += coalesce
        s["pm_writes"] += writes
        s["stall"] += stall
        s["acked"] += ack <= crash
        s["durable"] += commit
        return ack

    def barrier(self, c: int, t, n_live: int):
        if self.bcount + 1 >= n_live:
            self.clock = [t if b else x
                          for b, x in zip(self.blocked, self.clock)]
            self.clock[c] = t
            self.blocked = [False] * len(self.blocked)
            self.bcount = 0
        else:
            self.clock[c] = self.p.F(INF * 0.9)
            self.blocked[c] = True
            self.bcount += 1

    # ------------------------------------------------------------ main loop
    def run(self, ops, addrs, gaps, lengths):
        """Run every op of the trace; returns the fields of a result."""
        p = self.p
        F = p.F
        C = len(lengths)
        n_live = sum(1 for n in lengths if n > 0)
        gaps = [[F(g) for g in row[:n]] for row, n in zip(gaps, lengths)]
        while True:
            best, c = p.INF, -1
            for k in range(C):
                if self.ptr[k] < lengths[k] and not self.blocked[k]:
                    ts = self.clock[k] + gaps[k][self.ptr[k]]
                    if ts < best:
                        best, c = ts, k
            if c < 0 or not best < p.INF * 0.5:
                break
            i = self.ptr[c]
            self.ptr[c] += 1
            if best > p.crash:
                # the machine is off: the op never happens
                self.clock[c] = best
                continue
            op, addr = int(ops[c][i]), int(addrs[c][i])
            if op == COMPUTE or op == DRAM_WRITE:
                self.clock[c] = best
            elif op == DRAM_READ:
                self.clock[c] = best + p.dram_ns
            elif op == PM_READ:
                self.pm_read(c, best, addr)
            elif op == PERSIST:
                self.persist(c, best, addr)
            elif op == BARRIER:
                self.barrier(c, best, n_live)
        return self.result()

    def recovery(self):
        """Sec. V-D4: survivors per switch and the drain-all burst."""
        p = self.p
        if p.scheme == NOPB:
            return 0, 0.0, []
        per_bank = [0] * p.banks
        per_hop = []
        n = 0
        for i in range(p.n_pbe):
            if self.state[i] == DIRTY or (self.state[i] == DRAIN
                                          and self.dd[i] > p.crash):
                per_bank[self.tag[i] % p.banks] += 1
                n += 1
        per_hop.append(n)
        for row in self.rows:
            n = 0
            for i in range(p.n_pbe):
                if row["wt"][i] <= p.crash and (
                        row["state"][i] == DIRTY
                        or (row["state"][i] == DRAIN
                            and row["ddd"][i] > p.crash)):
                    per_bank[row["tag"][i] % p.banks] += 1
                    n += 1
            per_hop.append(n)
        total = sum(per_hop)
        cost = (max(per_bank) - 1) * p.nvm_write_occ_ns + p.nvm_write_ns \
            + 2.0 * p.ow_sw1_pm if total > 0 else 0.0
        return total, float(cost), per_hop

    def result(self) -> Dict[str, object]:
        p, s = self.p, self.s
        runtime = max([min(x, p.crash) if x < p.INF * 0.5 else p.zero
                       for x in self.clock])
        n_rec, rec_ns, per_hop = self.recovery()
        n_hops = 0 if p.scheme == NOPB else p.n_switches

        def mean(total, n):
            return float(total) / n if n > 0 else float("nan")

        return dict(
            runtime_ns=float(runtime),
            persist_lat_ns=mean(s["persist_sum"], s["persist_cnt"]),
            read_lat_ns=mean(s["read_sum"], s["read_cnt"]),
            persists=s["persist_cnt"], pm_reads=s["read_cnt"],
            read_hits=s["read_hits"], coalesces=s["coalesces"],
            pm_writes=s["pm_writes"], stall_ns=float(s["stall"]),
            pi_detours=s["pi_detours"], victim_drains=s["victims"],
            acked_persists=s["acked"], durable_persists=s["durable"],
            recovery_entries=n_rec, recovery_ns=rec_ns,
            hop_stats=([[float(x) for x in r]
                        for r in self.hop_stats[:n_hops]]
                       if n_hops else None),
            hop_recovery=per_hop[:n_hops] if n_hops else None,
            lat_hist=[float(x) for x in self.hist],
        )


def nominal_span_ns(gaps, lengths) -> float:
    """The longest core's sum of compute gaps."""
    return max(float(np.sum(np.asarray(g[:n], np.float64)))
               for g, n in zip(gaps, lengths))


def simulate_cell(trace, machine: dict, grid_row: dict, span_ns: float,
                  ftype=float) -> Dict[str, object]:
    """One cell: ``trace`` has ``ops``, ``addrs``, ``gaps``, ``lengths``."""
    crash = math.inf
    if "crash_at_span_fraction" in grid_row:
        crash = float(grid_row["crash_at_span_fraction"]) * span_ns
    p = Params(machine, grid_row, crash, ftype)
    lengths = [int(n) for n in trace.lengths]
    m = Machine(p, len(lengths))
    return m.run(np.asarray(trace.ops).tolist(),
                 np.asarray(trace.addrs).tolist(),
                 np.asarray(trace.gaps).tolist(), lengths)


def simulate_grid(traces, config: dict, ftype=float) -> List[List[dict]]:
    """Every {trace x grid row} cell of a configuration."""
    span = max(nominal_span_ns(t.gaps.tolist(), t.lengths.tolist())
               for t in traces)
    return [[simulate_cell(t, config["machine"], g, span, ftype)
             for g in config["grid"]] for t in traces]
