"""Crash-recovery demo across all three PCS layers (DESIGN.md §2).

  A — untimed oracle: the exact PB state machine loses power mid-drain;
      recovery (Section V-D4) re-drains every surviving entry and no
      acked version is lost.
  C — timed engine:   the same power loss as a traced ``crash_at_ns``
      scalar; the durability snapshot shows acked == durable and the
      modeled drain-all recovery cost.
  B — checkpoint tier: a training job persists shards, the process
      crashes at a deterministic persist index (``schedule_crash``),
      recovery re-drains the surviving buffer entries and the resume
      restores the acked prefix (read forwarding from the buffer tier).

    PYTHONPATH=src python examples/crash_recovery_demo.py

Runs in seconds; also exercised by ``benchmarks/run.py --smoke`` so it
cannot rot.
"""
import tempfile

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import PCSConfig, Scheme, fuzz_crash_ns, fuzz_trace
from repro.core.engine import simulate
from repro.core.semantics import EventKind, PersistentBuffer
from repro.launch.train import restore_state, save_state
from repro.optim import AdamWConfig, adamw_init
from repro.persistence import (DurableStore, HostBufferTier,
                               PCSCheckpointManager, PersistScheme)


def demo_oracle() -> None:
    print("== Layer A: untimed oracle (core.semantics) ==")
    pb = PersistentBuffer(PCSConfig(scheme=Scheme.PB_RF, n_pbe=4))
    acked = {}
    for i, addr in enumerate([0, 1, 2, 0, 3, 1]):
        for e in pb.persist(addr, f"{addr}@v{i}"):
            if e.kind in (EventKind.PERSIST_ACK, EventKind.COALESCED):
                acked[e.addr] = max(acked.get(e.addr, -1), e.version)
    # power loss with every drain still in flight
    pb.crash()
    events = pb.recover()
    redrained = sum(1 for e in events if e.kind == EventKind.DRAIN_SENT)
    print(f"acked {len(acked)} lines, crashed mid-drain, "
          f"recovery re-drained {redrained} surviving entries")
    for addr, ver in acked.items():
        rec = pb.pm.read(addr)
        assert rec is not None and rec[0] >= ver, f"acked {addr} lost"
    print("no acked version lost: OK")


def demo_engine() -> None:
    print("== Layer C: timed engine (crash_at_ns) ==")
    trace, _ = fuzz_trace(7, n_cores=3, n_slots=40, n_addrs=8)
    cfg = PCSConfig(scheme=Scheme.PB_RF, n_pbe=8)
    full = simulate(trace, cfg, bucket=128, track_addrs=8)
    crashed = simulate(trace, cfg.with_crash(fuzz_crash_ns(20)),
                       bucket=128, track_addrs=8)
    print(f"full run: {full.persists} persists; "
          f"crash at slot 20: {crashed.persists} issued, "
          f"{crashed.acked_persists} acked, "
          f"{crashed.durable_persists} durable")
    assert crashed.acked_persists <= crashed.durable_persists
    print(f"recovery: {crashed.recovery_entries} surviving PBEs, "
          f"drain-all {crashed.recovery_ns:.0f} ns; durable versions "
          f"{np.asarray(crashed.durable_ver).tolist()}")
    print("acked => durable at every crash point: OK")


def demo_checkpoint_tier() -> None:
    print("== Layer B: checkpoint tier (persistence.manager) ==")
    params = {"w": jnp.ones((16, 16)), "b": jnp.zeros((16,))}
    opt = adamw_init(AdamWConfig(), params)
    with tempfile.TemporaryDirectory() as d:
        buf = HostBufferTier(capacity_bytes=64 << 20)
        store = DurableStore(d + "/store", write_delay_s=0.01)
        mgr = PCSCheckpointManager(buf, store, scheme=PersistScheme.PB_RF)
        t = save_state(mgr, 4, params, opt, {"step": 4})
        print(f"persisted v4 in {t:.3f}s (ack-at-buffer; store writes "
              f"continue in background)")
        # power loss right before the *next* save's first shard
        n_shards = mgr.stats["persists"]
        mgr.schedule_crash(n_shards)
        save_state(mgr, 5, params, opt, {"step": 5})   # dropped: power off
        print(f"CRASH after {n_shards} acked shard persists; "
              f"{mgr.stats['lost_after_crash']} v5 shards lost with power")
        n = mgr.recover()
        print(f"recovered: {n} surviving buffer entries re-drained")
        rec = restore_state(mgr, params, opt)
        assert rec is not None and rec[0] == 4, rec
        print(f"resumed at v{rec[0]} "
              f"(read-forwarded={mgr.stats['restore_forwarded']}, "
              f"from-store={mgr.stats['restore_from_store']})")
        mgr.close()


def main() -> None:
    demo_oracle()
    demo_engine()
    demo_checkpoint_tier()
    print("OK")


if __name__ == "__main__":
    use_compile_cache()
    main()
