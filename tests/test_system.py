"""End-to-end system tests: train -> crash -> recover -> resume, with the
PCS persistence tier in each scheme."""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import SyntheticLMDataset
from repro.launch.steps import make_train_step
from repro.launch.train import make_manager, restore_state, save_state
from repro.models.transformer import init_params
from repro.optim import AdamWConfig, adamw_init
from repro.persistence import (DurableStore, HostBufferTier,
                               PCSCheckpointManager, PersistScheme)


class Args:
    def __init__(self, ckpt_dir, scheme="pb_rf"):
        self.scheme = scheme
        self.buffer_mb = 64
        self.ckpt_dir = ckpt_dir
        self.store_delay_ms = 1.0


@pytest.mark.slow
@pytest.mark.parametrize("scheme", ["nopb", "pb", "pb_rf"])
def test_train_crash_resume(tmp_path, scheme):
    cfg = get_config("smollm-135m", smoke=True)
    opt_cfg = AdamWConfig(lr=1e-3, total_steps=20)
    params = init_params(cfg, jax.random.key(0))
    opt_state = adamw_init(opt_cfg, params)
    data = SyntheticLMDataset(cfg.vocab, 16, 2)
    step = make_train_step(cfg, opt_cfg)

    mgr = make_manager(Args(str(tmp_path), scheme))
    losses = []
    for i in range(6):
        batch = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(m["loss"]))
        if (i + 1) % 3 == 0:
            save_state(mgr, i + 1, params, opt_state, data.state())
    # crash the manager (drainer killed, volatile routing lost), recover
    mgr.crash()
    mgr.recover()

    # a NEW manager over the same durable store must restore step 6 state
    mgr2 = make_manager(Args(str(tmp_path), scheme))
    p2 = init_params(cfg, jax.random.key(1))      # different init
    o2 = adamw_init(opt_cfg, p2)
    rec = restore_state(mgr2, p2, o2)
    assert rec is not None
    ver, p2, o2, data_state = rec
    assert ver == 6
    # restored params equal the live ones
    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
              for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
    assert err == 0.0
    # training continues from the restored state without loss blow-up
    data2 = SyntheticLMDataset(cfg.vocab, 16, 2)
    data2.restore(data_state)
    batch = {k: jnp.asarray(v) for k, v in data2.next_batch().items()}
    _, _, m = step(p2, o2, batch)
    assert abs(float(m["loss"]) - losses[-1]) < 1.0
    mgr2.close()


def test_restore_prefers_buffer_forwarding(tmp_path):
    """RF: a restore right after persist is served by the buffer tier."""
    buf = HostBufferTier(capacity_bytes=64 << 20)
    store = DurableStore(str(tmp_path / "s"), write_delay_s=0.05)
    mgr = PCSCheckpointManager(buf, store, scheme=PersistScheme.PB_RF)
    mgr.persist("w", 1, np.ones(1000))
    got = mgr.restore("w")                        # store write still in flight
    assert got[0] == 1
    assert mgr.stats["restore_forwarded"] == 1
    mgr.close()


@pytest.mark.slow
def test_cli_train_runs(tmp_path):
    """The launcher CLI end-to-end (smallest smoke config)."""
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    cmd = [sys.executable, "-m", "repro.launch.train", "--arch",
           "smollm-135m", "--smoke", "--steps", "4", "--batch", "2",
           "--seq", "16", "--ckpt-every", "2",
           "--ckpt-dir", str(tmp_path / "ck"), "--store-delay-ms", "1"]
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=300,
                         env=env, cwd=Path(__file__).resolve().parents[1])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "train done" in out.stdout
