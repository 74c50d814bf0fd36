"""Runtime substrate: failure detection, elastic remesh, stragglers,
optimizer and data pipeline."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.data import SyntheticLMDataset
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         cosine_schedule, global_norm, topk_compress_grads)
from repro.runtime import (FailureDetector, NodeStatus, StragglerMitigator,
                           plan_mesh)


def test_failure_detector_states():
    t = [0.0]
    det = FailureDetector(["a", "b"], suspect_after_s=1.0, dead_after_s=3.0,
                          clock=lambda: t[0])
    t[0] = 1.5
    det.heartbeat("a")
    t[0] = 2.0
    st = det.sweep()
    assert st["a"] == NodeStatus.HEALTHY
    assert st["b"] == NodeStatus.SUSPECT
    t[0] = 4.0
    st = det.sweep()
    assert st["a"] == NodeStatus.SUSPECT
    assert st["b"] == NodeStatus.DEAD
    assert det.alive() == ["a"]


def test_elastic_plan_shrinks_data_axis():
    plan = plan_mesh(256, model_parallel=16)
    assert plan.shape == (16, 16) and plan.grad_accum == 1
    plan = plan_mesh(255, model_parallel=16)
    assert plan.shape == (15, 16) and plan.grad_accum == 2
    plan = plan_mesh(511, model_parallel=16, pods=2)
    assert plan.shape == (2, 15, 16)
    assert plan_mesh(7, model_parallel=16) is None


def test_straggler_flags_and_catchup():
    m = StragglerMitigator(window=16, deadline_factor=2.0)
    for _ in range(10):
        assert not m.observe(1.0)
    assert m.observe(5.0)
    assert m.take_catchup() == 1
    assert m.take_catchup() == 0


def test_adamw_reduces_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1,
                      total_steps=100, schedule="const")
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = adamw_init(cfg, params)
    for _ in range(100):
        grads = {"w": 2 * params["w"]}
        params, state, _ = adamw_update(cfg, params, grads, state)
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.5


def test_grad_clip_bounds_update():
    cfg = AdamWConfig(lr=1.0, clip_norm=1.0, weight_decay=0.0,
                      warmup_steps=1, schedule="const")
    params = {"w": jnp.zeros(4)}
    state = adamw_init(cfg, params)
    _, _, m = adamw_update(cfg, params, {"w": jnp.full(4, 1e6)}, state)
    assert float(m["grad_norm"]) > 1e6  # reported pre-clip


def test_schedule_warmup_and_decay():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100)
    lrs = [float(cosine_schedule(cfg, jnp.asarray(s))) for s in (1, 10, 100)]
    assert lrs[0] < lrs[1]
    assert lrs[2] < 1e-6


def test_topk_compression_error_feedback():
    g = {"w": jnp.asarray([1.0, 0.1, 0.01, 0.001])}
    comp, err = topk_compress_grads(g, None, ratio=0.25)
    assert float(jnp.sum(comp["w"] != 0)) == 1
    # the residual is carried and eventually transmitted
    comp2, err2 = topk_compress_grads(
        jax.tree.map(jnp.zeros_like, g), err, ratio=0.25)
    assert float(comp2["w"][1]) > 0.0


def test_data_pipeline_deterministic_resume():
    d1 = SyntheticLMDataset(1000, 16, 4, seed=7)
    b0 = d1.next_batch()
    st = d1.state()
    b1 = d1.next_batch()
    d2 = SyntheticLMDataset(1000, 16, 4, seed=7)
    d2.restore(st)
    b1b = d2.next_batch()
    np.testing.assert_array_equal(b1["tokens"], b1b["tokens"])
    assert not np.array_equal(b0["tokens"], b1["tokens"])


def test_sharding_rules():
    from jax.sharding import PartitionSpec as P
    from repro.launch.sharding import param_spec
    mesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))

    class Leaf:
        def __init__(self, shape):
            self.shape = shape

    class K:
        def __init__(self, key):
            self.key = key

    # ffn up: (d, f) -> (data, model)
    spec = param_spec(mesh, [K("blocks"), K("0"), K("ffn"), K("up"), K("w")],
                      Leaf((26, 2304, 9216)))
    assert spec == P(None, "data", "model")
    # wo: (h*hd, d) -> (model, data)
    spec = param_spec(mesh, [K("blocks"), K("0"), K("attn"), K("wo"), K("w")],
                      Leaf((26, 2048, 2304)))
    assert spec == P(None, "model", "data")
    # non-divisible vocab falls back to d_model sharding
    spec = param_spec(mesh, [K("embed"), K("table")], Leaf((256206, 1024)))
    assert spec == P(None, "model")
    spec = param_spec(mesh, [K("embed"), K("table")], Leaf((256000, 2304)))
    assert spec == P("model", "data")
    # norms replicate (beyond the stacked dim)
    spec = param_spec(mesh, [K("blocks"), K("0"), K("ln1"), K("scale")],
                      Leaf((26, 2304)))
    assert spec == P(None, None)
