"""Per-kernel allclose sweeps (shape x dtype) against the ref oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention, ssd_scan, tat_lookup
from repro.kernels import ref

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("r,n", [(256, 16), (512, 64), (1024, 256)])
@pytest.mark.parametrize("dtype", [jnp.int32])
def test_tat_lookup_sweep(r, n, dtype):
    req = jnp.asarray(RNG.integers(0, n * 2, r), dtype)
    tat = jnp.asarray(RNG.integers(0, n * 2, n), dtype)
    st = jnp.asarray(RNG.integers(0, 3, n), jnp.int32)
    i1, s1 = tat_lookup(req, tat, st)
    i2, s2 = ref.tat_lookup_ref(req, tat, st)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_array_equal(s1, s2)


@pytest.mark.parametrize("kernel", ["tat_lookup", "flash_attention",
                                    "ssd_scan"])
def test_ops_reject_shape_not_dividing_block(kernel):
    """The wrappers raise; they never fall back to the reference."""
    f32 = jnp.float32
    with pytest.raises(ValueError, match="not a multiple of the kernel"):
        if kernel == "tat_lookup":
            tat_lookup(jnp.zeros(300, jnp.int32), jnp.zeros(16, jnp.int32),
                       jnp.ones(16, jnp.int32))
        elif kernel == "flash_attention":
            q = jnp.zeros((1, 1, 192, 64), f32)
            flash_attention(q, q, q)
        else:
            ssd_scan(jnp.zeros((1, 192, 1, 16), f32), jnp.zeros((1, 192, 1), f32),
                     -jnp.ones(1, f32), jnp.zeros((1, 192, 16), f32),
                     jnp.zeros((1, 192, 16), f32))


def test_tat_lookup_empty_never_matches():
    req = jnp.asarray([7, 7], jnp.int32)
    tat = jnp.asarray([7, 7, 7, 7], jnp.int32)
    st = jnp.asarray([0, 0, 0, 0], jnp.int32)  # all Empty
    idx, s = ref.tat_lookup_ref(req, tat, st)
    assert (idx == -1).all() and (s == 0).all()


@pytest.mark.parametrize("b,h,s,d", [(2, 2, 256, 64), (1, 4, 128, 128),
                                     (1, 1, 512, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 64])
@pytest.mark.slow
def test_flash_attention_sweep(b, h, s, d, dtype, window):
    q = jnp.asarray(RNG.standard_normal((b, h, s, d)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, h, s, d)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, h, s, d)), dtype)
    o1 = flash_attention(q, k, v, causal=True, window=window,
                         block_q=128, block_k=128)
    o2 = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    assert float(jnp.max(jnp.abs(
        o1.astype(jnp.float32) - o2.astype(jnp.float32)))) < tol


def test_flash_attention_noncausal():
    q = jnp.asarray(RNG.standard_normal((1, 2, 256, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 2, 256, 64)), jnp.float32)
    o1 = flash_attention(q, k, v, causal=False)
    o2 = ref.flash_attention_ref(q, k, v, causal=False)
    assert float(jnp.max(jnp.abs(o1 - o2))) < 2e-5


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 256, 3, 64, 128, 128), (1, 128, 2, 32, 64, 64),
    (2, 512, 1, 64, 128, 128), (1, 256, 4, 64, 64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.slow
def test_ssd_scan_sweep(b, s, h, p, n, chunk, dtype):
    x = jnp.asarray(RNG.standard_normal((b, s, h, p)), dtype)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 1.5, (h,)), jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, s, n)), dtype)
    C = jnp.asarray(RNG.standard_normal((b, s, n)), dtype)
    y1, f1 = ssd_scan(x, dt, A, B, C, chunk=chunk)
    y2, f2 = ref.ssd_scan_ref(x, dt, A, B, C, chunk=chunk)
    tol = 1e-3 if dtype == jnp.float32 else 1e-1
    assert float(jnp.max(jnp.abs(
        y1.astype(jnp.float32) - y2.astype(jnp.float32)))) < tol
    assert float(jnp.max(jnp.abs(f1 - f2))) < tol


def test_ssd_kernel_matches_sequential():
    """Transitively: kernel == chunked ref == sequential recurrence."""
    from repro.models.ssm import ssd_decode_step
    b, s, h, p, n = 1, 128, 2, 16, 32
    x = jnp.asarray(RNG.standard_normal((b, s, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 1.5, (h,)), jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, s, n)), jnp.float32)
    C = jnp.asarray(RNG.standard_normal((b, s, n)), jnp.float32)
    state = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        y, state = ssd_decode_step(x[:, t], dt[:, t], A, B[:, t], C[:, t], state)
        ys.append(y)
    y_seq = jnp.stack(ys, axis=1)
    y_k, f_k = ssd_scan(x, dt, A, B, C, chunk=64)
    assert float(jnp.max(jnp.abs(y_k - y_seq))) < 1e-3
    assert float(jnp.max(jnp.abs(f_k - state))) < 1e-3
