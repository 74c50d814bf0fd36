"""The benchmark's traffic: pinned arrays, sizes fixed by the mix, and the
copied generators equal to the program's own at the default seeds."""
import hashlib

import numpy as np
import pytest

import harness

# sha256 (first 16 hex digits) of ops, addrs, gaps and lengths per trace
PINNED = {
    ("splash7", 7): {
        "fft": "a12f71f6ad02e4b3", "lu_cont": "bf8ac880369e1f47",
        "lu_non": "fcc1085986c4b30b", "cholesky": "361241fe63ef84a1",
        "radiosity": "c160d62c2ad4ca07", "raytrace": "d9705c692177f2de",
        "volrend_npl": "80e1c690469b9d5d"},
    ("splash7", 2**31 + 9): {
        "fft": "20fa75ba7df5014d", "lu_cont": "567f1e1286f97c91",
        "lu_non": "ac5d55fa0b91f01d", "cholesky": "654f625c84bd328e",
        "radiosity": "9f7c5c9f163805ec", "raytrace": "0cb72a8257346ec7",
        "volrend_npl": "257155d9063f25f8"},
    ("fig1_probe", 7): {"fig1_probe": "9788c845804a32f9"},
    ("fig1_probe", 2**31 + 9): {"fig1_probe": "e9fb232b890a80b2"},
}
CONFIG_OF = {"splash7": "pcs16_1sw_nopb", "fig1_probe": "pcs16_chain4"}


def _build(traffic, seed):
    cfg = harness.load_json(harness.config_path(CONFIG_OF[traffic]))
    trf = harness.load_json(harness.traffic_path(traffic))
    return harness.build_traces(cfg, trf, seed)


def _digest(tr) -> str:
    h = hashlib.sha256()
    for a in (tr.ops, tr.addrs, tr.gaps, tr.lengths):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("traffic,seed", sorted(PINNED))
def test_traffic_arrays_pinned(traffic, seed):
    got = {t.name: _digest(t) for t in _build(traffic, seed)}
    assert got == PINNED[traffic, seed]


@pytest.mark.parametrize("traffic", sorted(CONFIG_OF))
def test_seed_changes_no_size(traffic):
    a, b = _build(traffic, 3), _build(traffic, 2**31 + 17)
    for x, y in zip(a, b):
        assert x.ops.shape == y.ops.shape
        np.testing.assert_array_equal(x.lengths, y.lengths)
        np.testing.assert_array_equal(x.ops, y.ops)
        for c in range(x.n_cores):
            n = int(x.lengths[c])
            # same mean gap (the probe's gaps are a permutation of one set)
            assert np.sum(x.gaps[c, :n], dtype=np.float64) == pytest.approx(
                np.sum(y.gaps[c, :n], dtype=np.float64), rel=1e-6)
    assert any(not np.array_equal(x.addrs, y.addrs)
               or not np.array_equal(x.gaps, y.gaps) for x, y in zip(a, b))


def test_copy_equals_program_generators():
    """At the default seeds and without the seed's entry, the copied
    generators give exactly what ``core/traces.py`` gives."""
    from repro.core import make_trace
    cfg = harness.load_json(harness.config_path("pcs16_1sw_nopb"))
    for tr in _build("splash7", None):
        want = make_trace(tr.name, persist_budget=cfg["persist_budget"])
        for k in ("ops", "addrs", "gaps", "lengths"):
            np.testing.assert_array_equal(getattr(tr, k), getattr(want, k))


def test_probe_equals_fig1_probe():
    from repro.core import Op
    (tr,) = _build("fig1_probe", None)
    n = 2000
    assert tr.ops.shape == (1, 2 * n)
    np.testing.assert_array_equal(
        tr.ops[0], np.tile([int(Op.PERSIST), int(Op.PM_READ)], n))
    np.testing.assert_array_equal(tr.addrs[0, ::2], np.arange(n))
    np.testing.assert_array_equal(tr.addrs[0, 1::2], (1 << 20) + np.arange(n))
    assert np.all(tr.gaps == np.float32(2000.0))
