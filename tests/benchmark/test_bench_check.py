"""The check reads each KV store at its own shape: the contract a latent
(MLA) program meets with a K store of the RoPE'd key part, (F, L, 1, P, 64),
and a V store of the RoPE-free latent, (F, L, 1, P, 512)."""

from types import SimpleNamespace

import numpy as np
import pytest

import bench_testlib  # noqa: F401  (puts the benchmark on the path)
from tppbench import check
from tppbench.traffic import Request

F, L, P = 12, 3, 4  # frames, layers, page size
ROPE, LATENT = 64, 512


class FakeKV:
    def __init__(self, rng):
        import jax.numpy as jnp

        self.k_store = jnp.asarray(rng.standard_normal((F, L, 1, P, ROPE), np.float32))
        self.v_store = jnp.asarray(rng.standard_normal((F, L, 1, P, LATENT), np.float32))
        self.flushed = 0

    def flush_migrations(self):
        self.flushed += 1

    def global_frames(self, pages):
        return np.asarray([2 * p + 1 for p in pages], np.int32)  # page p in frame 2p + 1


@pytest.fixture(scope="module")
def snapped():
    rng = np.random.default_rng(7)
    kv = FakeKV(rng)
    req = Request(index=0, prompt=list(range(7)), max_new=8, rid=5, tokens=3)
    seq = SimpleNamespace(pages=[4, 1, 3], cur_len=10)  # 10 positions over 3 pages
    eng = SimpleNamespace(kv=kv, seqs={5: seq}, requests={5: SimpleNamespace(out=[9, 8, 7])})
    (snap,) = check.snapshot(SimpleNamespace(engine=eng), [req], seed=1, k=1)
    return kv, seq, snap


def _held(store, seq):
    # (L, positions, 1, width): pages in the sequence's order, cut at cur_len
    frames = [2 * p + 1 for p in seq.pages]
    pages = np.asarray(store)[frames]  # (n, L, 1, P, D)
    return np.concatenate([pages[i].transpose(0, 2, 1, 3) for i in range(len(frames))],
                          axis=1)[:, : seq.cur_len]


def test_snapshot_keeps_each_store_at_its_width(snapped):
    kv, seq, snap = snapped
    assert kv.flushed == 1  # pending migrations settle before the read
    assert snap.k.shape == (L, 10, 1, ROPE) and snap.v.shape == (L, 10, 1, LATENT)
    np.testing.assert_array_equal(snap.k, _held(kv.k_store, seq))
    np.testing.assert_array_equal(snap.v, _held(kv.v_store, seq))
    assert snap.out == [9, 8, 7]


def test_v0_rel_err_is_taken_from_the_second_store(snapped):
    _, _, snap = snapped
    # the reference's cache, one lane: K off by half everywhere, V off in
    # layer 0 by a known share and exact above it
    k_ref = snap.k * 2.0
    v_ref = snap.v.copy()
    v_ref[0] = snap.v[0] / (1 + 1e-3)
    v0, kv = check.kv_errors([snap], (k_ref[None], v_ref[None]), [0])
    assert v0 == pytest.approx(1e-3, rel=1e-3)  # f32 differences of 1e-3
    assert kv == pytest.approx(0.5, rel=1e-6)  # K's error, at prompt positions
