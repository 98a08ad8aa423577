"""Unit tests for compression kernels: top-k, clipping, CountSketch.

Property tests follow SURVEY.md §4's implications: sketch linearity,
heavy-hitter recovery, lossless-limit equivalence with exact top-k.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.ops import (
    clip_by_l2_norm,
    make_sketch,
    sketch_decode,
    sketch_encode,
    sketch_l2estimate,
    sketch_unsketch,
    topk,
)


class TestTopk:
    def test_matches_numpy(self):
        rng = np.random.RandomState(0)
        vec = rng.randn(1000).astype(np.float32)
        k = 17
        out = np.asarray(topk(jnp.asarray(vec), k))
        # nonzero exactly at the k largest |v|
        order = np.argsort(vec**2)[::-1][:k]
        expected = np.zeros_like(vec)
        expected[order] = vec[order]
        np.testing.assert_allclose(out, expected)

    def test_2d_rowwise(self):
        rng = np.random.RandomState(1)
        mat = rng.randn(4, 100).astype(np.float32)
        out = np.asarray(topk(jnp.asarray(mat), 5))
        for i in range(4):
            assert (out[i] != 0).sum() == 5
            kept = np.abs(mat[i])[out[i] != 0].min()
            dropped = np.abs(mat[i])[out[i] == 0].max()
            assert kept >= dropped

    def test_jit(self):
        vec = jnp.arange(10.0) - 5.0
        out = jax.jit(lambda v: topk(v, 3))(vec)
        assert int((out != 0).sum()) == 3

    def test_approx_path(self):
        """Pin the --approx_topk plumbing (jit, vmap, 1-D and row-wise 2-D).
        approx_max_k has 0.95 default recall, so compare support overlap
        rather than exact equality."""
        rng = np.random.RandomState(10)
        vec = jnp.asarray(rng.randn(4096).astype(np.float32))
        k = 64
        out = jax.jit(lambda v: topk(v, k, approx=True))(vec)
        assert int((np.asarray(out) != 0).sum()) == k
        exact_support = set(np.nonzero(np.asarray(topk(vec, k)))[0])
        approx_support = set(np.nonzero(np.asarray(out))[0])
        assert len(exact_support & approx_support) >= int(0.9 * k)
        # values at recovered coords are the originals
        idx = sorted(approx_support)
        np.testing.assert_allclose(np.asarray(out)[idx],
                                   np.asarray(vec)[idx])
        mats = jnp.asarray(rng.randn(3, 2048).astype(np.float32))
        out2 = jax.jit(jax.vmap(lambda v: topk(v, 16, approx=True)))(mats)
        assert out2.shape == mats.shape
        assert all(int((r != 0).sum()) == 16 for r in np.asarray(out2))


class TestClip:
    def test_noop_below_threshold(self):
        v = jnp.array([0.3, 0.4])  # norm 0.5
        np.testing.assert_allclose(np.asarray(clip_by_l2_norm(v, 1.0)), [0.3, 0.4])

    def test_scales_above_threshold(self):
        v = jnp.array([3.0, 4.0])  # norm 5
        out = np.asarray(clip_by_l2_norm(v, 1.0))
        np.testing.assert_allclose(np.linalg.norm(out), 1.0, rtol=1e-6)
        np.testing.assert_allclose(out, [0.6, 0.8], rtol=1e-6)

    def test_sketch_table_uses_l2estimate(self):
        """Clipping a sketch table must clip by the median row norm
        (csvec l2estimate semantics, reference utils.py:305-313), not the
        Frobenius norm — the clipped table's estimate equals the threshold."""
        rng = np.random.RandomState(9)
        cs2 = make_sketch(d=D, c=C, r=R, num_blocks=1, seed=13)
        v = jnp.asarray((rng.randn(D) * 3).astype(np.float32))
        table = sketch_encode(cs2, v)
        est_before = float(sketch_l2estimate(cs2, table))
        clip = est_before / 2
        clipped = clip_by_l2_norm(table, clip)
        np.testing.assert_allclose(float(sketch_l2estimate(cs2, clipped)),
                                   clip, rtol=1e-5)


D, C, R = 5000, 2000, 5


@pytest.fixture(scope="module")
def cs():
    return make_sketch(d=D, c=C, r=R, num_blocks=4, seed=7)


class TestSketch:
    def test_linearity(self, cs):
        rng = np.random.RandomState(2)
        a = jnp.asarray(rng.randn(D).astype(np.float32))
        b = jnp.asarray(rng.randn(D).astype(np.float32))
        t = sketch_encode(cs, a) + sketch_encode(cs, b)
        t_sum = sketch_encode(cs, a + b)
        np.testing.assert_allclose(np.asarray(t), np.asarray(t_sum), atol=1e-4)

    def test_block_invariance(self):
        """Table must not depend on num_blocks (it is a memory knob only)."""
        rng = np.random.RandomState(3)
        v = jnp.asarray(rng.randn(D).astype(np.float32))
        t1 = sketch_encode(make_sketch(D, C, R, num_blocks=1, seed=7), v)
        t4 = sketch_encode(make_sketch(D, C, R, num_blocks=4, seed=7), v)
        t7 = sketch_encode(make_sketch(D, C, R, num_blocks=7, seed=7), v)
        np.testing.assert_allclose(np.asarray(t1), np.asarray(t4), atol=1e-4)
        np.testing.assert_allclose(np.asarray(t1), np.asarray(t7), atol=1e-4)

    def test_heavy_hitter_recovery(self, cs):
        """A vector with k big spikes + small noise: unsketch finds the spikes."""
        rng = np.random.RandomState(4)
        k = 10
        v = rng.randn(D).astype(np.float32) * 0.01
        spikes = rng.choice(D, k, replace=False)
        v[spikes] = np.sign(rng.randn(k)) * (10.0 + rng.rand(k))
        table = sketch_encode(cs, jnp.asarray(v))
        rec = np.asarray(sketch_unsketch(cs, table, k))
        assert set(np.nonzero(rec)[0]) == set(spikes)
        np.testing.assert_allclose(rec[spikes], v[spikes], rtol=0.05, atol=0.1)

    def test_lossless_limit_matches_topk(self):
        """With a huge table (c >> d), estimates ≈ exact values, so
        unsketch(k) must equal exact topk(k) (SURVEY.md §4 golden strategy)."""
        d = 200
        cs_big = make_sketch(d=d, c=50_000, r=7, num_blocks=1, seed=11)
        rng = np.random.RandomState(5)
        v = jnp.asarray(rng.randn(d).astype(np.float32))
        table = sketch_encode(cs_big, v)
        est = np.asarray(sketch_decode(cs_big, table))
        np.testing.assert_allclose(est, np.asarray(v), atol=1e-3)
        rec = np.asarray(sketch_unsketch(cs_big, table, 20))
        exact = np.asarray(topk(v, 20))
        np.testing.assert_allclose(rec, exact, atol=1e-3)

    def test_l2_estimate(self, cs):
        rng = np.random.RandomState(6)
        v = jnp.asarray(rng.randn(D).astype(np.float32))
        table = sketch_encode(cs, v)
        est = float(sketch_l2estimate(cs, table))
        true = float(jnp.linalg.norm(v))
        assert abs(est - true) / true < 0.15

    def test_decode_at_matches_decode(self, cs):
        """decode_at(table, idx) == decode(table)[idx] — the contract the
        subtractive error-feedback momentum masking relies on
        (core/server.py)."""
        rng = np.random.RandomState(9)
        v = jnp.asarray(rng.randn(D).astype(np.float32))
        table = sketch_encode(cs, v)
        idx = jnp.asarray(rng.choice(D, 40, replace=False))
        np.testing.assert_allclose(
            np.asarray(cs.decode_at(table, idx)),
            np.asarray(cs.decode(table))[np.asarray(idx)], atol=1e-5)

    def test_encode_jit_and_vmap(self, cs):
        rng = np.random.RandomState(8)
        vs = jnp.asarray(rng.randn(3, D).astype(np.float32))
        tables = jax.jit(jax.vmap(lambda v: sketch_encode(cs, v)))(vs)
        assert tables.shape == (3, R, C)
        # vmapped encode must agree with single encode
        single = sketch_encode(cs, vs[1])
        np.testing.assert_allclose(np.asarray(tables[1]), np.asarray(single),
                                   atol=1e-4)

    def test_sign_balance(self, cs):
        """Hash quality smoke check: bucket histogram ~uniform, signs ~balanced."""
        from commefficient_tpu.ops.sketch import _buckets_signs
        idx = jnp.arange(D, dtype=jnp.uint32)
        buckets, signs = _buckets_signs(cs, idx)
        assert float(jnp.abs(signs.mean())) < 0.05
        counts = np.bincount(np.asarray(buckets[0]), minlength=C)
        # expected D/C per bucket = 2.5; max shouldn't explode
        assert counts.max() < 15


@pytest.fixture(scope="module")
def ccs():
    from commefficient_tpu.ops.circulant import make_circulant_sketch
    return make_circulant_sketch(d=D, c=C, r=R, num_blocks=2, seed=7)


class TestCirculantSketch:
    """Circulant count sketch (ops/circulant.py): same property surface as
    the hash impl — it must be a drop-in (r, c) linear sketch with
    count-sketch estimator guarantees — plus the static-roll layout rules."""

    def test_linearity(self, ccs):
        rng = np.random.RandomState(2)
        a = jnp.asarray(rng.randn(D).astype(np.float32))
        b = jnp.asarray(rng.randn(D).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(ccs.encode(a) + ccs.encode(b)),
            np.asarray(ccs.encode(a + b)), atol=1e-4)

    def test_block_invariance(self):
        """num_blocks is a decode-memory knob only — table and decode must
        not depend on it."""
        from commefficient_tpu.ops.circulant import make_circulant_sketch
        rng = np.random.RandomState(3)
        v = jnp.asarray(rng.randn(D).astype(np.float32))
        t1 = make_circulant_sketch(D, C, R, num_blocks=1, seed=7)
        t3 = make_circulant_sketch(D, C, R, num_blocks=3, seed=7)
        np.testing.assert_allclose(np.asarray(t1.encode(v)),
                                   np.asarray(t3.encode(v)), atol=1e-4)
        np.testing.assert_allclose(np.asarray(t1.decode(t1.encode(v))),
                                   np.asarray(t3.decode(t3.encode(v))),
                                   atol=1e-4)

    def test_heavy_hitter_recovery(self, ccs):
        rng = np.random.RandomState(4)
        k = 10
        v = rng.randn(D).astype(np.float32) * 0.01
        spikes = rng.choice(D, k, replace=False)
        v[spikes] = np.sign(rng.randn(k)) * (10.0 + rng.rand(k))
        rec = np.asarray(ccs.unsketch(ccs.encode(jnp.asarray(v)), k))
        assert set(np.nonzero(rec)[0]) == set(spikes)
        np.testing.assert_allclose(rec[spikes], v[spikes], rtol=0.05,
                                   atol=0.1)

    def test_lossless_limit_exact(self):
        """c >= d => single block, rolls are invertible: decode is EXACT."""
        from commefficient_tpu.ops.circulant import make_circulant_sketch
        d = 200
        cs_big = make_circulant_sketch(d=d, c=256, r=3, seed=11)
        rng = np.random.RandomState(5)
        v = jnp.asarray(rng.randn(d).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(cs_big.decode(cs_big.encode(v))), np.asarray(v),
            atol=1e-5)

    def test_encode_at_matches_dense(self, ccs):
        """encode_at on a k-sparse vector == full encode (the server's
        error-feedback re-encode contract, reference
        fed_aggregator.py:593-595)."""
        rng = np.random.RandomState(6)
        idx = jnp.asarray(rng.choice(D, 50, replace=False))
        v = jnp.zeros((D,), jnp.float32).at[idx].set(
            jnp.asarray(rng.randn(50), jnp.float32))
        np.testing.assert_allclose(np.asarray(ccs.encode_at(v, idx)),
                                   np.asarray(ccs.encode(v)), atol=1e-4)

    def test_decode_at_matches_decode(self, ccs):
        """decode_at(table, idx) == decode(table)[idx] for the circulant
        impl (subtractive-EF momentum masking contract, core/server.py)."""
        rng = np.random.RandomState(10)
        v = jnp.asarray(rng.randn(D).astype(np.float32))
        table = ccs.encode(v)
        idx = jnp.asarray(rng.choice(D, 40, replace=False))
        np.testing.assert_allclose(
            np.asarray(ccs.decode_at(table, idx)),
            np.asarray(ccs.decode(table))[np.asarray(idx)], atol=1e-5)

    def test_l2_estimate(self, ccs):
        rng = np.random.RandomState(6)
        v = jnp.asarray(rng.randn(D).astype(np.float32))
        est = float(ccs.l2estimate(ccs.encode(v)))
        true = float(jnp.linalg.norm(v))
        assert abs(est - true) / true < 0.15

    def test_jit_with_sketch_argument(self, ccs):
        """The runtime threads the sketch as a jit ARGUMENT; the static
        shifts live in pytree aux data, so this must trace cleanly."""
        rng = np.random.RandomState(8)
        v = jnp.asarray(rng.randn(D).astype(np.float32))
        t = jax.jit(lambda cs, x: cs.encode(x))(ccs, v)
        np.testing.assert_allclose(np.asarray(t), np.asarray(ccs.encode(v)),
                                   atol=1e-4)

    def test_gather_fallback_matches_unrolled(self, monkeypatch):
        """Extreme d/c ratios (m > _UNROLL_MAX_BLOCKS) switch encode/decode
        to one (m, c) gather per row; results must be identical to the
        static-roll path."""
        from commefficient_tpu.ops import circulant as circ
        cs = circ.make_circulant_sketch(d=119, c=2, r=3, seed=3)  # m=60
        rng = np.random.RandomState(1)
        v = jnp.asarray(rng.randn(119).astype(np.float32))
        t_roll = cs.encode(v)
        dec_roll = cs.decode(t_roll)
        monkeypatch.setattr(circ.CirculantSketch, "_UNROLL_MAX_BLOCKS", 8)
        t_gather = cs.encode(v)
        np.testing.assert_allclose(np.asarray(t_roll),
                                   np.asarray(t_gather), atol=1e-5)
        np.testing.assert_allclose(np.asarray(dec_roll),
                                   np.asarray(cs.decode(t_gather)),
                                   atol=1e-5)

    @pytest.mark.parametrize("path,warns", [("xla", True),
                                            ("pallas", False)])
    def test_block_count_warning_is_the_xla_paths(self, monkeypatch, path,
                                                  warns):
        """Past _UNROLL_MAX_BLOCKS the XLA path falls to a gather and says
        so; the Pallas kernels take m as a grid length (m = 744 in the
        d = 3.9e8 cell) and the warning would be false there."""
        import warnings
        from commefficient_tpu.ops import circulant as circ
        monkeypatch.setattr(circ.CirculantSketch, "_UNROLL_MAX_BLOCKS", 8)
        monkeypatch.setattr(circ.CirculantSketch, "kernel_path",
                            property(lambda self: path))
        with warnings.catch_warnings(record=True) as seen:
            warnings.simplefilter("always")
            circ.make_circulant_sketch(d=119, c=2, r=3, seed=3)   # m = 60
        said = [w for w in seen if "_UNROLL_MAX_BLOCKS" in str(w.message)]
        assert bool(said) is warns, [str(w.message) for w in seen]

    def test_aligned_shift_granularity(self):
        """c % 1024 == 0 => shifts are multiples of 1024 (the pallas
        no-rotate enabler); unaligned c keeps full-range shifts."""
        from commefficient_tpu.ops import circulant as circ
        cs = circ.make_circulant_sketch(d=9000, c=2048, r=3, seed=5)
        assert all(s % 1024 == 0 for row in cs.shifts for s in row)
        assert any(s != 0 for row in cs.shifts for s in row)
        cs2 = circ.make_circulant_sketch(d=9000, c=500, r=3, seed=5)
        assert any(s % 1024 for row in cs2.shifts for s in row)

    def test_pallas_kernels_match_roll_path(self, monkeypatch):
        """The fused pallas kernels (ops/circulant_pallas.py v4,
        sublane-slice span extraction) must reproduce the roll path
        exactly — validated here in interpret mode (CPU); the TPU decode
        path is on by default when eligible."""
        from commefficient_tpu.ops import circulant as circ
        from commefficient_tpu.ops.circulant_pallas import (pallas_decode,
                                                            pallas_encode)
        cs = circ.make_circulant_sketch(d=9000, c=2048, r=5, num_blocks=3,
                                        seed=7)
        rng = np.random.RandomState(0)
        v = jnp.asarray(rng.randn(9000).astype(np.float32))
        t_roll = cs.encode(v)
        vp = jnp.pad(v, (0, cs.m * cs.c - cs.d))
        shifts = jnp.asarray(cs.shifts, jnp.int32)
        t_pl = pallas_encode(vp, shifts, cs.sign_keys, c=cs.c, r=cs.r,
                             m=cs.m, interpret=True)
        np.testing.assert_allclose(np.asarray(t_pl), np.asarray(t_roll),
                                   atol=1e-4)
        d_pl = pallas_decode(t_roll, shifts, cs.sign_keys, c=cs.c, r=cs.r,
                             m=cs.m, interpret=True)[: cs.d]
        np.testing.assert_allclose(np.asarray(d_pl),
                                   np.asarray(cs.decode(t_roll)), atol=1e-5)

    def test_pallas_multi_lane_tile_matches_roll_path(self, monkeypatch):
        """At real scale (c=524288 > _CT_MAX) the kernels tile the lane
        dimension; spans then cross lane-tile (and mod-c wrap) boundaries
        through the wrap padding. Exercise that path by shrinking _CT_MAX
        so c=2048 splits into 2 tiles of 1024."""
        from commefficient_tpu.ops import circulant as circ
        from commefficient_tpu.ops import circulant_pallas as cp
        monkeypatch.setattr(cp, "_CT_MAX", 1024)
        assert cp._lane_tile(2048) == 1024
        # d chosen so m differs from the test above: pallas_encode is
        # jit-cached on (c, r, m, interpret), and a shape collision would
        # silently reuse the un-monkeypatched single-tile trace
        cs = circ.make_circulant_sketch(d=11000, c=2048, r=5, num_blocks=3,
                                        seed=11)
        rng = np.random.RandomState(2)
        v = jnp.asarray(rng.randn(11000).astype(np.float32))
        t_roll = cs.encode(v)
        vp = jnp.pad(v, (0, cs.m * cs.c - cs.d))
        shifts = jnp.asarray(cs.shifts, jnp.int32)
        t_pl = cp.pallas_encode(vp, shifts, cs.sign_keys, c=cs.c, r=cs.r,
                                m=cs.m, interpret=True)
        np.testing.assert_allclose(np.asarray(t_pl), np.asarray(t_roll),
                                   atol=1e-4)
        d_pl = cp.pallas_decode(t_roll, shifts, cs.sign_keys, c=cs.c,
                                r=cs.r, m=cs.m, interpret=True)[: cs.d]
        np.testing.assert_allclose(np.asarray(d_pl),
                                   np.asarray(cs.decode(t_roll)), atol=1e-5)

    # (start, length) at c = 3072 in three lane tiles of 1024, d = 16,000,
    # m = 6, so m*c = 18,432; only this test decodes at c = 3072, so the
    # jit cache (keyed on c, r, nb, interpret) holds no other tile for it
    @pytest.mark.parametrize("start,length", [
        (3072, 6144),      # block-aligned: the cover's third block is slack
        (4000, 5000),      # starts and ends in mid-block
        (0, 16000),        # the whole vector: blocks 0..5 and one past
        (13000, 5600),     # crosses d, then m*c: the cover runs to block 7
        (17000, 40),       # every coordinate >= d
    ], ids=["block_aligned", "mid_block", "whole_d", "cover_past_block_m",
            "all_past_d"])
    def test_pallas_range_decode_bit_exact(self, monkeypatch, start, length):
        """``decode_range`` on the Pallas entry (whole covering blocks
        through the decode kernel, first block a traced scalar, one
        slice; interpret mode here) against the gather form and the
        static-roll ``decode``: the same bits on every coordinate < d,
        exactly 0 from d on — also where the cover reads past block
        m - 1 of the shift table."""
        import functools
        from commefficient_tpu.ops import circulant as circ
        from commefficient_tpu.ops import circulant_pallas as cp
        monkeypatch.setattr(cp, "_CT_MAX", 1024)
        cs = circ.make_circulant_sketch(d=16000, c=3072, r=5, seed=29)
        assert cs.m == 6 and cp._lane_tile(cs.c) == 1024
        rng = np.random.RandomState(29)
        table = cs.encode(jnp.asarray(rng.randn(cs.d).astype(np.float32)))
        want = np.zeros(start + length, np.float32)
        want[:cs.d] = np.asarray(cs.decode(table))[:start + length]
        want = want[start:]
        gather = np.asarray(jax.jit(
            lambda t, s: cs.decode_range(t, s, length))(table,
                                                        jnp.int32(start)))
        np.testing.assert_array_equal(gather, want)
        monkeypatch.setattr(circ.CirculantSketch, "_use_pallas_decode",
                            lambda self: True)
        monkeypatch.setattr(cp, "pallas_decode_range", functools.partial(
            cp.pallas_decode_range, interpret=True))
        f = jax.jit(lambda t, s: cs.decode_range(t, s, length))
        text = f.lower(table, jnp.int32(start)).as_text()
        assert "gather" not in text and "dynamic_slice" in text
        kernel = np.asarray(f(table, jnp.int32(start)))
        np.testing.assert_array_equal(kernel, want)
        assert cp.range_cover_blocks(cs.c, length) == max(
            (o + length - 1) // cs.c + 1 for o in range(cs.c))

    @pytest.mark.parametrize("c,d,cap", [
        (2048, 9500, None),      # one lane tile, m = 5
        (4096, 30000, 1024),     # four lane tiles, m = 8
    ], ids=["one_tile", "four_tiles"])
    def test_pallas_whole_decode_is_the_block_entry_at_zero(
            self, monkeypatch, c, d, cap):
        """``pallas_decode`` is ``pallas_decode_blocks`` at first block 0
        over all m blocks — one kernel — and equals the static rolls bit
        for bit; a traced first block > 0 gives that stretch of it."""
        from commefficient_tpu.ops import circulant as circ
        from commefficient_tpu.ops import circulant_pallas as cp
        if cap is not None:
            monkeypatch.setattr(cp, "_CT_MAX", cap)
        cs = circ.make_circulant_sketch(d=d, c=c, r=5, seed=c + d)
        rng = np.random.RandomState(d)
        table = cs.encode(jnp.asarray(rng.randn(d).astype(np.float32)))
        shifts = jnp.asarray(cs.shifts, jnp.int32)
        whole = np.asarray(cp.pallas_decode(
            table, shifts, cs.sign_keys, c=c, r=cs.r, m=cs.m,
            interpret=True))
        blocks = np.asarray(cp.pallas_decode_blocks(
            table, shifts, cs.sign_keys, 0, c=c, r=cs.r, nb=cs.m,
            interpret=True))
        np.testing.assert_array_equal(whole, blocks)
        np.testing.assert_array_equal(whole[:d], np.asarray(cs.decode(table)))
        two = jax.jit(lambda t, b: cp.pallas_decode_blocks(
            t, shifts, cs.sign_keys, b, c=c, r=cs.r, nb=2, interpret=True))
        for b in (1, cs.m - 2):
            np.testing.assert_array_equal(
                np.asarray(two(table, jnp.int32(b))),
                whole[b * c:(b + 2) * c])

    @staticmethod
    def _encode_in_block_order(cs, v):
        """Plain reference of the encode kernel's arithmetic: every table
        cell is 0 + term(b=0) + term(b=1) + ..., float32 adds in block
        order, term(b) the signed block rolled by its shift."""
        m, c = cs.m, cs.c
        vp = np.zeros(m * c, np.float32)
        vp[:cs.d] = v
        table = np.zeros((cs.r, c), np.float32)
        for j in range(cs.r):
            signs = np.asarray(cs._signs(j))
            for b in range(m):
                table[j] += np.roll(signs[b] * vp[b * c:(b + 1) * c],
                                    cs.shifts[j][b])
        return table

    # (c, d, cap on the encode's tile, tile it must pick, seam shifts,
    # scale, cap on the fill's chunk); every case has its own m:
    # pallas_encode is jit-cached on (c, r, m, interpret) and a collision
    # would reuse another case's tile. No d is a multiple of c or of 128:
    # the last block is ragged in every case
    @pytest.mark.parametrize("c,d,cap,tile,seam,scale,fill", [
        (2048, 7000, None, 2048, False, 1.0, None),     # one tile
        (2048, 13000, 1024, 1024, False, 1.0, None),    # two tiles
        # c = 500,736 = 3 * 163 * 1024 in miniature: a non-power-of-two
        # c whose tile is 3,072; spans cross tile edges and the mod-c seam
        (9216, 40000, None, 3072, False, 1.0, None),
        # shifts 0 and c - 1024 in every row: the span that crosses the
        # seam lies in the first tile and in the last
        (9216, 30000, None, 3072, True, 1.0, None),
        (2048, 15001, None, 2048, False, 0.37, None),
        (2048, 17003, 1024, 1024, False, 1.0 / 3.0, None),
        (9216, 50003, None, 3072, True, 3.3, None),
        # c/128 = 72 rows filled as 2 chunks of 32 and 8 left over, as
        # c = 500,736 fills 3,912 rows in 15 chunks of 256 and 72
        (9216, 60001, None, 3072, False, 0.37, 32),
        (9216, 70001, None, 3072, True, 1.0, 32),
    ], ids=["one_tile", "two_tiles", "c9216_tile3072", "shift_0_and_c-1024",
            "scale_0.37", "two_tiles_scale_third", "seam_scale_3.3",
            "fill_chunks_and_rest_scale_0.37", "fill_chunks_and_rest_seam"])
    def test_pallas_encode_one_pass_bit_exact(self, monkeypatch, c, d, cap,
                                              tile, seam, scale, fill):
        """The one-pass encode (table resident, lane tiles looped inside
        the kernel; the block scaled and wrap-padded in VMEM by the
        kernel, the scale a prefetched scalar) against the roll path's
        ``encode(scale * v)``, and bit for bit against the block-order
        reference of ``scale * v``: the table the parent's kernel made
        of the scaled, padded, wrap-padded copy XLA handed it, the same
        float32 additions in the same order."""
        import dataclasses
        from commefficient_tpu.ops import circulant as circ
        from commefficient_tpu.ops import circulant_pallas as cp
        if cap is not None:
            monkeypatch.setattr(cp, "_ENCODE_CT_MAX", cap)
        if fill is not None:
            monkeypatch.setattr(cp, "_FILL_ROWS", fill)
            assert (c // 128) // fill >= 2 and (c // 128) % fill
        assert cp._encode_tile(c) == tile
        assert d % c and d % 128
        cs = circ.make_circulant_sketch(d=d, c=c, r=5, seed=c + d)
        if seam:
            cs = dataclasses.replace(cs, shifts=tuple(
                (0, c - 1024) + row[2:] for row in cs.shifts))
        # some span wraps mod c and, where a tile is more than one shift
        # step, some span starts inside a tile
        assert any(s for row in cs.shifts for s in row)
        assert tile == cp.SHIFT_ALIGN or any(
            s % tile for row in cs.shifts for s in row)
        rng = np.random.RandomState(d)
        v = rng.randn(d).astype(np.float32)
        vp = jnp.pad(jnp.asarray(v), (0, cs.m * c - d))
        t_pl = np.asarray(cp.pallas_encode(
            vp, jnp.asarray(cs.shifts, jnp.int32), cs.sign_keys,
            jnp.float32(scale), c=c, r=cs.r, m=cs.m, interpret=True))
        scaled = np.float32(scale) * v
        np.testing.assert_allclose(t_pl, np.asarray(cs.encode(scaled)),
                                   atol=1e-4)
        np.testing.assert_array_equal(
            t_pl, self._encode_in_block_order(cs, scaled))

    def test_pallas_encode_batches_vector_and_scale(self):
        """The per-client path runs ``cs.encode`` under ``jax.vmap``: the
        kernel's scratch and its scalar batch, with one scale for all
        rows of the batch or one a row."""
        from commefficient_tpu.ops import circulant as circ
        from commefficient_tpu.ops import circulant_pallas as cp
        cs = circ.make_circulant_sketch(d=21001, c=2048, r=3, seed=31)
        shifts = jnp.asarray(cs.shifts, jnp.int32)
        rng = np.random.RandomState(31)
        vs = np.zeros((3, cs.m * cs.c), np.float32)
        vs[:, :cs.d] = rng.randn(3, cs.d)
        scales = np.asarray([1.0, 2.5, 0.3], np.float32)

        def enc(v, s):
            return cp.pallas_encode(v, shifts, cs.sign_keys, s, c=cs.c,
                                    r=cs.r, m=cs.m, interpret=True)

        one = np.asarray(jax.vmap(lambda v: enc(v, 2.5))(jnp.asarray(vs)))
        each = np.asarray(jax.vmap(enc)(jnp.asarray(vs), jnp.asarray(scales)))
        for i in range(3):
            np.testing.assert_array_equal(one[i], self._encode_in_block_order(
                cs, np.float32(2.5) * vs[i, :cs.d]))
            np.testing.assert_array_equal(each[i], self._encode_in_block_order(
                cs, scales[i] * vs[i, :cs.d]))

    @pytest.mark.parametrize("c,r,d", [
        (500736, 5, 25504026),     # rn50_sketch_8x64
        (524288, 5, 124444416),    # gpt2_sketch_8x8x2x256
    ])
    def test_encode_hbm_bytes_is_one_pass(self, c, r, d):
        """The encode's own BlockSpecs move the input once, as it lies,
        and the table once; the zeros that close the last block are all
        that separates that from what the algorithm needs. The (lane
        tile, block) grid of v4 fetched every block once per lane tile:
        150x and 8.8x."""
        from commefficient_tpu.ops import circulant_pallas as cp
        from perfbench.harness.arith import sketch_encode_bytes
        m = -(-d // c)
        moved = cp.encode_hbm_bytes(c, r, m)
        assert moved == 4 * (m * c + r * c)
        need = sketch_encode_bytes(d, r, c)
        assert 1.0 <= moved / need < 1.005
        pt = cp._lane_tile(c)
        per_tile_grid = 4 * ((c // pt) * m * (c + pt) + r * c)
        assert per_tile_grid / need > 8
