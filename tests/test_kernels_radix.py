"""Unit tests for the radix-factored group accumulation kernels.

The hi/lo one-hot factorization (ops/kernels.py _radix_onehots) must be
bit-exact with the direct one-hot matmul on both sides of the RADIX_G
threshold — these are the primitives every group-by result flows
through (parity: DefaultGroupByExecutor's per-function aggregation,
with exactness guarantees the reference gets from Java longs).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from pinot_tpu.ops import kernels


def _naive_hist(ids, mask, g):
    out = np.zeros(g, dtype=np.int64)
    np.add.at(out, ids[mask], 1)
    return out


@pytest.mark.parametrize("g_pad", [32, 128, 256, 1024, 8192])
def test_mxu_histogram_matches_naive(g_pad):
    """All three regimes: <=128 fused compare+reduce (the adaptive hist
    scout's path), direct bf16 matmul, hi/lo-factored radix."""
    rng = np.random.default_rng(1)
    n = kernels.BLOCK * 2
    ids = rng.integers(0, g_pad, n).astype(np.int32)
    mask = rng.random(n) < 0.3
    out = np.asarray(kernels._mxu_histogram(
        jnp.asarray(ids), jnp.asarray(mask), g_pad))
    np.testing.assert_array_equal(out, _naive_hist(ids, mask, g_pad))


@pytest.mark.parametrize("g_pad,n_parts", [(256, 4), (1024, 4), (4096, 2),
                                           (8192, 5), (32768, 4)])
def test_dense_group_part_sums_exact(g_pad, n_parts):
    """Covers the direct batched path (g < 512), the batched radix
    concat (n_l*g1 <= 128), and the wide-table scan fallback
    (g_pad=8192 with 6 lanes → n_l*g1 = 384; g_pad=32768 → 1280)."""
    rng = np.random.default_rng(2)
    n = kernels.BLOCK * 2
    key = rng.integers(0, g_pad, n).astype(np.int32)
    mask = rng.random(n) < 0.5
    parts = rng.integers(0, 128, (n_parts, n)).astype(np.int8)  # max 127
    out, _vsums, count = kernels._dense_group_sums(
        [jnp.asarray(parts[p]) for p in range(n_parts)], (),
        jnp.asarray(key), jnp.asarray(mask), g_pad, with_count=True)
    exp = np.zeros((n_parts, g_pad), dtype=np.int64)
    for p in range(n_parts):
        np.add.at(exp[p], key[mask], parts[p][mask].astype(np.int64))
    np.testing.assert_array_equal(np.asarray(out), exp)
    np.testing.assert_array_equal(np.asarray(count),
                                  _naive_hist(key, mask, g_pad))


@pytest.mark.parametrize("g_pad", [256, 2048])
def test_dense_group_float_sums(g_pad):
    rng = np.random.default_rng(3)
    n = 4096 * 2
    key = rng.integers(0, g_pad, n).astype(np.int32)
    mask = rng.random(n) < 0.5
    vals = rng.random(n).astype(np.float64) * 100
    _psums, out, count = kernels._dense_group_sums(
        (), [jnp.asarray(vals)], jnp.asarray(key), jnp.asarray(mask), g_pad)
    exp = np.zeros(g_pad)
    np.add.at(exp, key[mask], vals[mask])
    np.testing.assert_allclose(np.asarray(out[0]), exp, rtol=1e-9)
    assert count is None


def _one_pass_case(g_pad, lanes, seed=6):
    """7 blocks (an odd count, as a benchmark segment's 763), a mask
    that keeps 4%: (operands, float64 / int64 expectations)."""
    rng = np.random.default_rng(seed)
    n = kernels.BLOCK * 7
    n_parts, with_count = {"float": (0, False), "float+count": (0, True),
                           "parts+float+count": (2, True)}[lanes]
    key = rng.integers(0, g_pad, n).astype(np.int32)
    mask = rng.random(n) < 0.04
    # revenues: integers below 2^24, exact in float32
    vals = rng.integers(90_000, 10_500_000, n).astype(np.float32)
    parts = rng.integers(0, 128, (n_parts, n)).astype(np.int8)
    exp_v = np.zeros(g_pad)
    np.add.at(exp_v, key[mask], vals[mask].astype(np.float64))
    exp_p = np.zeros((n_parts, g_pad), dtype=np.int64)
    for p in range(n_parts):
        np.add.at(exp_p[p], key[mask], parts[p][mask].astype(np.int64))
    return ((parts, vals, key, mask, with_count),
            (exp_p, exp_v, _naive_hist(key, mask, g_pad)))


def _run_one_pass(g_pad, parts, vals, key, mask, with_count):
    return kernels._dense_group_sums(
        [jnp.asarray(p) for p in parts], [jnp.asarray(vals)],
        jnp.asarray(key), jnp.asarray(mask), g_pad, with_count=with_count)


def _check_one_pass(got, want, with_count, rtol):
    (psums, vsums, count), (exp_p, exp_v, exp_c) = got, want
    np.testing.assert_array_equal(np.asarray(psums), exp_p)
    np.testing.assert_allclose(np.asarray(vsums[0], np.float64), exp_v,
                               rtol=rtol)
    if with_count:
        np.testing.assert_array_equal(np.asarray(count), exp_c)
    else:
        assert count is None


@pytest.mark.parametrize("lanes", ["float", "float+count",
                                   "parts+float+count"])
@pytest.mark.parametrize("g_pad", [64, 512, 4096])
@pytest.mark.parametrize("x64", [True, False], ids=["f64", "f32"])
def test_dense_group_sums_one_pass(x64, g_pad, lanes):
    """Every summed lane and the count from ONE pass, against np.add.at:
    the direct one-hot (g 64) and the batched radix form (g 512, 4096),
    with float64 value lanes (x64, the CPU parity mode) and with the
    device path's float32 ones, which ride the bf16 operand as three
    exact pieces beside the part lanes (products exact, float32
    accumulation: a few ulps of the group's sum)."""
    import jax
    operands, want = _one_pass_case(g_pad, lanes)
    with jax.enable_x64(x64):
        assert kernels.sum_dtype() == (jnp.float64 if x64 else jnp.float32)
        got = _run_one_pass(g_pad, *operands)
        assert got[1].dtype == kernels.sum_dtype()
    _check_one_pass(got, want, operands[-1], 1e-12 if x64 else 5e-7)


@pytest.mark.parametrize("x64", [True, False], ids=["f64", "f32"])
def test_dense_group_sums_wide_table_fallback(x64):
    """g_pad 8192 is 64 hi bins: 2 part lanes + count (+ 3 float pieces
    in float32 mode) make n_l * g1 = 192 (384) > 128, the scan with a
    per-step concat dot; still equal. Under x64 the float64 lane's own
    contraction (1 * 64) stays batched."""
    import jax
    operands, want = _one_pass_case(8192, "parts+float+count", seed=8)
    with jax.enable_x64(x64):
        got = _run_one_pass(8192, *operands)
    _check_one_pass(got, want, True, 1e-12 if x64 else 5e-7)


@pytest.mark.parametrize("values", ["integers_to_2^24", "random_float32"])
def test_bf16_pieces_reconstruct_float32_exactly(values):
    """No bf16 rounding of a value: the three pieces add up to the
    float32 they were cut from, bit for bit."""
    rng = np.random.default_rng(7)
    if values == "integers_to_2^24":
        v = np.concatenate([rng.integers(0, 1 << 24, 200_000),
                            [0, 1, (1 << 24) - 1, 1 << 24]]
                           ).astype(np.float32)
        v = np.concatenate([v, -v])
    else:
        v = (rng.standard_normal(200_000)
             * 10.0 ** rng.uniform(-20, 20, 200_000)).astype(np.float32)
    pieces = kernels._bf16_pieces(jnp.asarray(v))
    assert [p.dtype for p in pieces] == [jnp.bfloat16] * 3
    total = np.zeros_like(v)
    for p in pieces:
        total = total + np.asarray(p).astype(np.float32)
    np.testing.assert_array_equal(total, v)


@pytest.mark.parametrize("t_slots", [300, 8192, 16384])
def test_slot_sum_tables_radix_and_direct(t_slots):
    """Both sides of the SLOT_RADIX_G threshold, with the drop slot, max
    7-bit plane values (the s8 contract: every int lane <= 127), and a
    non-divisible row count."""
    rng = np.random.default_rng(4)
    k = (1 << 16) + 777          # forces pad + a non-divisible chunk
    gslot = rng.integers(0, t_slots + 1, k).astype(np.int32)  # incl. drop
    int_vals = rng.integers(0, 128, (k, 3)).astype(np.int32)  # max 127
    f32_vals = (rng.random((k, 2)) * 10).astype(np.float64)
    count_mask = rng.random(k) < 0.9
    orig_chunk = kernels.SLOT_CHUNK
    kernels.SLOT_CHUNK = 1 << 16          # cover the multi-chunk scan
    try:
        ti, tf, tc = kernels._slot_sum_tables(
            jnp.asarray(gslot), t_slots, jnp.asarray(int_vals),
            jnp.asarray(f32_vals), jnp.asarray(count_mask))
    finally:
        kernels.SLOT_CHUNK = orig_chunk
    keep = gslot < t_slots
    exp_i = np.zeros((3, t_slots), dtype=np.int64)
    for li in range(3):
        np.add.at(exp_i[li], gslot[keep], int_vals[keep, li])
    np.testing.assert_array_equal(np.asarray(ti), exp_i)
    exp_f = np.zeros((2, t_slots))
    for li in range(2):
        np.add.at(exp_f[li], gslot[keep], f32_vals[keep, li])
    np.testing.assert_allclose(np.asarray(tf), exp_f, rtol=1e-9)
    exp_c = np.zeros(t_slots, dtype=np.int64)
    np.add.at(exp_c, gslot[keep & count_mask], 1)
    np.testing.assert_array_equal(np.asarray(tc), exp_c)


def test_radix_onehots_reconstruct():
    idx = jnp.asarray(np.arange(0, 1024, 7, dtype=np.int32))
    oh_hi, oh_lo = kernels._radix_onehots(idx, 1024, jnp.bfloat16)
    full = np.asarray(oh_hi)[:, :, None] * np.asarray(oh_lo)[:, None, :]
    direct = np.asarray(jnp.squeeze(
        jnp.asarray(np.eye(1024, dtype=np.float32))[idx]))
    np.testing.assert_array_equal(full.reshape(len(idx), 1024), direct)


def test_part_sums_oversized_fallback_exact():
    """_part_sums splits on 127 * padded < 2^31: the fast path fully
    reduces on device ([n_parts]); past ~16.9M padded rows the partsT
    block-partial fallback keeps int32 exact. Both must match an int64
    reference."""
    import numpy as np
    from pinot_tpu.ops.kernels import BLOCK, _part_sums

    rng = np.random.default_rng(5)
    for padded, expect_reduced in ((4 * BLOCK, True),
                                   (2065 * BLOCK, False)):   # >16.9M
        assert (127 * padded < 2**31) == expect_reduced
        lanes = rng.integers(0, 128, (2, padded)).astype(np.int8)
        mask = rng.random(padded) < 0.37
        sums, reduced = _part_sums(jnp.asarray(lanes), jnp.asarray(mask))
        assert reduced is expect_reduced
        got = np.asarray(sums).astype(np.int64)
        if not reduced:
            assert got.shape == (2, padded // BLOCK)
            got = got.sum(axis=1)
        ref = (lanes.astype(np.int64) * mask[None, :]).sum(axis=1)
        assert np.array_equal(got, ref)
