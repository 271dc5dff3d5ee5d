"""IVF training / assignment device kernels.

The two hot loops of IVF — the [n, c] distance matrix and the one-hot
recentering — are batched matmuls, so both kernels are MXU work by
construction (unlike the query-time scoring tree, which trades the MXU
for bit-exact cross-backend accumulation; training has no such
contract — the ARTIFACT it produces is what gets pinned, and the
seeded host loop makes that artifact reproducible per backend). Both
matmuls carry an explicit f32 precision: at the TPU's default the
operands are rounded to bf16, which on a v5e put 25 of 8192 rows in a
centroid that was not their nearest (PR 21 chip run).

Shapes are static (pow2-padded rows/centroids/dim) with live counts as
runtime scalars, so Lloyd's whole fixed-iteration loop reuses one
compiled step. Builders are lru-cached and traced by the tpulint deep
tier through `kernels.extra_contract_cases` at both shape buckets.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from pinot_tpu.ops import kernels


@functools.lru_cache(maxsize=64)
def build_ivf_assign_kernel(n_pad: int, c_pad: int, dim_pad: int):
    """kernel(data f32 [n_pad, dim_pad], centroids f32 [c_pad, dim_pad],
    n_rows i32, n_centroids i32) → {"ivf.assign": i32 [n_pad] nearest
    live centroid (ties → lower id), "ivf.dist": f32 [n_pad] squared L2
    to it (0 on padding rows)}."""

    def kernel(data, centroids, n_rows, n_centroids):
        row_n2 = kernels.vec_tree_sum(data * data)            # [n_pad]
        cen_n2 = kernels.vec_tree_sum(centroids * centroids)  # [c_pad]
        cross = jnp.matmul(data, centroids.T,                 # MXU [n, c]
                           precision=jax.lax.Precision.HIGHEST)
        d2 = row_n2[:, None] - 2.0 * cross + cen_n2[None, :]
        cval = jnp.arange(c_pad, dtype=jnp.int32) < n_centroids
        d2 = jnp.where(cval[None, :], d2, jnp.float32(jnp.inf))
        assign = jnp.argmin(d2, axis=1).astype(jnp.int32)
        rval = jnp.arange(n_pad, dtype=jnp.int32) < n_rows
        # the matmul identity can go slightly negative — clamp, and
        # zero padding rows so block sums need no host-side masking
        dist = jnp.where(rval, jnp.maximum(jnp.min(d2, axis=1), 0.0),
                         jnp.float32(0)).astype(jnp.float32)
        return {"ivf.assign": assign, "ivf.dist": dist}

    return kernel


@functools.lru_cache(maxsize=64)
def build_ivf_train_kernel(n_pad: int, c_pad: int, dim_pad: int):
    """One Lloyd's step: assign + one-hot recentering. Empty clusters
    keep their prior centroid (deterministic — no reseeding). Returns
    {"ivf.centroids": f32 [c_pad, dim_pad], "ivf.counts": i32 [c_pad]}."""
    assign_k = build_ivf_assign_kernel(n_pad, c_pad, dim_pad)

    def kernel(data, centroids, n_rows, n_centroids):
        assign = assign_k(data, centroids, n_rows, n_centroids)["ivf.assign"]
        rval = jnp.arange(n_pad, dtype=jnp.int32) < n_rows
        oh = ((assign[:, None] == jnp.arange(c_pad, dtype=jnp.int32)) &
              rval[:, None]).astype(jnp.float32)              # [n, c]
        sums = jnp.matmul(oh.T, data,                         # MXU [c, d]
                          precision=jax.lax.Precision.HIGHEST)
        counts = kernels.vec_tree_sum(oh.T)                   # f32 [c_pad]
        new_c = jnp.where(counts[:, None] > 0,
                          sums / jnp.maximum(counts[:, None], 1.0),
                          centroids).astype(jnp.float32)
        return {"ivf.centroids": new_c,
                "ivf.counts": counts.astype(jnp.int32)}

    return kernel


@functools.lru_cache(maxsize=64)
def build_ivf_probe_kernel(c_pad: int, dim_pad: int, nprobe: int,
                           metric: str):
    """Standalone probe-select (the same helper the fused "ivf_probe"
    filter pred calls): kernel(centroids f32 [c_pad, dim_pad], cvalid
    bool [c_pad], q f32 [dim_pad], q_norm f32) → {"ivf.probe": i32
    [nprobe] top-nprobe live centroid ids, "ivf.probe_ok": bool
    [nprobe] slot validity when fewer live centroids than nprobe}."""

    def kernel(centroids, cvalid, q, q_norm):
        probe, ok = kernels.ivf_select_probes(centroids, cvalid, q,
                                              q_norm, metric, nprobe)
        return {"ivf.probe": probe, "ivf.probe_ok": ok}

    return kernel


@functools.lru_cache(maxsize=64)
def get_ivf_assign_kernel(n_pad: int, c_pad: int, dim_pad: int):
    return jax.jit(kernels.named_kernel(
        build_ivf_assign_kernel(n_pad, c_pad, dim_pad), "ivf_assign"))


@functools.lru_cache(maxsize=64)
def get_ivf_train_kernel(n_pad: int, c_pad: int, dim_pad: int):
    return jax.jit(kernels.named_kernel(
        build_ivf_train_kernel(n_pad, c_pad, dim_pad), "ivf_train"))
