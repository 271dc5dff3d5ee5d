"""Kernel-fusion probe: measures the shipping filtered-SUM kernel at SSB
q1.x scale (100M rows, 8 segments) on the real chip.

Round-5 finding this probe validated: XLA on this stack does NOT
multi-output-fuse sibling reductions — a stack/concat of per-lane block
reduces (the old _part_sums) materialized the int32 where() contribs at
row scale (3.4GB accessed, 4.9ms) while ONE reduce over one elementwise
producer runs at the HBM roof (0.8GB, 0.8ms). See _part_sums in
pinot_tpu/ops/kernels.py. Timing: slope method — t = (t(N2)-t(N1))/(N2-N1)
cancels the per-dispatch round-trip exactly; params are scan-varying so
the body cannot be hoisted.
"""
import sys
import time

sys.path.insert(0, "/root/repo")

import jax
import jax.numpy as jnp
import numpy as np

S = 8
PER = 12_500_992
N1, N2 = 32, 160


def log(m):
    print(m, file=sys.stderr, flush=True)


def median(xs):
    return float(np.median(np.asarray(xs)))


def slope_time(run, tag, zs1, zs2):
    t0 = time.perf_counter()
    jax.device_get(run(zs1)); jax.device_get(run(zs2))
    log(f"{tag}: compiled in {time.perf_counter()-t0:.1f}s")
    s = []
    for _ in range(7):
        t0 = time.perf_counter(); jax.device_get(run(zs1))
        t1 = time.perf_counter(); jax.device_get(run(zs2))
        t2 = time.perf_counter()
        s.append(((t2 - t1) - (t1 - t0)) / (N2 - N1))
    ms = median(s) * 1e3
    log(f"{tag}: {ms:.3f} ms/exec ({S*PER/(median(s))/1e9:.0f}B rows/s)")
    return ms


def main():
    from pinot_tpu.parallel.sharded import make_mesh, get_sharded_kernel

    log(f"devices: {jax.devices()}")
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    lanes = {
        "d_year.ids": jax.random.randint(ks[0], (S, PER), 0, 7, jnp.int8),
        "lo_discount.ids": jax.random.randint(ks[1], (S, PER), 0, 11,
                                              jnp.int8),
        "lo_quantity.ids": jax.random.randint(ks[2], (S, PER), 0, 50,
                                              jnp.int8),
        "lo_revenue.parts": jax.random.randint(ks[3], (S, 3, PER), 0, 128,
                                               jnp.int8),
        "lo_supplycost.parts": jax.random.randint(ks[4], (S, 3, PER), 0,
                                                  128, jnp.int8),
    }
    jax.block_until_ready(list(lanes.values()))
    zs1 = jnp.zeros(N1, jnp.int32)
    zs2 = jnp.zeros(N2, jnp.int32)
    nd = jax.device_put(np.full(S, PER - 7, np.int32))
    mesh = make_mesh()
    results = {}

    FILTER = ("and", (
        ("pred", "eq_id", "d_year", "sv", None),
        ("pred", "range_ids", "lo_discount", "sv", None),
        ("pred", "range_ids", "lo_quantity", "sv", None)))

    cases = {
        "q1_one_sum": ((("sum", "lo_revenue", "sv", ("parts", 8192)),),
                       ("d_year.ids", "lo_discount.ids", "lo_quantity.ids",
                        "lo_revenue.parts")),
        "q4_two_sums": ((("sum", "lo_revenue", "sv", ("parts", 8192)),
                         ("sum", "lo_supplycost", "sv", ("parts", 8192))),
                        ("d_year.ids", "lo_discount.ids",
                         "lo_quantity.ids", "lo_revenue.parts",
                         "lo_supplycost.parts")),
    }
    for tag, (aggs, keys) in cases.items():
        sub = {k: lanes[k] for k in keys}
        fn = get_sharded_kernel(mesh, PER, FILTER, aggs, None, None,
                                tuple(sorted(sub.keys())))

        @jax.jit
        def timed(cols, nd, zs, _fn=fn):
            def body(c, z):
                fparams = (jnp.int32(1) + z, jnp.int32(1) + z,
                           jnp.int32(4) + z, jnp.int32(0) + z,
                           jnp.int32(24) + z)
                o = _fn(cols, fparams, nd)
                return c + sum(v.astype(jnp.float32).sum()
                               for v in o.values()), None
            return jax.lax.scan(body, jnp.float32(0), zs)[0]

        try:
            ca = timed.lower(sub, nd, zs1).compile().cost_analysis()
            if isinstance(ca, list):
                ca = ca[0]
            log(f"{tag}: cost bytes={ca.get('bytes accessed', 0)/1e9:.2f}GB")
        except Exception as e:  # noqa: BLE001
            log(f"{tag}: cost_analysis unavailable ({e})")
        results[tag] = slope_time(
            lambda zs, _t=timed, _s=sub: _t(_s, nd, zs), tag, zs1, zs2)
    print(results)


if __name__ == "__main__":
    main()
