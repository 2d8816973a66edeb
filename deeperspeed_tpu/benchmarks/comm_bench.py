"""Collective-communication micro-benchmark (``ds_bench`` equivalent).

The reference's ``bin/ds_bench`` drives NCCL collective benchmarks
(allreduce/allgather/alltoall/p2p) across ranks; here the same surface runs
the XLA collectives the framework actually uses -- psum, all_gather,
all_to_all, ppermute -- inside shard_map over the active mesh axis, and
reports algorithmic bandwidth per op/size.

Each timing loop ends in ``block_until_ready`` (dispatch is asynchronous).
"""

import argparse
import json
import time


DEFAULT_SIZES_MB = [1, 4, 16, 64]


def _timed(fn, x, iters):
    out = fn(x).block_until_ready()  # warmup
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(out)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters


def _collectives(axis, n_dev):
    import jax
    import jax.numpy as jnp

    def allreduce(x):
        return jax.lax.psum(x, axis) / n_dev  # normalized to stay finite

    def allgather(x):
        g = jax.lax.all_gather(x, axis)
        return g[0]

    def reduce_scatter(x):
        return jnp.broadcast_to(
            jax.lax.psum_scatter(x, axis, tiled=True) / n_dev, x.shape)

    def alltoall(x):
        return jax.lax.all_to_all(x.reshape(n_dev, -1), axis, 0, 0).reshape(
            x.shape)

    def p2p_ring(x):
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        return jax.lax.ppermute(x, axis, perm)

    return {"allreduce": allreduce, "allgather": allgather,
            "reduce_scatter": reduce_scatter, "alltoall": alltoall,
            "p2p_ring": p2p_ring}


def _algo_bytes(op, nbytes, n_dev):
    """Algorithmic bytes moved per device (ring-algorithm convention, the
    reference's comms-logging bandwidth formulas)."""
    if op == "allreduce":
        return 2 * nbytes * (n_dev - 1) / n_dev
    if op in ("allgather", "reduce_scatter"):
        return nbytes * (n_dev - 1) / n_dev
    return nbytes  # alltoall, p2p


def run_bench(ops=None, sizes_mb=None, iters=20, axis="dp"):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..parallel import topology as topo

    mesh = topo.get_mesh()
    if mesh is None:
        mesh = topo.MeshTopology()
        topo.set_mesh(mesh)
    n_dev = mesh.sizes[axis]
    if n_dev < 2:
        print(json.dumps({"error": f"axis {axis!r} has size {n_dev}; "
                          "need >= 2 devices for collectives"}))
        return []
    colls = _collectives(axis, n_dev)
    ops = ops or list(colls)
    sizes_mb = sizes_mb or DEFAULT_SIZES_MB
    results = []
    for op in ops:
        for mb in sizes_mb:
            n = int(mb * 2 ** 20 // 4)
            n = max(n_dev, n - n % n_dev)  # divisible for alltoall/scatter
            local = jnp.ones((n,), jnp.float32)
            fn = jax.jit(jax.shard_map(
                colls[op], mesh=mesh.mesh, in_specs=P(),
                out_specs=P(), axis_names={axis}, check_vma=False))
            dt = _timed(fn, local, iters)
            bw = _algo_bytes(op, n * 4, n_dev) / dt / 1e9
            rec = {"op": op, "size_mb": mb, "ms": round(dt * 1e3, 3),
                   "algo_GBps": round(bw, 4), "devices": n_dev,
                   "axis": axis}
            print(json.dumps(rec), flush=True)
            results.append(rec)
    return results


def main(args=None):
    parser = argparse.ArgumentParser(
        description="XLA collective benchmark over the device mesh "
                    "(reference bin/ds_bench equivalent)")
    parser.add_argument("--ops", nargs="*", default=None,
                        help="subset of: allreduce allgather reduce_scatter "
                             "alltoall p2p_ring")
    parser.add_argument("--sizes-mb", nargs="*", type=float, default=None)
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--axis", default="dp")
    ns = parser.parse_args(args)
    run_bench(ops=ns.ops, sizes_mb=ns.sizes_mb, iters=ns.iters, axis=ns.axis)


if __name__ == "__main__":
    main()
