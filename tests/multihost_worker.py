"""Worker process for the 2-process jax.distributed DCN dryrun
(tests/test_multihost_replay.py; SURVEY §5.8, VERDICT r3 #8).

Each process owns 4 virtual CPU devices; together they form one global
8-device mesh spanning "hosts".  Both enter the SAME sharded
verification computation in lockstep — exactly the discipline the
coordinated blocksync-replay path provides (a single thread applying a
deterministic window, unlike uncoordinated reactor calls) — and each
writes its addressable bitmap shards for the parent to stitch and
check.  XLA inserts the cross-process collective for the replicated
all-valid bit (the psum in make_sharded_verifier's out_shardings).

Two modes (argv[6], default "raw"):

  raw   — the original dryrun: make_sharded_verifier driven directly,
          per-process addressable bitmap shards written for the parent
          to stitch.
  prod  — the PRODUCTION path (ADR-027): ops/ed25519.verify_batch
          called inside a sharding.lockstep() window, exactly the shape
          blocksync replay_window / coordinated bulk verify produce.
          The route must come back "global-mesh" with the psum'd
          all-valid bit in the launch record; the returned bitmap is
          replicated, so each process emits the FULL bitmap and the
          parent asserts both copies equal the host oracle.

Usage: python multihost_worker.py <pid> <nproc> <coord> <npz> <out> [mode]
Env: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _main_prod(pid, nproc, npz_path, out_path):
    """Production route: verify_batch under lockstep() — the global
    mesh plane end-to-end, including the AOT-compile + barrier seal and
    the per-process addressable staging inside _put_sharded."""
    import numpy as np

    from tendermint_tpu.ops import ed25519 as edops
    from tendermint_tpu.parallel import sharding as shd

    assert shd.global_mesh_ready(), "distributed runtime not detected"

    data = np.load(npz_path)
    pubs = [bytes(p) for p in data["pubs"]]
    sigs = [bytes(s) for s in data["sigs"]]
    msgs = [bytes(m) for m in data["msgs"]]

    with shd.lockstep():
        bitmap = edops.verify_batch(pubs, msgs, sigs)
    ll = edops.last_launch()
    with open(out_path, "w") as f:
        json.dump({
            "pid": pid,
            "path": ll.get("path"),
            "shards": ll.get("shards"),
            "all_valid": ll.get("all_valid"),
            # a backend without multi-process computations (CPU jaxlib
            # today) latches the global plane off after the first real
            # collective fault; the parent asserts the degrade contract
            # in that case instead of the global route
            "global_latched_off": shd._GLOBAL_PLANE is False,
            "bitmap": np.asarray(bitmap).astype(int).tolist(),
        }, f)


def main():
    pid, nproc = int(sys.argv[1]), int(sys.argv[2])
    coord, npz_path, out_path = sys.argv[3], sys.argv[4], sys.argv[5]
    mode = sys.argv[6] if len(sys.argv) > 6 else "raw"

    import jax

    # forced through config as well as env, like tests/conftest.py:
    # these workers must never claim a chip the host may have
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nproc, process_id=pid)
    assert len(jax.devices()) == 4 * nproc, jax.devices()
    assert len(jax.local_devices()) == 4

    if mode == "prod":
        _main_prod(pid, nproc, npz_path, out_path)
        return

    from jax.sharding import NamedSharding, PartitionSpec as P

    from tendermint_tpu.ops import ed25519 as edops
    from tendermint_tpu.parallel import sharding as shd

    data = np.load(npz_path)
    pubs, sigs = data["pubs"], data["sigs"]
    msgs = [bytes(m) for m in data["msgs"]]

    # identical host staging on every process (deterministic)
    dev, host_ok = edops.prepare_batch(pubs, sigs, msgs)
    n = host_ok.shape[0]
    ndev = 4 * nproc
    nb = -(-n // ndev) * ndev
    dev = edops._pad_dev(dev, n, nb)

    mesh = shd.make_mesh(jax.devices())
    jitted, _run = shd.make_sharded_verifier(mesh)
    sh = NamedSharding(mesh, P(shd.BATCH_AXIS))

    def to_global(a):
        return jax.make_array_from_callback(
            a.shape, sh, lambda idx: np.ascontiguousarray(a[idx]))

    args = (to_global(dev["pub"]), to_global(dev["r"]),
            to_global(dev["s_digits"]), to_global(dev["k_digits"]))
    # AOT-compile, then rendezvous at a coordination-service barrier
    # before executing: compilation is per-process and can skew by
    # minutes under load, while Gloo's collective-context setup inside
    # the first execution only waits ~30 s for the other process.
    compiled = jitted.lower(*args).compile()
    from jax._src import distributed as _dist
    _dist.global_state.client.wait_at_barrier("tm_tpu_mh_compiled",
                                              240 * 1000)
    bitmap, all_valid = compiled(*args)
    # the all-valid bit is replicated (out_shardings P()): every process
    # observes the same value via the XLA-inserted cross-host reduction
    av = bool(np.asarray(
        [s.data for s in all_valid.addressable_shards][0]))
    shards = sorted(
        ((s.index[0].start or 0, np.asarray(s.data))
         for s in bitmap.addressable_shards), key=lambda t: t[0])
    with open(out_path, "w") as f:
        json.dump({
            "pid": pid,
            "all_valid": av,
            "shards": [{"start": int(st), "bits": b.astype(int).tolist()}
                       for st, b in shards],
        }, f)


if __name__ == "__main__":
    main()
