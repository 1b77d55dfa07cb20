"""Where a cold mesh-pallas launch's wall goes on a multi-chip TPU host, and
whether its bitmap is right (PR 21; PERF.md "Chip bring-up" quotes its log).

The local plane is off by default on TPU (parallel/sharding.MESH_ON_TPU);
this script switches it on for its own process, splits the first launch of
the 4 x 256-row bucket into trace / lower / compile, then drives
ops/ed25519.verify_batch at that size and at 10,000 rows against a
per-signature OpenSSL oracle.  One line of JSON per step, also appended to
chiprun_out/mesh_probe.log.  Budget ~9 min of wall on four chips.

Run: chiprun --chips 4 --timeout 1200 -- python scripts/mesh_probe.py
"""
import hashlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

LOG = os.path.join(ROOT, "chiprun_out", "mesh_probe.log")
T0 = time.perf_counter()
REC_KEYS = ("path", "n", "nb", "shards", "first_launch", "compile_s",
            "wall_s", "shard_rows", "stage_s", "h2d_s", "drain_s")


def say(**kw):
    kw["t"] = round(time.perf_counter() - T0, 2)
    line = json.dumps(kw)
    print(line, flush=True)
    with open(LOG, "a") as f:
        f.write(line + "\n")


def batch(n: int, bad):
    """(pubs, msgs, sigs, oracle bitmap): n signed votes from seeded keys,
    the lanes in `bad` tampered."""
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey)

    from tendermint_tpu.crypto import ed25519 as edkeys

    privs = [edkeys.PrivKey(hashlib.sha256(b"probe/%d/%d" % (n, i)).digest())
             for i in range(n)]
    pubs = [p.pub_key().bytes() for p in privs]
    msgs = [b"mesh probe vote %d/%6d" % (n, i) for i in range(n)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    for i in bad:
        sigs[i] = bytes([sigs[i][0] ^ 1]) + sigs[i][1:]
    want = np.zeros(n, dtype=bool)
    for i in range(n):
        try:
            Ed25519PublicKey.from_public_bytes(pubs[i]).verify(sigs[i],
                                                               msgs[i])
            want[i] = True
        except InvalidSignature:
            pass
    return pubs, msgs, sigs, want


def served(n: int, bad):
    """Two calls of the served route at n rows, with their launch records."""
    from tendermint_tpu.ops import ed25519 as edops

    pubs, msgs, sigs, want = batch(n, bad)
    for call in range(2):
        t = time.perf_counter()
        bits = edops.verify_batch(pubs, msgs, sigs)
        dt = time.perf_counter() - t
        rec = dict(edops.last_launch())
        say(step="verify_batch", n=n, call=call, s=round(dt, 4),
            equal=bool(np.array_equal(bits, want)),
            false=[int(i) for i in np.flatnonzero(~bits)],
            rec={k: rec.get(k) for k in REC_KEYS})


def main() -> int:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < 2:
        print(f"mesh_probe: needs a multi-chip TPU host, found "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 2
    os.makedirs(os.path.dirname(LOG), exist_ok=True)
    say(devices=[str(d) for d in devices], kind=devices[0].device_kind)

    import tendermint_tpu  # noqa: F401 - places the compile cache
    from tendermint_tpu.ops import ed25519 as edops
    from tendermint_tpu.parallel import sharding

    sharding.MESH_ON_TPU = True
    plane = sharding.data_plane()
    say(plane=plane is not None, nshard=getattr(plane, "nshard", None))

    n = plane.nshard * edops.PALLAS_TILE
    pubs, msgs, sigs, want = batch(n, [0, n // 4 - 1, n // 4, n - 1])
    packed, host_ok = edops.prepare_batch_packed(pubs, sigs, msgs)
    arg = jax.device_put(np.ascontiguousarray(packed),
                         NamedSharding(plane.mesh,
                                       P(None, sharding.BATCH_AXIS)))
    fn = plane._packed_fn()
    stage = arg
    for step, advance in (("trace", lambda a: fn.trace(a)),
                          ("lower", lambda tr: tr.lower()),
                          ("compile", lambda lo: lo.compile())):
        t = time.perf_counter()
        stage = advance(stage)
        say(step=step, s=round(time.perf_counter() - t, 2))
    got = np.asarray(stage(arg))[:n] & host_ok
    say(step="aot_bitmap", equal=bool(np.array_equal(got, want)))

    served(n, [0, n // 4 - 1, n // 4, n - 1])
    served(10_000, [1, 5_000, 9_999])
    say(done=True, cache_entries=len(
        os.listdir(jax.config.jax_compilation_cache_dir)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
