"""Lowering regressions for the kernels only a chip can run: each is
traced and lowered for TPU (Mosaic MLIR) here on the CPU, so a JAX bump
cannot break the one-chip or the multi-chip Pallas path unseen again —
the shard_map'd step had never traced anywhere before PR 21.  Slow: each
pays ~30 s of tracing the unrolled ladder."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from tendermint_tpu.ops import ed25519 as edops
from tendermint_tpu.ops import pallas_ed25519 as pe
from tendermint_tpu.parallel import sharding

pytestmark = pytest.mark.slow


def _lowers_to_mosaic(fn, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, jnp.int8) for s in shapes]
    text = fn.trace(*args, **static).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


def test_packed_ladder_lowers_for_tpu():
    _lowers_to_mosaic(pe.verify_packed_pallas, (128, 256),
                      tile=edops.PALLAS_TILE)


@pytest.mark.parametrize("chunk", [edops.SPLIT_CHUNK_SMALL,
                                   edops.SPLIT_CHUNK])
def test_split_ladder_lowers_for_tpu(chunk):
    # the two shapes the split route launches (ops/ed25519._split_chunk)
    _lowers_to_mosaic(pe.verify_packed_split_pallas, (32, chunk),
                      (96, chunk), tile=edops.PALLAS_TILE)


def test_mesh_pallas_step_lowers_for_tpu_on_four_devices():
    plane = sharding._DataPlane(sharding.make_mesh(jax.devices()[:4]))
    assert plane.nshard == 4
    _lowers_to_mosaic(plane._packed_fn(), (128, 4 * edops.PALLAS_TILE))
