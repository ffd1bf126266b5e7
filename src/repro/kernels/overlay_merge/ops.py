"""Public wrapper: u64 overlay-pack <-> int32-plane packing around the
overlay_merge kernel.

All plane splitting/joining happens ON DEVICE: the serving engines hand over
the (3, cap) device-resident pack and the step's small (3, bcap) batch pack,
and nothing wider than the batch ever crosses the host boundary (DESIGN.md
§14).  The call runs in three jitted stages — split (u64 arithmetic), the
kernel (traced and lowered with x64 off, ``run_x32``), join."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..fused_lookup.fused_lookup import run_x32
from ..fused_lookup.ops import u64_join, u64_planes
from .overlay_merge import overlay_merge_planes
from .ref import overlay_merge_ref


@jax.jit
def _split(packs, batches):
    planes = []
    for p in (packs, batches):
        planes += [*u64_planes(p[:, 0], True), *u64_planes(p[:, 1], False),
                   (p[:, 2] != 0).astype(jnp.int32)]
    return planes


@jax.jit
def _join(okh, okl, oph, opl, otb):
    return jnp.stack([u64_join(okh, okl, True), u64_join(oph, opl, False),
                      otb.astype(jnp.uint64)], axis=1)


def overlay_merge_pack_stacked(packs, batches, cap_out: int, *,
                               interpret: bool = False,
                               use_ref: bool = False) -> jnp.ndarray:
    """Merge per-shard sorted write batches into the stacked overlay packs.

    ``packs`` (S, 3, Ca) u64 and ``batches`` (S, 3, Cb) u64 in overlay
    layout (keys/payloads/tombstones, u64-max key padding); returns the
    merged (S, 3, cap_out) packs — sorted union per shard, batch wins on
    collisions, tombstones retained."""
    planes = _split(jnp.asarray(packs, dtype=jnp.uint64),
                    jnp.asarray(batches, dtype=jnp.uint64))
    fn = overlay_merge_ref if use_ref else functools.partial(
        overlay_merge_planes, interpret=interpret)
    return _join(*run_x32(fn, *planes, cap_out=cap_out))


def overlay_merge_pack(pack, batch, cap_out: int, *,
                       interpret: bool = False,
                       use_ref: bool = False) -> jnp.ndarray:
    """Flat (3, Ca) ⊕ (3, Cb) -> (3, cap_out) merge — the engines' write
    path (``overlay_merge_backend_fn`` signature)."""
    return overlay_merge_pack_stacked(
        jnp.asarray(pack, dtype=jnp.uint64)[None],
        jnp.asarray(batch, dtype=jnp.uint64)[None],
        cap_out, interpret=interpret, use_ref=use_ref)[0]


def overlay_merge_pack_stacked_mesh(mesh, packs, batches, cap_out: int, *,
                                    interpret: bool = False) -> jnp.ndarray:
    """Stacked merge under ``shard_map``: each device merges only its own
    shard rows (per-device-local, no collectives — the fused-lookup
    placement idiom), for engines that keep the stacked packs
    device-partitioned instead of replicated.  S must be divisible by the
    mesh's device count."""
    spec = PartitionSpec("shards", None)
    fn = jax.jit(jax.shard_map(
        functools.partial(overlay_merge_planes, cap_out=cap_out,
                          interpret=interpret),
        mesh=mesh, in_specs=(spec,) * 10, out_specs=(spec,) * 5,
        check_vma=False))
    planes = _split(jnp.asarray(packs, dtype=jnp.uint64),
                    jnp.asarray(batches, dtype=jnp.uint64))
    return _join(*run_x32(fn, *planes))
