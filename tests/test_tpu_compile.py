"""Compile-only guards for a TPU v5e (no chip needed).

The TPU compiler is installed next to JAX, and it compiles for a chip that
is described rather than attached: each test here lowers a program of the
serving path for one chip of a described ``v5e:2x2`` topology, from shapes
alone, and fails with whatever the chip's compiler would refuse (unaligned
blocks, 64-bit types in a kernel, VMEM or HBM overflow).  Nothing runs.

Covered: the jitted jnp read and scan steps of the sharded engine at the
pool widths of the 200M-key, 16-shard deployment ``chip_smoke.py`` loads,
on one chip and as the mesh engine over four; the fused lookup kernel with
both leaf strategies at a geometry the tuner admits; the overlay-merge
kernel; the jnp overlay merge over a window of the 200M-key pack.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test runner's
workers all import this file.
"""
import dataclasses
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import Aulid, DeltaOverlay, partition_bulkload
from repro.core.device_index import build_device_index, stack_device_indexes
from repro.core.lookup import (device_arrays, lookup_batch_sharded_overlay,
                               merge_overlay_pack_jnp, overlay_arrays,
                               overlay_merge_write_back,
                               scan_batch_sharded_overlay,
                               stacked_device_arrays)
from repro.core.workloads import make_dataset, payloads_for
from repro.kernels.fused_lookup import ops as fops
from repro.kernels.fused_lookup import tuning
from repro.kernels.fused_lookup.fused_lookup import fused_lookup_planes
from repro.kernels.overlay_merge.overlay_merge import overlay_merge_planes

# Per-shard pool widths of the stacked mirror chip_smoke.py serves: 200M
# OSM-like keys over 16 shards, default AulidConfig (its "load" line prints
# them), and its merged overlay pack (gamma*n = 10M -> next pow2).
SHARDS = 16
WIDTHS_200M = {"slot": 131072, "node": 2, "pa": (8192, 64), "bt": (2, 1),
               "leaf": (65536, 256)}
OVERLAY_CAP_200M = 1 << 24
GETS, SCANS, SCAN_COUNT, OV_BOUND = 2048, 8, 128, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache (if
    # one is on) but cannot be read back without the chip: keep it off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _stacked_200m(sharding) -> dict:
    """ShapeDtypeStructs of the 200M-key stacked operand dict (dtypes and
    ranks from a tiny real stack, widths from WIDTHS_200M)."""
    keys = make_dataset("osm", 4000, seed=0)
    part = partition_bulkload(keys, payloads_for(keys), 2)
    tiny = stacked_device_arrays(stack_device_indexes(
        [build_device_index(s) for s in part.shards], part.bounds))
    w = WIDTHS_200M
    per_field = {"slot_tag": (w["slot"],), "slot_key": (w["slot"],),
                 "slot_ptr": (w["slot"],), "next_occ": (w["slot"],),
                 "succ_slot": (w["slot"],), "node_base": (w["node"],),
                 "node_fanout": (w["node"],), "node_slope": (w["node"],),
                 "node_intercept": (w["node"],),
                 "node_overflow_slot": (w["node"],),
                 "leaf_count": w["leaf"][:1], "leaf_next": w["leaf"][:1],
                 "pa_keys": w["pa"], "pa_ptrs": w["pa"], "bt_keys": w["bt"],
                 "bt_ptrs": w["bt"], "leaf_keys": w["leaf"],
                 "leaf_pay": w["leaf"], "meta": (2,), "last_leaf_min": ()}
    out = {f: _sds((SHARDS,) + shape, tiny[f].dtype, sharding)
           for f, shape in per_field.items()}
    out["bounds"] = _sds((SHARDS - 1,), jnp.uint64, sharding)
    out["leaf_next_chain"] = _sds((SHARDS * w["leaf"][0],), jnp.int32,
                                  sharding)
    out["snap_token"] = _sds((), jnp.int64, sharding)
    out["bounds_version"] = _sds((), jnp.int64, sharding)
    return out


def _overlay_200m(sharding) -> dict:
    return {"ov_pack": _sds((3, OVERLAY_CAP_200M), jnp.uint64, sharding),
            "ov_token": _sds((), jnp.int64, sharding)}


def test_jnp_read_step_200m(one_chip):
    q = _sds((GETS,), jnp.uint64, one_chip)
    compiled = lookup_batch_sharded_overlay.lower(
        _stacked_200m(one_chip), _overlay_200m(one_chip), q,
        height=3).compile()
    mem = compiled.memory_analysis()
    # the program (pools + overlay + temporaries) fits one 16 GB chip
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_jnp_scan_step_200m(one_chip):
    q = _sds((SCANS,), jnp.uint64, one_chip)
    compiled = scan_batch_sharded_overlay.lower(
        _stacked_200m(one_chip), _overlay_200m(one_chip), q,
        count=SCAN_COUNT, height=3, ov_bound=OV_BOUND).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_mesh_steps_200m(topo):
    """The mesh engine's read and scan steps over the four chips of the
    described host (``chip_smoke.py --chips 4``): pools sharded on the
    leading shard axis, each device holding a quarter of them."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.core.lookup import (lookup_batch_sharded_mesh_packed,
                                   scan_batch_sharded_overlay_mesh)
    from repro.parallel.index_placement import stacked_spec
    mesh = Mesh(np.array(topo.devices), ("shards",))

    def placed(d):
        return {k: _sds(v.shape, v.dtype, NamedSharding(
            mesh, stacked_spec(k, v.shape, mesh) if v.ndim
            else PartitionSpec())) for k, v in d.items()}

    one = SingleDeviceSharding(topo.devices[0])
    stk, ovr = placed(_stacked_200m(one)), placed(_overlay_200m(one))
    q_mat = _sds((SHARDS, 256), jnp.uint64,
                 NamedSharding(mesh, PartitionSpec("shards", None)))
    read = lookup_batch_sharded_mesh_packed.lower(mesh, stk, q_mat,
                                                  height=3).compile()
    leaf_bytes = SHARDS * 2 * 8 * WIDTHS_200M["leaf"][0] \
        * WIDTHS_200M["leaf"][1]
    assert read.memory_analysis().argument_size_in_bytes < leaf_bytes / 2
    q = _sds((SCANS,), jnp.uint64, NamedSharding(mesh, PartitionSpec()))
    scan_batch_sharded_overlay_mesh.lower(
        mesh, stk, ovr, q, count=SCAN_COUNT, height=3, ov_bound=OV_BOUND,
        qcap=SCANS).compile()


@pytest.fixture(scope="module")
def admitted_mirror():
    """A real 60k-key mirror + small overlay, packed as the fused kernel's
    operands, at a geometry the tuner admits for both leaf strategies."""
    keys = make_dataset("osm", 60_000, seed=0)
    idx = Aulid()
    idx.bulkload(keys, payloads_for(keys))
    arrs = device_arrays(build_device_index(idx))
    ov = DeltaOverlay()
    for k in keys[:600:3]:
        ov.record_insert(int(k), 7)
    ovr = overlay_arrays(ov)
    geom = tuning.PoolGeometry.from_device_arrays(arrs, ovr)
    return fops.FusedOperands(arrs), fops.OverlayOperands(ovr), geom


@pytest.mark.parametrize("leaf", ["persistent", "looped"])
def test_fused_kernel(one_chip, admitted_mirror, leaf):
    ops, ovo, geom = admitted_mirror
    st = tuning.choose_strategy(geom, interpret=False)   # admitted: no raise
    st = dataclasses.replace(st, leaf=leaf)
    cfg = fops.kernel_config(ops.geom, st, 3,
                             bounds_len=int(ops.bounds.shape[1]),
                             overlay_cap=ovo.cap, sharded=False,
                             has_overlay=True)
    T = 4
    q = _sds((T * st.qb, 1), jnp.int32, one_chip)
    args = [_sds(a.shape, a.dtype, one_chip)
            for a in (*ops.pool_args(), ovo.ov_u32, ovo.ov_tomb)]
    with jax.enable_x64(False):
        compiled = fused_lookup_planes.lower(
            cfg, _sds((T,), jnp.int32, one_chip), q, q, *args,
            interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("S,ca,cb", [(1, 4096, 256), (4, 4096, 256)])
def test_merge_kernel(one_chip, S, ca, cb):
    a = _sds((S, ca), jnp.int32, one_chip)
    b = _sds((S, cb), jnp.int32, one_chip)
    with jax.enable_x64(False):
        compiled = overlay_merge_planes.lower(
            a, a, a, a, a, b, b, b, b, b, cap_out=ca,
            interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("w", [16384, 1 << 20])
def test_merge_window_200m(one_chip, w):
    """The jnp delta merge over a window of the 200M-key deployment's pack,
    and its write-back into the whole (3, 2^24) pack.  The merge holds one
    loop, the search of the batch into the window: no binary search runs
    per window slot."""
    pack = _sds((3, OVERLAY_CAP_200M), jnp.uint64, one_chip)
    head = _sds((3, w), jnp.uint64, one_chip)
    batch = _sds((3, 8192), jnp.uint64, one_chip)
    merged = merge_overlay_pack_jnp.lower(head, batch, cap_out=w).compile()
    assert merged.out_info.shape == (3, w)
    assert len(re.findall(r" while\(", merged.as_text())) <= 1
    back = overlay_merge_write_back.lower(pack, head).compile()
    assert back.out_info.shape == (3, OVERLAY_CAP_200M)
