"""JAX pytree ↔ host-memory shard records.

The torch reference flattens a ``state_dict`` of CPU tensors
(ckpt_saver.py:270). The TPU equivalent must handle leaves that are
GSPMD-sharded ``jax.Array``s: every host process owns a subset of shards
(``arr.addressable_shards``), each covering a global index. We record
``(path, global_shape, dtype, index, data)`` per shard so that

- saving is per-host and embarrassingly parallel (no gather), and
- loading can reassemble any slice of the global array from whichever
  shard files contain it, even if the mesh/world size changed.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

# index of a shard in the global array: ((start, stop) per dim); () = scalar
Index = Tuple[Tuple[int, int], ...]


@dataclass
class ShardRecord:
    """One contiguous block of one leaf, owned by this host."""

    path: str  # "/"-joined pytree key path
    global_shape: Tuple[int, ...]
    dtype: str
    index: Index
    data: Optional[np.ndarray] = None  # None once serialized to shm

    @property
    def nbytes(self) -> int:
        n = np.dtype(self.dtype).itemsize
        for lo, hi in self.index:
            n *= hi - lo
        return n

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(hi - lo for lo, hi in self.index)


def _slices_to_index(slices: Sequence[slice], shape: Sequence[int]) -> Index:
    out = []
    for s, dim in zip(slices, shape):
        lo = 0 if s.start is None else s.start
        hi = dim if s.stop is None else s.stop
        out.append((int(lo), int(hi)))
    return tuple(out)


def _keystr(kp) -> str:
    import jax

    return "/".join(
        str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
        for k in kp
    ) or "."


def host_shard_records(state: Any) -> List[ShardRecord]:
    """Flatten a pytree into this host's shard records (device→host copy).

    ``jax.Array`` leaves contribute their addressable shards with
    ``replica_id == 0`` (so replicated arrays are saved exactly once per
    replica set); numpy/python leaves are saved whole by every process that
    holds them — load dedupes by path+index, and on a single host there is
    no duplication at all. Device→host copies are started async for all
    shards before any is consumed.
    """
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    records: List[ShardRecord] = []
    pending: List[Tuple[ShardRecord, Any]] = []
    for kp, leaf in leaves:
        path = _keystr(kp)
        if isinstance(leaf, jax.Array):
            gshape = tuple(leaf.shape)
            dt = str(leaf.dtype)
            for shard in leaf.addressable_shards:
                if shard.replica_id != 0:
                    continue
                rec = ShardRecord(
                    path=path,
                    global_shape=gshape,
                    dtype=dt,
                    index=_slices_to_index(shard.index, gshape),
                )
                try:  # overlap D2H of all shards
                    shard.data.copy_to_host_async()
                except Exception:
                    pass
                pending.append((rec, shard.data))
        else:
            arr = np.asarray(leaf)
            records.append(
                ShardRecord(
                    path=path,
                    global_shape=tuple(arr.shape),
                    dtype=str(arr.dtype),
                    index=tuple((0, d) for d in arr.shape),
                    data=arr,
                )
            )
    for rec, dev in pending:
        rec.data = np.asarray(dev)
        records.append(rec)
    return records


def host_shard_plan(state: Any) -> List[Tuple[ShardRecord, Any]]:
    """``host_shard_records`` without the device→host copies: each
    entry is ``(record_with_data_None, source)`` where ``source`` is
    the single-device ``jax.Array`` shard still on the chip, or a host
    numpy copy for non-device leaves. The chunked stager (ckpt/engine.py)
    drains sources incrementally between train steps; host leaves are
    copied eagerly because they are tiny AND mutable (e.g. sampler
    state) — the snapshot must be of save time, not drain time."""
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    plan: List[Tuple[ShardRecord, Any]] = []
    for kp, leaf in leaves:
        path = _keystr(kp)
        if isinstance(leaf, jax.Array):
            gshape = tuple(leaf.shape)
            dt = str(leaf.dtype)
            for shard in leaf.addressable_shards:
                if shard.replica_id != 0:
                    continue
                rec = ShardRecord(
                    path=path,
                    global_shape=gshape,
                    dtype=dt,
                    index=_slices_to_index(shard.index, gshape),
                )
                plan.append((rec, shard.data))
        else:
            arr = np.array(leaf)  # eager copy: see docstring
            plan.append(
                (
                    ShardRecord(
                        path=path,
                        global_shape=tuple(arr.shape),
                        dtype=str(arr.dtype),
                        index=tuple((0, d) for d in arr.shape),
                    ),
                    arr,
                )
            )
    return plan


def target_shards(leaf) -> Optional[List[Tuple[Any, Index]]]:
    """``[(device, index), ...]`` this process must fill to rebuild
    ``leaf`` — one entry per addressable shard, replicas included.

    Accepts a concrete ``jax.Array`` *or* an abstract
    ``jax.ShapeDtypeStruct`` carrying a sharding, so a restarted worker
    can describe its restore target without allocating device zeros
    first. Returns None for host (numpy/python) leaves."""
    import jax

    if isinstance(leaf, jax.Array) and hasattr(leaf, "sharding"):
        gshape = tuple(leaf.shape)
        return [
            (s.device, _slices_to_index(s.index, gshape))
            for s in leaf.addressable_shards
        ]
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None and hasattr(
        sharding, "addressable_devices_indices_map"
    ):
        gshape = tuple(leaf.shape)
        return [
            (d, _slices_to_index(idx, gshape))
            for d, idx in sharding.addressable_devices_indices_map(
                gshape
            ).items()
        ]
    return None


def host_shard_index_set(state: Any) -> set:
    """The ``(path, index)`` pairs ``host_shard_records`` would produce,
    without performing any device→host copies. Replicated shards collapse
    to one entry (a set), matching the save side's replica_id==0 filter.
    Accepts abstract spec leaves like ``target_shards``."""
    leaves_with_path = _flatten_with_path(state)
    out = set()
    for kp, leaf in leaves_with_path:
        path = _keystr(kp)
        shards = target_shards(leaf)
        if shards is not None:
            for _, idx in shards:
                out.add((path, idx))
        else:
            arr = np.asarray(leaf)
            out.add((path, tuple((0, d) for d in arr.shape)))
    return out


def _flatten_with_path(state):
    import jax

    return jax.tree_util.tree_flatten_with_path(state)[0]


def assemble_leaf(
    global_shape: Tuple[int, ...],
    dtype: str,
    want: Index,
    records: List[ShardRecord],
) -> np.ndarray:
    """Build the ``want`` slice of a leaf from overlapping shard records."""
    shape = tuple(hi - lo for lo, hi in want)
    # fast path: a single record covers the request exactly
    for r in records:
        if r.index == want and r.data is not None:
            return r.data
    out = np.empty(shape, dtype=np.dtype(dtype))
    # coverage mask, not a count: overlapping records (e.g. files from two
    # world layouts in one step dir) must not mask a real hole — a hole
    # would silently return np.empty garbage as weights
    covered = np.zeros(shape, dtype=bool) if shape else np.zeros((), bool)
    for r in records:
        if r.data is None:
            continue
        # overlap of r.index with want, in both coordinate systems
        src_sel, dst_sel, ok = [], [], True
        for (wlo, whi), (rlo, rhi) in zip(want, r.index):
            lo, hi = max(wlo, rlo), min(whi, rhi)
            if lo >= hi:
                ok = False
                break
            src_sel.append(slice(lo - rlo, hi - rlo))
            dst_sel.append(slice(lo - wlo, hi - wlo))
        if not ok:
            continue
        block = r.data[tuple(src_sel)] if src_sel else r.data
        if dst_sel:
            out[tuple(dst_sel)] = block
            covered[tuple(dst_sel)] = True
        else:
            out[...] = block
            covered[...] = True
    if not covered.all():
        raise ValueError(
            f"checkpoint shards do not cover requested index {want} of "
            f"shape {global_shape}"
        )
    return out


def _unpack_flat(flat, layout):
    """On-device unpack of one flat transfer buffer: static slices +
    reshapes, fused by XLA into HBM-bandwidth copies."""
    import jax

    return tuple(
        jax.lax.slice(flat, (o,), (o + n,)).reshape(shape)
        for (o, n, shape) in layout
    )


_unpack_jits: Dict[bool, Any] = {}


def _get_unpack_jit(donate: bool):
    """Donate the flat buffer only at GB scale — XLA warns (and gains
    nothing) when a tiny donated buffer cannot be aliased."""
    if donate not in _unpack_jits:
        import jax

        _unpack_jits[donate] = jax.jit(
            _unpack_flat,
            static_argnums=(1,),
            donate_argnums=(0,) if donate else (),
        )
    return _unpack_jits[donate]


def restore_state(
    target: Any,
    read_records: Callable[[str], List[ShardRecord]],
) -> Any:
    """Rebuild a pytree shaped/sharded like ``target`` from shard records.

    ``read_records(path)`` returns every available record for a leaf.
    ``target`` leaves may be concrete ``jax.Array``s *or* abstract
    ``jax.ShapeDtypeStruct``s carrying shardings (``target_shards``) — a
    restarted worker should pass specs so the restore never materializes
    a throwaway zeros-state on device.

    Transfer strategy: all shard blocks bound for one (device, dtype)
    are packed into a single flat host buffer and moved with ONE
    ``device_put``, then sliced back apart on-device by a jitted unpack
    (the flat buffer is donated, so its HBM is reused). Per-leaf puts
    pay a per-call dispatch cost for each of the 446 leaves of a 124M
    model, where the packed path pays one bulk transfer per dtype;
    this is what makes restore-from-memory fast after an
    elastic restart (reference contract: engine.py:315 restores in
    seconds, not minutes).
    """
    import jax

    leaves, treedef = jax.tree_util.tree_flatten_with_path(target)
    out: List[Any] = [None] * len(leaves)
    # (device, dtype) -> list of (leaf_pos, shard_shape, np_block)
    plan: Dict[Tuple[Any, str], List[Tuple[int, Tuple[int, ...], Any]]] = {}
    leaf_meta: Dict[int, Tuple[Tuple[int, ...], Any]] = {}
    for i, (kp, leaf) in enumerate(leaves):
        path = _keystr(kp)
        recs = read_records(path)
        shards = target_shards(leaf)
        if shards is None:
            np_leaf = np.asarray(leaf)
            want = tuple((0, d) for d in np_leaf.shape)
            block = assemble_leaf(
                tuple(np_leaf.shape), str(np_leaf.dtype), want, recs
            )
            # copy: assemble_leaf's exact-match fast path returns the
            # record's buffer, which under load_records(copy=False) is a
            # live view into shm — it must not outlive the shard lock.
            # (preserve python scalar-ness for 0-d leaves)
            out[i] = block[()] if block.ndim == 0 else np.array(block)
            continue
        gshape = tuple(leaf.shape)
        dt = str(leaf.dtype)
        leaf_meta[i] = (gshape, leaf.sharding)
        for device, want in shards:
            block = assemble_leaf(gshape, dt, want, recs)
            shape = tuple(hi - lo for lo, hi in want)
            plan.setdefault((device, dt), []).append((i, shape, block))

    # phase 1: start every bulk H2D (device_put is async — transfers to
    # distinct devices overlap). Flats are capped at ~512 MB: transfer
    # throughput on some runtimes degrades past that, and smaller flats
    # bound the transient host allocation.
    flat_cap = 512 << 20
    staged = []
    for (device, dt), items in plan.items():
        npdt = np.dtype(dt)
        bins: List[List[Tuple[int, Tuple[int, ...], Any]]] = [[]]
        bin_bytes = [0]
        for item in items:
            _, shape, _ = item
            n = int(np.prod(shape)) if shape else 1
            nbytes = n * npdt.itemsize
            if bins[-1] and bin_bytes[-1] + nbytes > flat_cap:
                bins.append([])
                bin_bytes.append(0)
            bins[-1].append(item)
            bin_bytes[-1] += nbytes
        for bin_items in bins:
            if not bin_items:
                continue
            sizes = [
                int(np.prod(shape)) if shape else 1
                for _, shape, _ in bin_items
            ]
            flat = np.empty((sum(sizes),), npdt)
            layout = []
            off = 0
            for (_, shape, block), n in zip(bin_items, sizes):
                flat[off : off + n] = np.ascontiguousarray(
                    block
                ).reshape(-1)
                layout.append((off, n, shape))
                off += n
            dflat = jax.device_put(flat, device)
            staged.append((bin_items, dflat, tuple(layout)))

    # phase 2: on-device unpack, then stitch global arrays
    singles: Dict[int, List[Any]] = {}
    for items, dflat, layout in staged:
        unpack = _get_unpack_jit(donate=dflat.nbytes >= (64 << 20))
        pieces = unpack(dflat, layout)
        for (i, _, _), piece in zip(items, pieces):
            singles.setdefault(i, []).append(piece)
    for i, (gshape, sharding) in leaf_meta.items():
        out[i] = jax.make_array_from_single_device_arrays(
            gshape, sharding, singles[i]
        )
    return jax.tree_util.tree_unflatten(treedef, out)
