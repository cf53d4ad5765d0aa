"""Device-resident hot tier: HBM-pinned embedding rows with Pallas
gather/scatter, over any host-side KvEmbedding store.

Parity target: TFPlus ``KvVariable`` serves recommender gathers from
wherever the row lives; this repo's port kept every row host-side, so
``SparseTrainer`` paid a synchronous host gather → device step → host
scatter cycle every step. Zipfian access means a small hot set absorbs
almost all traffic: this module pins that hot set in HBM and serves it
with Pallas kernels, leaving the host store (``ShardedKvEmbedding`` /
``TieredKvEmbedding`` / ``NativeTieredKvEmbedding``) as the warm tier
of a three-tier hierarchy::

    HBM hot tier (this module)  --spill/fault-->  host C++ store
    host C++ store              --evict/fault-->  disk cold tier

Design:

- The tier is ONE device table ``[capacity, row_floats]`` (values +
  optimizer slots — update state travels with the row, the same fused
  layout the C++ store uses). ``capacity`` comes from an HBM byte
  budget, the knob that bounds the tier (docs/sparse-embeddings.md).
- Gather/scatter are Pallas kernels over **sorted unique ids**: the
  id→slot map lives host-side (cheap numpy hash ops on deduped ids),
  the kernels move one row per grid step via scalar-prefetched slot
  indices (``PrefetchScalarGridSpec``) — compiled on TPU, and run
  under the Pallas interpreter on CPU via
  ``jax_compat.pallas_interpret_mode`` so tier-1 runs everywhere.
  ``DLROVER_TPU_EMB_KERNEL=jnp`` selects a pure ``jnp.take``/``.at[]``
  path (the default off the TPU; a kernel that fails raises).
- Missing rows FAULT IN from the host store (full rows incl. slots via
  ``export_rows`` — a state read, no freq/ts bump); LRU victims spill
  back with an **async D2H**: the evicted rows are handed to a drain
  thread as device arrays with ``copy_to_host_async`` already issued,
  so the step never blocks on the host link. Both directions are
  priced through the PR-6 ``LinkModel`` host leg
  (``topology.price_host_transfer``).
- The sparse optimizer update runs ON DEVICE (adagrad / momentum /
  adam over the gathered rows, duplicate ids segment-summed), then a
  Pallas scatter writes the new rows back into the table in place
  (``input_output_aliases`` — no table-sized copy per step).

Coherency contract: while a row is device-resident its device copy is
authoritative and the host copy is stale; ``flush()`` (checkpoint
cadence) and spills write it back. ``export_state`` flushes first so a
checkpoint can never lose device-only training.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from dlrover_tpu.common.jax_compat import pallas_interpret_mode
from dlrover_tpu.common.log import default_logger as logger

_DEF_HBM_BUDGET = 64 << 20  # 64 MiB of rows unless the caller budgets


def _bucket(n: int, floor: int = 64) -> int:
    """Next power of two ≥ n (≥ floor): the shape buckets that keep
    kernel/jit compiles amortized across variable unique-id counts."""
    b = floor
    while b < n:
        b <<= 1
    return b


# -- kernels -----------------------------------------------------------------


class _Kernels:
    """Pallas gather/scatter over a ``[capacity, row_floats]`` table,
    one row per grid step, slots scalar-prefetched so the index map can
    address HBM before the body runs. A kernel that fails to build or
    run raises: the jnp take/at path is a mode, never a silent drop.

    Mode resolution (``DLROVER_TPU_EMB_KERNEL`` overrides): ``auto``
    compiles the Pallas kernels on TPU and uses the jnp path on CPU —
    the interpreter executes the grid one id at a time in Python
    (seconds per 4k-id batch), correct but only useful as a numerics
    check, which is exactly what ``pallas`` forces in the tests."""

    def __init__(self, mode: Optional[str] = None):
        import os

        mode = mode or os.getenv("DLROVER_TPU_EMB_KERNEL", "auto")
        if mode == "auto":
            mode = "jnp" if pallas_interpret_mode() else "pallas"
        self.mode = mode
        self._gather_calls: Dict[Tuple[int, int, int], Any] = {}
        self._scatter_calls: Dict[Tuple[int, int, int], Any] = {}

    # jnp fallback path (also the reference the tests check against):
    # jitted per shape bucket, with the table DONATED to the scatter so
    # the update happens in place — the jnp twin of the pallas kernel's
    # input_output_aliases (an eager .at[].set would copy the whole
    # table every step)
    def _gather_jnp(self, table, slots):
        import jax
        import jax.numpy as jnp

        key = ("gj", len(slots)) + table.shape
        fn = self._gather_calls.get(key)
        if fn is None:
            fn = jax.jit(lambda t, s: jnp.take(t, s, axis=0))
            self._gather_calls[key] = fn
        return fn(table, jnp.asarray(slots, jnp.int32))

    def _scatter_jnp(self, table, slots, rows):
        import jax
        import jax.numpy as jnp

        key = ("sj", len(slots)) + table.shape
        fn = self._scatter_calls.get(key)
        if fn is None:
            fn = jax.jit(
                lambda t, s, r: t.at[s].set(r), donate_argnums=(0,)
            )
            self._scatter_calls[key] = fn
        return fn(table, jnp.asarray(slots, jnp.int32), rows)

    # The kernels see the table as ``[capacity, 1, row_floats]``: the
    # TPU lowering wants the last two dims of a block to be multiples of
    # (8, 128) or the array's own, and a one-row block of the 2-D table
    # is neither (Mosaic refuses it). Each call is jitted so the reshape
    # is part of the program, not an eager copy of the table.
    def _build_gather(self, n: int, capacity: int, row_floats: int):
        import jax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kernel(_slots_ref, table_ref, out_ref):
            out_ref[...] = table_ref[...]

        row = (1, 1, row_floats)
        gs = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[pl.BlockSpec(row, lambda i, s: (s[i], 0, 0))],
            out_specs=pl.BlockSpec(row, lambda i, s: (i, 0, 0)),
        )
        call = pl.pallas_call(
            kernel,
            grid_spec=gs,
            out_shape=jax.ShapeDtypeStruct((n,) + row[1:], np.float32),
            interpret=pallas_interpret_mode(),
        )
        return jax.jit(
            lambda slots, table: call(
                slots, table.reshape((capacity,) + row[1:])
            ).reshape(n, row_floats)
        )

    def _build_scatter(self, n: int, capacity: int, row_floats: int):
        import jax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kernel(_slots_ref, rows_ref, _table_ref, out_ref):
            out_ref[...] = rows_ref[...]

        row = (1, 1, row_floats)
        gs = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[
                pl.BlockSpec(row, lambda i, s: (i, 0, 0)),
                pl.BlockSpec(row, lambda i, s: (s[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec(row, lambda i, s: (s[i], 0, 0)),
        )
        # the table (input 2, counting the scalar-prefetch arg) aliases
        # the output and is donated: untouched rows persist, addressed
        # rows are overwritten in place — no table-sized copy per step
        call = pl.pallas_call(
            kernel,
            grid_spec=gs,
            out_shape=jax.ShapeDtypeStruct(
                (capacity,) + row[1:], np.float32
            ),
            input_output_aliases={2: 0},
            interpret=pallas_interpret_mode(),
        )
        return jax.jit(
            lambda slots, rows, table: call(
                slots,
                rows.reshape((n,) + row[1:]),
                table.reshape((capacity,) + row[1:]),
            ).reshape(capacity, row_floats),
            donate_argnums=(2,),
        )

    def gather(self, table, slots_np: np.ndarray):
        """rows[i] = table[slots[i]] — slots are sorted unique device
        slot ids (host side guarantees uniqueness/sortedness)."""
        import jax.numpy as jnp

        if self.mode == "jnp":
            return self._gather_jnp(table, jnp.asarray(slots_np))
        key = (len(slots_np),) + table.shape
        call = self._gather_calls.get(key)
        if call is None:
            call = self._build_gather(
                len(slots_np), table.shape[0], table.shape[1]
            )
            self._gather_calls[key] = call
        return call(jnp.asarray(slots_np, jnp.int32), table)

    def scatter(self, table, slots_np: np.ndarray, rows):
        """table[slots[i]] = rows[i], in place (aliased); returns the
        new table array. Slots MUST be unique (duplicate writes would
        race in the grid) — the callers pass deduped ids only."""
        import jax.numpy as jnp

        if self.mode == "jnp":
            return self._scatter_jnp(table, jnp.asarray(slots_np), rows)
        key = (len(slots_np),) + table.shape
        call = self._scatter_calls.get(key)
        if call is None:
            call = self._build_scatter(
                len(slots_np), table.shape[0], table.shape[1]
            )
            self._scatter_calls[key] = call
        return call(jnp.asarray(slots_np, jnp.int32), rows, table)


# -- stats -------------------------------------------------------------------


@dataclass
class EmbeddingTierStats:
    """Per-table hot-tier telemetry; ``export_metrics`` publishes it as
    ``dlrover_embedding_*`` gauges (docs/observability.md) and the
    trainer forwards the same scalars to the master / Brain
    ``job_metrics`` through its train-metrics report."""

    gathers: int = 0
    unique_ids: int = 0
    hits: int = 0  # unique ids already device-resident
    faults: int = 0  # unique ids faulted in from the host tier
    fault_bytes: int = 0  # H2D row traffic
    spill_rows: int = 0
    spill_bytes: int = 0  # D2H row traffic
    scatter_lag_s: float = 0.0  # enqueue→host-import latency (sum)
    scatter_drains: int = 0
    host_leg_s: float = 0.0  # LinkModel-priced host-link seconds

    @property
    def hit_pct(self) -> float:
        total = self.hits + self.faults
        return 100.0 * self.hits / total if total else 0.0

    @property
    def scatter_lag_ms(self) -> float:
        if not self.scatter_drains:
            return 0.0
        return 1e3 * self.scatter_lag_s / self.scatter_drains

    def as_dict(self) -> Dict[str, float]:
        return {
            "emb_gather_hit_pct": round(self.hit_pct, 3),
            "emb_faults": float(self.faults),
            "emb_fault_bytes": float(self.fault_bytes),
            "emb_spill_rows": float(self.spill_rows),
            "emb_spill_bytes": float(self.spill_bytes),
            "emb_scatter_lag_ms": round(self.scatter_lag_ms, 3),
            "emb_host_leg_ms": round(1e3 * self.host_leg_s, 3),
        }


# -- hot tier ----------------------------------------------------------------


class DeviceHotTier:
    """The HBM row cache: device table + host-side id→slot map + LRU.

    Not thread-safe by itself — :class:`DeviceSparseEmbedding` owns the
    lock that serializes table mutations (the pipeline's fault-in
    thread vs the train thread's grad scatter)."""

    def __init__(
        self,
        dim: int,
        num_slots: int = 1,
        hbm_budget_bytes: int = _DEF_HBM_BUDGET,
        capacity: Optional[int] = None,
        kernels: Optional[_Kernels] = None,
    ):
        import jax.numpy as jnp

        self.dim = dim
        self.num_slots = num_slots
        self.row_floats = dim * (1 + num_slots)
        row_bytes = self.row_floats * 4
        self.capacity = int(
            capacity
            if capacity is not None
            else max(64, hbm_budget_bytes // row_bytes)
        )
        self.hbm_bytes = self.capacity * row_bytes
        # one extra SCRATCH row at index ``capacity``: batches pad
        # their unique-id slot lists up to a power-of-two bucket with
        # it, so every kernel/jit shape is reused instead of
        # recompiling per step (unique counts vary batch to batch).
        # Padding entries carry zero gradients, so the scratch row's
        # update is the identity and concurrent identical writes to it
        # are benign.
        self.scratch_slot = self.capacity
        self.table = jnp.zeros(
            (self.capacity + 1, self.row_floats), jnp.float32
        )
        self._kernels = kernels or _Kernels()
        self._slot_of: Dict[int, int] = {}
        # bookkeeping arrays include the scratch slot so padded slot
        # lists can index them; the scratch entry never binds an id, so
        # occupancy/dirty scans (keyed on _id_of >= 0) exclude it
        self._id_of = np.full(self.capacity + 1, -1, np.int64)
        self._dirty = np.zeros(self.capacity + 1, bool)
        self._last_used = np.zeros(self.capacity + 1, np.int64)
        # pin refcounts: slots referenced by an outstanding
        # PreparedBatch must not be LRU victims — the pipeline thread's
        # fault-in for step N+1 would otherwise evict rows step N is
        # about to update, silently reusing the slot for another id
        self._pins = np.zeros(self.capacity + 1, np.int32)
        self._free: List[int] = list(range(self.capacity - 1, -1, -1))
        self._tick = 0

    def __len__(self) -> int:
        return len(self._slot_of)

    @property
    def kernel_mode(self) -> str:
        return self._kernels.mode

    def lookup(self, unique_ids: np.ndarray) -> np.ndarray:
        """slots for ``unique_ids`` (-1 = not resident). Read-only."""
        slots = np.empty(len(unique_ids), np.int64)
        get = self._slot_of.get
        for i, k in enumerate(unique_ids):
            slots[i] = get(int(k), -1)
        return slots

    def touch(self, slots: np.ndarray):
        self._tick += 1
        self._last_used[slots] = self._tick

    def pin(self, slots: np.ndarray):
        self._pins[slots] += 1

    def unpin(self, slots: np.ndarray):
        self._pins[slots] = np.maximum(self._pins[slots] - 1, 0)

    def recency_snapshot(self) -> Dict[str, Any]:
        """Copy of the residency/LRU/pin bookkeeping. The serving-path
        guarantee is stated against this: a read-only probe
        (``gather(insert_missing=False)``) must leave two snapshots
        bit-identical — no admissions, no recency touches, no pin
        drift — so serving traffic can never evict or age what
        training needs resident."""
        return {
            "tick": self._tick,
            "resident": dict(self._slot_of),
            "last_used": self._last_used.copy(),
            "pins": self._pins.copy(),
        }

    def _allocate(
        self, n: int
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """n free slots, evicting coldest UNPINNED residents if needed.
        Returns (slots, victim_slots, victim_ids) — victim ids are
        captured BEFORE the unbind, and the victims' rows must be read
        out by the caller before anything scatters over them."""
        n_free = len(self._free)
        victims = np.empty(0, np.int64)
        victim_ids = np.empty(0, np.int64)
        if n > n_free:
            need = n - n_free
            occupied = np.nonzero(
                (self._id_of >= 0) & (self._pins == 0)
            )[0]
            order = np.argsort(self._last_used[occupied], kind="stable")
            victims = occupied[order[:need]]
            if len(victims) < need:
                raise ValueError(
                    f"hot tier capacity {self.capacity} cannot hold "
                    f"{n} new rows ({int((self._pins > 0).sum())} "
                    f"pinned by in-flight steps) — raise the HBM "
                    f"budget or lower the pipeline depth"
                )
            victim_ids = self._id_of[victims].copy()
            for s in victims:
                del self._slot_of[int(self._id_of[s])]
                self._id_of[s] = -1
                self._free.append(int(s))
        slots = np.array(
            [self._free.pop() for _ in range(n)], np.int64
        )
        return slots, victims, victim_ids

    def gather_rows(self, slots: np.ndarray):
        """Full rows (values + slots) at device ``slots``. Exact
        power-of-two slot lists (the PreparedBatch hot path) return a
        device array straight from the kernel; ragged lists (spill /
        flush) are padded to a bucket against the scratch slot and
        materialized to a host numpy slice — slicing a device array at
        a per-call-unique length would trigger an XLA compile per
        shape, and these callers want host bytes anyway."""
        n = len(slots)
        padded_len = _bucket(n)
        s = np.asarray(slots, np.int32)
        if padded_len != n:
            p = np.full(padded_len, self.scratch_slot, np.int32)
            p[:n] = s
            return np.asarray(self._kernels.gather(self.table, p))[:n]
        return self._kernels.gather(self.table, s)

    def scatter_rows(self, slots: np.ndarray, rows, dirty: bool = True):
        """Overwrite rows at unique device ``slots`` in place (padding
        writes land on the scratch row, whose content is immaterial).
        Ragged numpy inputs are padded HOST-side so the device only
        ever sees bucket shapes — no per-step eager-op compiles."""
        import jax.numpy as jnp

        n = len(slots)
        padded_len = _bucket(n)
        s = np.asarray(slots, np.int32)
        if padded_len != n:
            p = np.full(padded_len, self.scratch_slot, np.int32)
            p[:n] = s
            np_rows = np.asarray(rows, np.float32).reshape(
                n, self.row_floats
            )
            padded = np.zeros(
                (padded_len, self.row_floats), np.float32
            )
            padded[:n] = np_rows
            rows = padded
            s = p
        self.table = self._kernels.scatter(
            self.table, s, jnp.asarray(rows)
        )
        if dirty:
            self._dirty[slots] = True

    def bind(self, ids: np.ndarray, slots: np.ndarray):
        for k, s in zip(ids, slots):
            self._slot_of[int(k)] = int(s)
            self._id_of[s] = k
        self.touch(slots)

    def dirty_slots(self) -> np.ndarray:
        # padded scatters may mark the scratch slot dirty; only bound
        # slots carry rows that need a write-back
        return np.nonzero(self._dirty & (self._id_of >= 0))[0]

    def clear_dirty(self, slots: np.ndarray):
        self._dirty[slots] = False

    def drop(self, slots: np.ndarray):
        """Unbind slots (rows must already be safe host-side)."""
        for s in slots:
            k = int(self._id_of[s])
            if k >= 0:
                del self._slot_of[k]
            self._id_of[s] = -1
            self._dirty[s] = False
            self._pins[s] = 0
            self._free.append(int(s))


# -- prepared step -----------------------------------------------------------


@dataclass
class PreparedBatch:
    """Everything the train step needs for one batch of ids, built by
    ``prepare`` (possibly on the pipeline thread one step ahead):
    sorted unique ids, their device slots, and the inverse map back to
    the per-occurrence order."""

    ids: np.ndarray
    unique_ids: np.ndarray
    inverse: np.ndarray
    slots: np.ndarray  # padded to a power-of-two bucket (scratch slot)
    n_unique: int = 0  # real entries in ``slots`` before padding
    generation: int = 0
    released: bool = False  # pins returned (apply_grads or release)


# -- the three-tier facade ---------------------------------------------------


class DeviceSparseEmbedding:
    """HBM hot tier over a host KvEmbedding store, with the sparse
    optimizer running on device.

    The train cycle becomes::

        prep = emb.prepare(ids)          # pipeline thread, step N+1
        rows = emb.gather_for(prep)      # device gather, step N
        ... dense step produces row_grads ...
        emb.apply_grads(prep, row_grads) # on-device update + scatter

    ``sparse_optimizer`` ∈ {adagrad, momentum, adam} — the on-device
    subset of the host store's fused family (rows carry the same
    [value | slot…] layout, so a row can move tiers mid-training and
    keep its optimizer state).
    """

    SUPPORTED_OPTS = ("adagrad", "momentum", "adam")

    def __init__(
        self,
        host,
        hbm_budget_bytes: int = _DEF_HBM_BUDGET,
        capacity: Optional[int] = None,
        sparse_optimizer: str = "adagrad",
        lr: float = 0.05,
        eps: float = 1e-8,
        momentum: float = 0.9,
        beta1: float = 0.9,
        beta2: float = 0.999,
        table_name: str = "t0",
        kernel_mode: Optional[str] = None,
        async_spill: bool = True,
        spill_stripe_min_bytes: Optional[int] = None,
    ):
        if sparse_optimizer not in self.SUPPORTED_OPTS:
            raise ValueError(
                f"device tier supports {self.SUPPORTED_OPTS}, got "
                f"{sparse_optimizer!r} (use the host-path SparseTrainer "
                f"cycle for the full fused family)"
            )
        need_slots = {"adagrad": 1, "momentum": 1, "adam": 2}[
            sparse_optimizer
        ]
        if host.num_slots < need_slots:
            raise ValueError(
                f"{sparse_optimizer} needs num_slots >= {need_slots}"
            )
        self.host = host
        self.table_name = table_name
        self.hot = DeviceHotTier(
            host.dim,
            host.num_slots,
            hbm_budget_bytes=hbm_budget_bytes,
            capacity=capacity,
            kernels=_Kernels(kernel_mode),
        )
        self._opt = sparse_optimizer
        self._lr = float(lr)
        self._eps = float(eps)
        self._momentum = float(momentum)
        self._beta1 = float(beta1)
        self._beta2 = float(beta2)
        self.stats = EmbeddingTierStats()
        # host-link arbitration (parallel/transfer_sched.py): the
        # fault-in H2D leg and the spill D2H leg register as streams so
        # they interleave with checkpoint staging by priority instead
        # of queueing blindly. Grants wrap whole transfers — ordering
        # changes, contents never do. Acquired OUTSIDE self._lock
        # always (the arbiter is a leaf lock).
        from dlrover_tpu.parallel import transfer_sched

        self._fault_stream = transfer_sched.get_arbiter().register(
            f"emb_fault:{table_name}",
            transfer_sched.Priority.BACKPRESSURE,
            direction="h2d",
        )
        self._spill_stream = transfer_sched.get_arbiter().register(
            f"emb_spill:{table_name}",
            transfer_sched.Priority.BACKGROUND,
            direction="d2h",
        )
        # multi-rail spill striping: a spill whose staging D2H is at
        # least this large splits its row ranges across every admitted
        # rail (the striper's per-range grants replace the single
        # stream grant). Only the device→host copy stripes — the host
        # import stays single-threaded (ShardedKvEmbedding.import_rows
        # is not thread-safe).
        self._spill_stripe_min_bytes = (
            transfer_sched.DEFAULT_STRIPE_MIN_BYTES
            if spill_stripe_min_bytes is None
            else max(int(spill_stripe_min_bytes), 1)
        )
        self._spill_striper = transfer_sched.StripedTransfer(
            self._spill_stream.arbiter,
            name=f"emb_spill:{table_name}",
            direction="d2h",
            priority=transfer_sched.Priority.BACKGROUND,
            ignore_window=True,
        )
        # one lock serializes every table mutation: the pipeline
        # thread's fault-in scatter vs the train thread's grad scatter
        # (jax arrays are immutable — the hazard is lost updates via
        # interleaved read-modify-swap, not torn reads)
        self._lock = threading.RLock()
        self._gen = 0
        self._update_fns: Dict[Tuple[int, int], Any] = {}
        # async spill drain: victims leave _allocate as device arrays
        # with copy_to_host_async issued; this thread materializes and
        # imports them so the step never blocks on the D2H
        self._spill_q: "queue.Queue" = queue.Queue()
        self._spill_err: Optional[BaseException] = None
        # spill lifetime tracking (both under self._lock): ids whose
        # dirty rows are queued/in-flight to the host — a fault-in for
        # one of them must wait, or it would read the PRE-spill host
        # value and silently lose the victim's training; and an
        # explicit in-flight count, because Queue.empty() flips False
        # the moment the drain DEQUEUES an item, not when its import
        # lands — join_spills on empty() could let a checkpoint export
        # race the last import
        self._pending_spill_ids: set = set()
        self._spills_inflight = 0
        self._async_spill = async_spill
        self._spill_thread: Optional[threading.Thread] = None
        if async_spill:
            self._spill_thread = threading.Thread(
                target=self._drain_spills,
                daemon=True,
                name=f"emb-spill-{table_name}",
            )
            self._spill_thread.start()

    # -- spill drain ---------------------------------------------------
    def _drain_spills(self):
        while True:
            item = self._spill_q.get()
            if item is None:
                return
            try:
                self._import_spill(*item)
            except BaseException as e:  # surfaced on next flush()
                self._spill_err = e
                logger.error(f"embedding spill drain failed: {e!r}")
                with self._lock:
                    self._spills_inflight -= 1
                    self._pending_spill_ids.difference_update(
                        int(k) for k in item[1]
                    )

    def _import_spill(
        self, t_enq: float, ids, dev_rows, n: int, arbitrate: bool = True
    ):
        from contextlib import nullcontext

        from dlrover_tpu.parallel import transfer_sched

        # link-grant ordering is ALWAYS link → emb/host locks: the
        # drain thread holds no lock here, so it arbitrates; the
        # synchronous (async_spill=False) path runs INLINE under
        # self._lock from _allocate and must NOT wait on the link — a
        # grant-holding fault-in briefly takes self._lock inside
        # _host_rows, and emb→link here would be the ABBA half of a
        # deadlock
        prio = transfer_sched.Priority.BACKGROUND
        if arbitrate:
            # backlog escalates priority: a deep spill queue is about
            # to stall _allocate (the step path), so it outranks
            # background checkpoint staging
            if self._spill_q.qsize() >= 2:
                prio = transfer_sched.Priority.BACKPRESSURE
        nbytes = n * self.host.dim * 4
        stripes = (
            arbitrate
            and nbytes >= self._spill_stripe_min_bytes
            and len(self._spill_striper.rails()) >= 2
        )
        if stripes:
            # stripe ONLY the D2H staging: per-rail workers land row
            # ranges into a preallocated host buffer (disjoint slices,
            # so concurrent writes never overlap) under the striper's
            # per-range grants — no outer stream grant, or the striper
            # would deadlock against its own stream's held rail. The
            # host import below runs single-threaded after the join.
            # row width comes from the device gather (dim plus the
            # optimizer slot columns), not host.dim
            rows = np.empty(
                (n,) + tuple(dev_rows.shape[1:]),
                np.dtype(dev_rows.dtype),
            )
            rowb = max(1, rows.nbytes // max(n, 1))
            step = max(1, self._spill_striper.chunk_bytes // rowb)
            ranges = []
            lo = 0
            while lo < n:
                hi = min(lo + step, n)
                ranges.append(((lo, hi), (hi - lo) * rowb))
                lo = hi

            def _stage(rail, rng):
                rlo, rhi = rng
                rows[rlo:rhi] = np.asarray(dev_rows[rlo:rhi])

            self._spill_striper.run_items(
                ranges, _stage, priority=prio
            )
            self.host.import_rows(ids, rows)
        else:
            grant = (
                self._spill_stream.transfer(nbytes, priority=prio)
                if arbitrate
                else nullcontext()
            )
            # lands the (already async) D2H; the device array is
            # bucket-padded, the tail rows are scratch filler
            with grant:
                rows = np.asarray(dev_rows)[:n]
                self.host.import_rows(ids, rows)
        self.stats.spill_rows += len(ids)
        self.stats.spill_bytes += rows.nbytes
        self.stats.scatter_lag_s += time.perf_counter() - t_enq
        self.stats.scatter_drains += 1
        self.stats.host_leg_s += self._price(rows.nbytes, h2d=False)
        with self._lock:
            self._spills_inflight -= 1
            self._pending_spill_ids.difference_update(
                int(k) for k in ids
            )

    @staticmethod
    def _price(nbytes: int, h2d: bool) -> float:
        try:
            from dlrover_tpu.parallel.topology import price_host_transfer

            return price_host_transfer(nbytes, h2d=h2d)
        except Exception:
            return 0.0

    def _spill(
        self,
        victim_slots: np.ndarray,
        victim_ids: Optional[np.ndarray] = None,
    ):
        """Read victims' rows and hand them to the drain (async D2H).
        ``victim_ids`` must be passed when the caller already unbound
        the slots (the ``_allocate`` path clears ``_id_of`` first)."""
        if len(victim_slots) == 0:
            return
        ids = (
            victim_ids
            if victim_ids is not None
            else self.hot._id_of[victim_slots].copy()
        )
        # only dirty victims need the write-back; clean ones are
        # byte-identical host-side already
        dirty = self.hot._dirty[victim_slots]
        if dirty.any():
            d_slots = victim_slots[dirty]
            # bucket-padded DEVICE gather (not gather_rows, whose
            # ragged path materializes to host synchronously): the
            # array stays on device with its D2H dispatched async, and
            # the drain thread slices the real rows off once it lands
            n = len(d_slots)
            padded = np.full(
                _bucket(n), self.hot.scratch_slot, np.int32
            )
            padded[:n] = d_slots
            dev_rows = self.hot._kernels.gather(
                self.hot.table, padded
            )
            try:
                dev_rows.copy_to_host_async()
            except Exception:
                pass
            item = (time.perf_counter(), ids[dirty], dev_rows, n)
            # bookkeeping BEFORE dispatch (callers hold self._lock):
            # _import_spill decrements/clears on completion either way
            self._spills_inflight += 1
            self._pending_spill_ids.update(int(k) for k in ids[dirty])
            if self._async_spill:
                self._spill_q.put(item)
            else:
                # inline under self._lock: no link arbitration (see
                # _import_spill's ordering note)
                self._import_spill(*item, arbitrate=False)
        self.hot.clear_dirty(victim_slots)

    # -- prepare / gather / update -------------------------------------
    def prepare(self, ids) -> PreparedBatch:
        """Dedup ``ids`` (sorted unique) and make every unique id
        device-resident, faulting missing rows in from the host tier.
        Safe to call from the pipeline thread one step ahead of the
        compute that will consume it."""
        ids = np.ascontiguousarray(ids, np.int64).ravel()
        unique, inverse = np.unique(ids, return_inverse=True)
        while True:
            with self._lock:
                gen0 = self._gen
                slots = self.hot.lookup(unique)
                missing_mask = slots < 0
                missing = unique[missing_mask]
                self.stats.gathers += 1
                self.stats.unique_ids += len(unique)
                self.stats.hits += int((~missing_mask).sum())
            if not len(missing):
                with self._lock:
                    if self._gen != gen0:
                        continue  # resident set changed under us
                    self.hot.touch(slots)
                    self.hot.pin(slots)
                    gen = gen0
                break
            # host legs OUTSIDE the lock: the C++ gather/export and the
            # H2D dispatch are the slow part and must overlap the train
            # thread's compute, not serialize against its scatter.
            # Rows stay numpy until the (bucket-padded) scatter so no
            # ragged-shape eager op ever reaches the device. The link
            # grant (BACKPRESSURE: a consumer may be waiting on this
            # prep) orders the leg against spills/staging.
            if self._spills_racing(missing):
                # one of these ids was just evicted and its spill has
                # not landed host-side: reading now would fault the
                # PRE-spill value back in and lose the victim's
                # training. Join BEFORE taking the link grant — the
                # drain needs the link to land its import, and joining
                # while HOLDING the grant deadlocks against it (the
                # arbiter's forced-grant backstop outlasts the join
                # timeout; graftlint lock-discipline.grant, found as a
                # flaky 30 s wedge in the spill-lifetime test)
                self.join_spills()
            with self._fault_stream.transfer(
                len(missing) * self.host.dim * 4
            ):
                racing = self._spills_racing(missing)
                rows_np = None if racing else self._host_rows(missing)
            if racing:
                # re-armed between the join and the export (a
                # concurrent prepare faulted one of these ids in and
                # evicted it again): the grant is released now, so
                # join and retry from the top
                self.join_spills()
                continue
            with self._lock:
                if self._gen != gen0:
                    # an import_state/evict resharded the world while
                    # the rows were in flight: binding them now would
                    # install PRE-restore values under the new
                    # generation and defeat the staleness check —
                    # discard and re-read the (new) host state
                    continue
                # re-check residency: a concurrent prepare may have
                # faulted some of these in meanwhile
                cur = self.hot.lookup(missing)
                still = cur < 0
                if still.any():
                    new_ids = missing[still]
                    new_slots, victims, victim_ids = self.hot._allocate(
                        int(still.sum())
                    )
                    self._spill(victims, victim_ids)
                    self.hot.scatter_rows(
                        new_slots, rows_np[still], dirty=False
                    )
                    self.hot.bind(new_ids, new_slots)
                self.stats.faults += len(missing)
                self.stats.fault_bytes += rows_np.nbytes
                self.stats.host_leg_s += self._price(
                    rows_np.nbytes, h2d=True
                )
                slots = self.hot.lookup(unique)
                self.hot.touch(slots)
                self.hot.pin(slots)
                gen = gen0
            break
        # pad the slot list to a power-of-two bucket with the scratch
        # slot: kernel/jit shapes recur across steps instead of
        # recompiling for every distinct unique-id count
        padded_len = _bucket(len(unique))
        padded = np.full(padded_len, self.hot.scratch_slot, np.int64)
        padded[: len(unique)] = slots
        return PreparedBatch(
            ids=ids,
            unique_ids=unique,
            inverse=inverse.astype(np.int32),
            slots=padded,
            n_unique=len(unique),
            generation=gen,
        )

    def _spills_racing(self, ids: np.ndarray) -> bool:
        """True if any of ``ids`` has an in-flight spill whose import
        has not landed host-side yet (reading it now would return the
        pre-spill value)."""
        with self._lock:
            return bool(
                self._pending_spill_ids.intersection(
                    int(k) for k in ids
                )
            )

    def _host_rows(self, missing: np.ndarray) -> np.ndarray:
        """Full rows for ``missing`` from the host tier; keys the host
        has never seen are created there first (deterministic C++ init)
        so both tiers agree on the row's birth value. Callers must have
        joined any racing spill of these ids FIRST — and before taking
        the link grant: the drain needs the link to land its import,
        so a grant-holding join deadlocks (prepare does this)."""
        rows, _f, _t, present = self.host.export_rows(missing)
        absent = missing[~present]
        if len(absent):
            # gather(insert_missing=True) creates + inits; rows (incl.
            # zero slots) then export with the authoritative values.
            # TieredKvEmbedding.gather also faults disk-cold rows hot
            # first, so all three tiers compose here.
            self.host.gather(absent, insert_missing=True)
            rows2, _f2, _t2, present2 = self.host.export_rows(missing)
            rows[~present] = rows2[~present]
        return rows

    def _check_gen(self, prep: PreparedBatch):
        if prep.generation != self._gen:
            raise RuntimeError(
                "PreparedBatch is stale: the embedding was flushed/"
                "resharded after prepare() — re-prepare this batch"
            )

    def gather_for(self, prep: PreparedBatch):
        """Values for every occurrence in ``prep.ids`` as a device
        array ``[len(ids), dim]`` (what the dense step consumes)."""
        with self._lock:
            self._check_gen(prep)
            rows = self.hot.gather_rows(prep.slots)
        return self._project_fn(len(prep.slots), len(prep.inverse))(
            rows, prep.inverse
        )

    def _project_fn(self, n_padded: int, n_ids: int):
        """Jitted (padded rows, inverse) -> per-occurrence values."""
        key = ("proj", n_padded, n_ids)
        fn = self._update_fns.get(key)
        if fn is None:
            import jax
            import jax.numpy as jnp

            dim = self.host.dim

            def project(rows, inverse):
                return jnp.take(rows[:, :dim], inverse, axis=0)

            fn = jax.jit(project)
            self._update_fns[key] = fn
        return fn

    def gather(self, ids, insert_missing: bool = True):
        """One-call gather (prepare inline): host-store-compatible
        surface for code that does not pipeline.

        ``insert_missing=False`` is the read-only probe the host
        stores honor, so it must not create keys OR promote rows into
        the device tier: resident rows read from HBM, the rest read
        through the host path (which faults disk-cold rows but never
        invents keys), absent keys read zeros."""
        if insert_missing:
            prep = self.prepare(ids)
            try:
                return self.gather_for(prep)
            finally:
                self.release(prep)
        import jax.numpy as jnp

        ids = np.ascontiguousarray(ids, np.int64).ravel()
        unique, inverse = np.unique(ids, return_inverse=True)
        dim = self.host.dim
        vals = np.zeros((len(unique), dim), np.float32)
        with self._lock:
            slots = self.hot.lookup(unique)
            resident = slots >= 0
            if resident.any():
                rows = np.asarray(
                    self.hot.gather_rows(slots[resident])
                )
                vals[resident] = rows[:, :dim]
        missing = unique[~resident]
        if len(missing):
            if self._spills_racing(missing):
                self.join_spills()
            vals[~resident] = self.host.gather(
                missing, insert_missing=False
            )
        return jnp.asarray(vals[inverse])

    def release(self, prep: PreparedBatch):
        """Return the pins a ``prepare`` took. ``apply_grads`` does
        this implicitly; gather-only consumers (eval) call it once the
        step no longer needs the rows resident. Idempotent."""
        with self._lock:
            if prep.released:
                return
            prep.released = True
            if prep.generation == self._gen:
                self.hot.unpin(prep.slots[: prep.n_unique])

    def _update_fn(self, n_padded: int, n_ids: int):
        """Jitted (padded rows, per-occurrence grads, inverse, step) ->
        new padded rows for this optimizer (cached per shape bucket).
        Duplicate occurrences are segment-summed inside the jit; padded
        rows receive zero gradient, so their update is the identity."""
        key = (n_padded, n_ids, self.host.num_slots)
        fn = self._update_fns.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        dim = self.host.dim
        opt = self._opt
        lr, eps = self._lr, self._eps
        mom, b1, b2 = self._momentum, self._beta1, self._beta2

        def update(rows, grads_occ, inverse, step):
            grads = jax.ops.segment_sum(
                grads_occ, inverse, num_segments=n_padded
            )
            w = rows[:, :dim]
            if opt == "adagrad":
                acc = rows[:, dim : 2 * dim] + grads * grads
                w = w - lr * grads / (jnp.sqrt(acc) + eps)
                rows = rows.at[:, dim : 2 * dim].set(acc)
            elif opt == "momentum":
                m = mom * rows[:, dim : 2 * dim] + grads
                w = w - lr * m
                rows = rows.at[:, dim : 2 * dim].set(m)
            else:  # adam
                m = b1 * rows[:, dim : 2 * dim] + (1.0 - b1) * grads
                v = b2 * rows[:, 2 * dim : 3 * dim] + (
                    1.0 - b2
                ) * grads * grads
                bc1 = 1.0 - b1 ** step.astype(jnp.float32)
                bc2 = 1.0 - b2 ** step.astype(jnp.float32)
                w = w - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)
                rows = rows.at[:, dim : 2 * dim].set(m)
                rows = rows.at[:, 2 * dim : 3 * dim].set(v)
            return rows.at[:, :dim].set(w)

        fn = jax.jit(update)
        self._update_fns[key] = fn
        return fn

    def apply_grads(self, prep: PreparedBatch, row_grads, step: int = 1):
        """On-device sparse update: segment-sum duplicate occurrences
        onto the unique rows, run the optimizer math, scatter the new
        rows back into the HBM table. Never touches the host link."""
        import jax.numpy as jnp

        grads = jnp.asarray(row_grads, jnp.float32).reshape(
            len(prep.ids), self.host.dim
        )
        fn = self._update_fn(len(prep.slots), len(prep.ids))
        with self._lock:
            self._check_gen(prep)
            rows = self.hot.gather_rows(prep.slots)
            new_rows = fn(
                rows,
                grads,
                prep.inverse,
                jnp.asarray(max(1, int(step)), jnp.int32),
            )
            self.hot.scatter_rows(prep.slots, new_rows, dirty=True)
            if not prep.released:
                prep.released = True
                self.hot.unpin(prep.slots[: prep.n_unique])

    # -- spill / flush / checkpoint ------------------------------------
    def evict_to_host(self, keep_rows: Optional[int] = None) -> int:
        """Spill coldest resident rows until at most ``keep_rows``
        remain (default: half the capacity) — the HBM→host analogue of
        ``TieredKvEmbedding.evict_cold``, run at checkpoint cadence."""
        with self._lock:
            keep = (
                self.hot.capacity // 2 if keep_rows is None else keep_rows
            )
            occupied = np.nonzero(
                (self.hot._id_of >= 0) & (self.hot._pins == 0)
            )[0]
            excess = len(occupied) - max(0, keep)
            if excess <= 0:
                return 0
            order = np.argsort(
                self.hot._last_used[occupied], kind="stable"
            )
            victims = occupied[order[:excess]]
            self._spill(victims)
            self.hot.drop(victims)
            self._bump_gen()
        return int(excess)

    def _bump_gen(self):
        """Invalidate every outstanding PreparedBatch (they must
        re-prepare) and reset ALL pins with them: a stale prep's
        release() is a no-op by design, so leaving its pins in place
        would leak one batch of un-evictable slots per bump."""
        self._gen += 1
        self.hot._pins[:] = 0

    def flush(self) -> int:
        """Write every dirty resident row back to the host store and
        wait for the spill drain: after flush the host tiers hold the
        complete, current state (the checkpoint precondition). Rows
        STAY resident (and clean)."""
        with self._lock:
            dirty = self.hot.dirty_slots()
            if len(dirty):
                ids = self.hot._id_of[dirty].copy()
                rows = np.asarray(self.hot.gather_rows(dirty))
                self.host.import_rows(ids, rows)
                self.stats.spill_rows += len(ids)
                self.stats.spill_bytes += rows.nbytes
                self.stats.host_leg_s += self._price(
                    rows.nbytes, h2d=False
                )
                self.hot.clear_dirty(dirty)
        self.join_spills()
        return int(len(dirty))

    def join_spills(self, timeout: float = 30.0):
        """Barrier on the async spill drain (checkpoint/teardown).
        Waits on the in-flight COUNT, not the queue: the queue empties
        the moment the drain dequeues, while the import of that last
        item may still be running — returning then would let a
        checkpoint export race it."""
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                if self._spills_inflight == 0:
                    break
            if time.monotonic() > deadline:
                raise TimeoutError("embedding spill drain wedged")
            time.sleep(0.002)
        if self._spill_err is not None:
            err, self._spill_err = self._spill_err, None
            raise err

    def close(self):
        if self._spill_thread is not None:
            self._spill_q.put(None)
            self._spill_thread.join(timeout=5.0)
            self._spill_thread = None

    # -- host-store passthrough (checkpoint / reshard surface) ---------
    def export_state(self, since_versions=None):
        """Flush-then-export: the host store's merged view IS the
        checkpoint (device-resident training included)."""
        self.flush()
        return self.host.export_state(since_versions)

    def shard_versions(self):
        return self.host.shard_versions()

    def import_state(self, state):
        """Restore into the host tier and invalidate the device tier:
        resident rows may now be stale, so they are dropped (clean —
        the import is authoritative) and will fault back in."""
        with self._lock:
            occupied = np.nonzero(self.hot._id_of >= 0)[0]
            self.hot.drop(occupied)
            self._bump_gen()
        self.host.import_state(state)

    def warm_reshard(self, new_num_shards: int):
        """Flush, then warm-reshard the host store (move-only): the
        device tier keeps serving — residency survives a reshard
        because the id→slot map is independent of host routing."""
        self.flush()
        return self.host.warm_reshard(new_num_shards)

    def __len__(self) -> int:
        return len(self.host)

    @property
    def dim(self) -> int:
        return self.host.dim

    @property
    def num_slots(self) -> int:
        return self.host.num_slots

    # -- telemetry -----------------------------------------------------
    def export_metrics(self, registry=None) -> Dict[str, float]:
        """Publish per-table gauges; returns the scalar dict the
        trainer forwards to the master (→ Brain job_metrics)."""
        if registry is None:
            from dlrover_tpu.obs.metrics import default_registry

            registry = default_registry()
        scalars = self.stats.as_dict()
        scalars["emb_hot_rows"] = float(len(self.hot))
        scalars["emb_hbm_bytes"] = float(self.hot.hbm_bytes)
        # refresh the arbiter's standing-demand hints (the dry-runner
        # prices aggregate host traffic from these): average bytes per
        # gather cycle so far
        gathers = max(self.stats.gathers, 1)
        self._fault_stream.demand_bytes_per_step = (
            self.stats.fault_bytes // gathers
        )
        self._spill_stream.demand_bytes_per_step = (
            self.stats.spill_bytes // gathers
        )
        for name, value in scalars.items():
            registry.gauge(
                f"dlrover_embedding_{name[4:]}",
                f"embedding hot tier: {name[4:]}",
                labelnames=("table",),
            ).labels(self.table_name).set(value)
        return scalars
