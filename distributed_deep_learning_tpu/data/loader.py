"""Host-side batched loader feeding device-sharded arrays.

Replaces the reference's DataLoader stack (``SubsetRandomSampler`` →
``DistributedSampler`` → ``DataLoader`` with per-item ``.to(device)``,
``CNN/main.py:165-179`` + ``CNN/dataset.py:107``) with the TPU-native
pattern: form the whole per-process batch on host, then do ONE
``device_put`` onto a :class:`~jax.sharding.NamedSharding` that splits the
batch dimension over the data-parallel mesh axes.  XLA then sees fully
sharded inputs and never inserts host transfers inside the step.

Multi-host: each process materialises only its addressable shard of the
global batch (`jax.make_array_from_process_local_data`), so the loader
scales to pods without any code change.

The input pipeline reports itself: :meth:`DeviceLoader.iter_batches`
clocks ``batch_form`` (the dataset's gather / decode) and ``h2d_enqueue``
(the sharded ``device_put``) of every batch, always on, and publishes
the sums and a ring of per-batch host seconds as
``obs.last_run("loader")``; while a profiler session or a Tracer listens
each batch is a ``ddl:batch`` span with the two as children.
"""

from __future__ import annotations

from typing import Iterator

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_deep_learning_tpu.data.datasets import ArrayDataset
from distributed_deep_learning_tpu.obs import runlog
from distributed_deep_learning_tpu.obs.trace import PhaseClock

# Batch dimension is sharded over both data-parallel-ish axes; ZeRO/fsdp
# meshes reuse the same loader unchanged.
BATCH_AXES = ("data", "fsdp")

#: the host phases of one batch, see the module docstring
BATCH_PHASES = ("batch_form", "h2d_enqueue")


class DeviceLoader:
    """Iterates seeded, sharded, device-resident batches of one split."""

    def __init__(self, dataset: ArrayDataset, indices: np.ndarray,
                 global_batch_size: int, mesh: Mesh, *,
                 shuffle: bool = False, seed: int = 42,
                 drop_remainder: bool = True):
        self.dataset = dataset
        self.indices = np.asarray(indices)
        self.global_batch_size = int(global_batch_size)
        self.mesh = mesh
        self.shuffle = shuffle
        self.seed = seed
        self.drop_remainder = drop_remainder
        self.epoch = 0

        dp = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
        if self.global_batch_size % dp:
            raise ValueError(f"global batch {global_batch_size} not divisible "
                             f"by data-parallel size {dp}")
        self._sharding = NamedSharding(mesh, P(BATCH_AXES))
        # Which rows of the *global* batch this process must materialise:
        # derived from the sharding itself (covers replicated-batch meshes,
        # e.g. pure-stage meshes spanning several hosts, where every process
        # needs the full batch — not from a contiguous-even-slice assumption).
        imap = self._sharding.addressable_devices_indices_map(
            (self.global_batch_size,))
        rows = np.zeros(self.global_batch_size, dtype=bool)
        for (sl,) in imap.values():
            rows[sl] = True
        self._local_rows = np.flatnonzero(rows)
        #: this loader's record over all its epochs (obs.last_run("loader")
        #: while it is the loader last iterated)
        self.record = runlog.RunRecord(
            "loader", PhaseClock(BATCH_PHASES),
            global_batch_size=self.global_batch_size)

    def __len__(self) -> int:
        n = len(self.indices)
        if self.drop_remainder:
            return n // self.global_batch_size
        return -(-n // self.global_batch_size)

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def _epoch_indices(self) -> np.ndarray:
        idx = self.indices
        if self.shuffle:
            rng = np.random.default_rng((self.seed, self.epoch))
            idx = idx[rng.permutation(len(idx))]
        if self.drop_remainder:
            idx = idx[:len(idx) - len(idx) % self.global_batch_size]
        return idx

    def _to_device(self, host: np.ndarray) -> jax.Array:
        return jax.make_array_from_process_local_data(self._sharding, host)

    def __iter__(self) -> Iterator[tuple[jax.Array, jax.Array]]:
        return self.iter_batches()

    def iter_host_batches(self, skip: int = 0
                          ) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        """This epoch's HOST-side (x, y) batches — the pure batch-formation
        path (gather/decode, no device transfer), skipping the first
        ``skip`` without materialising them (mid-epoch resume: the skipped
        batches were already trained before the checkpoint — no gather, no
        decode, no device transfer for them)."""
        idx = self._epoch_indices()
        for start in range(skip * self.global_batch_size, len(idx),
                           self.global_batch_size):
            batch_idx = idx[start:start + self.global_batch_size]
            if len(batch_idx) < self.global_batch_size and self.drop_remainder:
                break
            # materialise only this process's rows of the global batch
            local = batch_idx[self._local_rows] \
                if jax.process_count() > 1 else batch_idx
            yield self.dataset.batch(local)

    def iter_batches(self, skip: int = 0
                     ) -> Iterator[tuple[jax.Array, jax.Array]]:
        """Device-resident batches, double-buffered: batch k+1's sharded
        ``device_put`` is enqueued BEFORE batch k is handed to the caller,
        so its host→device transfer drains while the caller's step k
        dispatch runs — one batch of transfer latency is always hidden,
        even without :class:`PrefetchLoader`."""
        pc = runlog.publish(self.record).phases
        form, put = pc.phase("batch_form"), pc.phase("h2d_enqueue")
        host = self.iter_host_batches(skip)
        prev = None
        while True:
            # the tick closes before the yield: a span never stays open
            # across the consumer's step
            with pc.tick(pc.n_ticks, "batch", trace_id="train",
                         track="loader") as tk:
                with form:
                    xy = next(host, None)
                if xy is not None:
                    with put:
                        cur = (self._to_device(xy[0]),
                               self._to_device(xy[1]))
                    tk.kind = "batch"
            if xy is None:
                break
            if prev is not None:
                yield prev
            prev = cur
        if prev is not None:
            yield prev


class PrefetchLoader:
    """Background-thread prefetch wrapper over any batch iterable.

    Overlaps host-side batch formation (gather / decode — the C++ library's
    territory) and the sharded ``device_put`` with device compute: while
    step *k* runs on the TPU, batch *k+1..k+depth* are being built.  The
    reference got this from DataLoader worker processes; a thread is the
    right tool here because the heavy lifting releases the GIL (memcpy in
    the native gather, IO, device transfer).
    """

    def __init__(self, loader, depth: int = 2):
        self.loader = loader
        self.depth = max(1, int(depth))

    def __len__(self) -> int:
        return len(self.loader)

    def set_epoch(self, epoch: int) -> None:
        if hasattr(self.loader, "set_epoch"):
            self.loader.set_epoch(epoch)

    def iter_batches(self, skip: int = 0):
        """Mid-epoch resume passthrough: skip inside the WRAPPED loader
        (before materialisation) when it supports it, else drop the first
        ``skip`` prefetched items."""
        if hasattr(self.loader, "iter_batches"):
            return self._pump(self.loader.iter_batches(skip))
        import itertools

        return itertools.islice(self._pump(iter(self.loader)), skip, None)

    def __iter__(self):
        return self._pump(iter(self.loader))

    def _pump(self, source):
        import queue
        import threading

        q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        _END = object()
        stop = threading.Event()

        def put(item) -> bool:
            # bounded-wait put so an abandoned consumer (early `break` from
            # the epoch loop) never strands the producer on a full queue
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    continue
            return False

        def produce():
            try:
                for item in source:
                    if not put(item):
                        return
                put(_END)
            except BaseException as e:  # surface in the consumer
                put(e)

        t = threading.Thread(target=produce, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is _END:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()
            while True:  # unblock a producer mid-put
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            t.join(timeout=5.0)


def make_loaders(dataset: ArrayDataset, splits, global_batch_size: int,
                 mesh: Mesh, seed: int = 42, prefetch: int = 2):
    """(train, val, test) loaders with reference semantics: train shuffles
    per-epoch, eval splits iterate in fixed order.  The train loader is
    wrapped in :class:`PrefetchLoader` (``prefetch`` batches deep, 0 to
    disable) so host batch formation overlaps device compute — the analogue
    of the reference's DataLoader worker processes."""
    train = DeviceLoader(dataset, splits.train, global_batch_size, mesh,
                         shuffle=True, seed=seed)
    if prefetch:
        train = PrefetchLoader(train, depth=prefetch)
    val = DeviceLoader(dataset, splits.val, global_batch_size, mesh,
                       shuffle=False, seed=seed)
    test = DeviceLoader(dataset, splits.test, global_batch_size, mesh,
                        shuffle=False, seed=seed)
    return train, val, test
