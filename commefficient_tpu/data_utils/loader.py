"""FedLoader — static-shaped, client-major batch assembly for XLA.

The reference's DataLoader emits flat ragged batches with per-datum client
ids, which the PS re-splits per client and ships over queues (reference
fed_aggregator.py:217-224). XLA wants fixed shapes, so the loader builds the
client-major layout directly from ``FedSampler.iter_structured``:

  train round batch: {
    client_ids:  (W,)  int32   sampled client per worker slot
    worker_mask: (W,)  float32 1.0 for real slots, 0.0 for padding
    inputs:      (W, B, ...)   transformed model inputs
    targets:     (W, B)        int32
    mask:        (W, B)        float32 per-datum validity
  }

where W = num_workers and B = local_batch_size (or the max client size when
local_batch_size == -1, the fedavg whole-client mode). Padded slots/datums
carry zero masks; the worker computes data-weighted sums so they contribute
nothing — replacing the reference's skip/assert handling of ragged tails.

Val batches are flat: {inputs: (B, ...), targets: (B,), mask: (B,)} with the
client_id −1 sentinel implied (no per-client state on the val path,
reference fed_aggregator.py:337-364).

Fast path: when the dataset exposes a contiguous store
(``native_train_access``) and the transform is expressible as the fused
native pad/crop/flip/normalize kernel (``transform.native_spec``), whole
rounds are assembled by one multithreaded C++ call
(commefficient_tpu.native.image_batch) instead of a per-item Python loop.
Augmentation randomness is drawn with ``np.random`` in the exact per-item
order of the Python transform stack, so both paths produce identical batches
under the same seed (covered by tests/test_native.py).

``PrefetchLoader`` wraps any loader with a bounded background-thread queue —
the C++ assembly releases the GIL, so host batch prep overlaps device
compute (the role of the reference's DataLoader worker processes).
"""

from __future__ import annotations

import queue
import threading

import numpy as np

from commefficient_tpu import native
from commefficient_tpu.profiling import annotate

__all__ = ["FedLoader", "PrefetchLoader", "cv_collate"]


def cv_collate(items):
    """items: list of (image, target) → stacked arrays."""
    images = np.stack([np.asarray(i, np.float32) for i, _ in items])
    targets = np.asarray([t for _, t in items], np.int64)
    return {"inputs": images, "targets": targets}


class FedLoader:
    def __init__(self, dataset, num_workers=1, local_batch_size=8,
                 collate_fn=cv_collate, val_batch_size=None, use_native=None):
        self.dataset = dataset
        self.num_workers = num_workers
        self.local_batch_size = local_batch_size
        self.collate_fn = collate_fn
        self.val_batch_size = val_batch_size or 64
        self.train = dataset.type == "train"
        # cheap structural check first — native.available() may trigger the
        # one-time g++ build, pointless when the fast path can't apply
        ok = self._native_ok()
        self.use_native = ok and (native.available() if use_native is None
                                  else bool(use_native))
        if self.train:
            from commefficient_tpu.data_utils.fed_sampler import FedSampler

            self.sampler = FedSampler(dataset, num_workers, local_batch_size)

    def _native_ok(self) -> bool:
        # the fused path emits cv-style {inputs, targets} batches; a custom
        # collate_fn must win over it
        if self.collate_fn is not cv_collate:
            return False
        spec = getattr(self.dataset.transform, "native_spec", None)
        if spec is None:
            return False
        access = (self.dataset.native_train_access() if self.train
                  else self.dataset.native_val_access())
        return access is not None

    @property
    def batch_pad(self) -> int:
        if self.local_batch_size == -1:
            return int(np.max(self.dataset.data_per_client))
        return self.local_batch_size

    def steps_per_epoch(self) -> int:
        # reference utils.py:315-321
        if self.local_batch_size == -1:
            return int(self.dataset.num_clients // self.num_workers)
        return int(np.ceil(len(self.dataset)
                           / (self.local_batch_size * self.num_workers)))

    def __len__(self):
        if self.train:
            return self.steps_per_epoch()
        return int(np.ceil(len(self.dataset) / self.val_batch_size))

    def _pad_id(self, workers):
        """Client id used for the inert padding lanes of a short cohort.
        The legacy closed-population value is 0 (kept byte-for-byte:
        client 0 always owns row 0 there, and masked lanes scatter an
        exactly-zero delta, so a padding collision with a sampled client
        is a no-op by construction). Under open-world churn
        (--churn, docs/service.md) client 0 may be departed or
        never-registered — no row to gather — so padding reuses a LIVE
        cohort member instead: same zero-delta inertness, but the row
        directory can always translate it."""
        if getattr(self.sampler, "_population", None) is not None \
                and len(workers):
            return int(workers[0])
        return 0

    def _fetch(self, idx_list):
        items = []
        for i in idx_list:
            cid, *rest = self.dataset[int(i)]
            items.append(tuple(rest))
        return self.collate_fn(items)

    def __iter__(self):
        if self.train:
            if self.use_native:
                yield from self._train_iter_native()
            else:
                yield from self._train_iter()
        else:
            if self.use_native:
                yield from self._val_iter_native()
            else:
                yield from self._val_iter()

    # -- python per-item paths --------------------------------------------

    def _train_iter(self):
        W, B = self.num_workers, self.batch_pad
        for workers, idx_lists in self.sampler.iter_structured():
            n = len(workers)
            client_ids = np.full(W, self._pad_id(workers), np.int32)
            client_ids[:n] = workers
            worker_mask = np.zeros(W, np.float32)
            worker_mask[:n] = 1.0
            mask = np.zeros((W, B), np.float32)
            batch_cols = None
            for w, idxs in enumerate(idx_lists):
                cols = self._fetch(idxs)
                if batch_cols is None:
                    batch_cols = {
                        k: np.zeros((W, B) + v.shape[1:], v.dtype)
                        for k, v in cols.items()
                    }
                b = len(idxs)
                mask[w, :b] = 1.0
                for k, v in cols.items():
                    batch_cols[k][w, :b] = v
            batch = dict(batch_cols)
            batch["client_ids"] = client_ids
            batch["worker_mask"] = worker_mask
            batch["mask"] = mask
            yield batch

    def _val_iter(self):
        N = len(self.dataset)
        B = self.val_batch_size
        for start in range(0, N, B):
            idxs = range(start, min(start + B, N))
            cols = self._fetch(idxs)
            n = len(next(iter(cols.values())))
            mask = np.zeros(B, np.float32)
            mask[:n] = 1.0
            batch = {
                k: np.concatenate(
                    [v, np.zeros((B - n,) + v.shape[1:], v.dtype)], axis=0)
                if n < B else v
                for k, v in cols.items()
            }
            batch["mask"] = mask
            yield batch

    # -- native fused paths ------------------------------------------------

    def _assemble_native(self, flat_idx, spec, access):
        """flat_idx: (M,) int64 flat dataset indices, −1 = padding. Returns
        (inputs (M,size,size,C) f32, targets (M,) int64)."""
        M = flat_idx.shape[0]
        rows = np.full(M, -1, np.int64)
        ok = flat_idx >= 0
        rows[ok] = self.dataset.store_rows(flat_idx[ok])
        if spec["train"]:
            # same np.random draw order as RandomCrop (h then w) +
            # RandomHorizontalFlip, per item
            crop_h = np.zeros(M, np.int32)
            crop_w = np.zeros(M, np.int32)
            flip = np.zeros(M, np.uint8)
            hi = 2 * spec["pad"] + 1
            for m in range(M):
                if not ok[m]:
                    continue
                crop_h[m] = np.random.randint(0, hi)
                crop_w[m] = np.random.randint(0, hi)
                flip[m] = np.random.rand() < 0.5
        else:
            crop_h = crop_w = flip = None
        inputs = native.image_batch(
            access["store"], rows, crop_h, crop_w, flip,
            spec["pad"], spec["size"], spec["mean"], spec["std"])
        targets = np.zeros(M, np.int64)
        targets[ok] = access["targets"][rows[ok]]
        return inputs, targets

    def _train_iter_native(self):
        W, B = self.num_workers, self.batch_pad
        spec = self.dataset.transform.native_spec
        access = self.dataset.native_train_access()
        for workers, idx_lists in self.sampler.iter_structured():
            n = len(workers)
            client_ids = np.full(W, self._pad_id(workers), np.int32)
            client_ids[:n] = workers
            worker_mask = np.zeros(W, np.float32)
            worker_mask[:n] = 1.0
            mask = np.zeros((W, B), np.float32)
            flat_idx = np.full((W, B), -1, np.int64)
            for w, idxs in enumerate(idx_lists):
                b = len(idxs)
                mask[w, :b] = 1.0
                flat_idx[w, :b] = np.asarray(idxs, np.int64)
            inputs, targets = self._assemble_native(flat_idx.reshape(-1),
                                                    spec, access)
            yield {
                "inputs": inputs.reshape((W, B) + inputs.shape[1:]),
                "targets": targets.reshape(W, B),
                "client_ids": client_ids,
                "worker_mask": worker_mask,
                "mask": mask,
            }

    def _val_iter_native(self):
        N = len(self.dataset)
        B = self.val_batch_size
        spec = self.dataset.transform.native_spec
        access = self.dataset.native_val_access()
        for start in range(0, N, B):
            n = min(B, N - start)
            flat_idx = np.full(B, -1, np.int64)
            flat_idx[:n] = np.arange(start, start + n)
            mask = np.zeros(B, np.float32)
            mask[:n] = 1.0
            # val store rows are the flat val indices themselves
            rows = flat_idx
            inputs = native.image_batch(
                access["store"], rows, None, None, None,
                0, spec["size"], spec["mean"], spec["std"])
            targets = np.zeros(B, np.int64)
            targets[:n] = access["targets"][start:start + n]
            yield {"inputs": inputs, "targets": targets, "mask": mask}


class PrefetchLoader:
    """Background-thread prefetch with a bounded queue.

    The role of the reference's DataLoader worker processes
    (train_dataloader_workers, reference utils.py:178-182): overlap host-side
    batch assembly with device compute. One thread suffices because the heavy
    work happens inside GIL-released native calls.
    """

    _END = object()

    def __init__(self, loader, depth: int = 2):
        self.loader = loader
        self.depth = depth

    def __len__(self):
        return len(self.loader)

    def __getattr__(self, name):
        if name == "loader":  # unpickling: avoid infinite recursion
            raise AttributeError(name)
        return getattr(self.loader, name)

    def __iter__(self):
        q: queue.Queue = queue.Queue(maxsize=self.depth)
        err = []
        stop = threading.Event()

        def worker():
            try:
                it = iter(self.loader)
                while True:
                    # one batch's assembly (sampler draw, the native
                    # calls) on this thread: what the loader can sustain,
                    # whether or not the loop is waiting for it
                    with annotate("fed_input_produce"):
                        try:
                            batch = next(it)
                        except StopIteration:
                            break
                    while not stop.is_set():
                        try:
                            q.put(batch, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    if stop.is_set():
                        return
            except BaseException as e:  # re-raised on the consumer side
                err.append(e)
            finally:
                while True:  # sentinel must land even if the queue is full
                    try:
                        q.put(self._END, timeout=0.1)
                        break
                    except queue.Full:
                        if stop.is_set():
                            break

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        try:
            while True:
                with annotate("fed_input_wait"):
                    item = q.get()
                if item is self._END:
                    break
                yield item
        finally:
            # consumer stopped early (break / GeneratorExit): unblock and
            # reap the producer instead of leaking it
            stop.set()
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            t.join()
            if err:
                raise err[0]
