"""Infinite, per-host-sharded batch loader with device prefetch.

Replaces the reference's ``MultiEpochsDataLoader`` + ``_RepeatSampler``
(``SRNdataset.py:12-40``, persistent workers that yield forever) and its
broken ``DistributedSampler`` usage (``train.py:224-226``, see SURVEY.md
§2.7).  TPU-native design:

  * each host draws its own disjoint slice of the global batch, derived
    deterministically from ``(seed, step, global_slot)`` — no sampler state
    to synchronise and resume is exact: seek to any step by number;
  * **elasticity determinism rule**: the global batch stream is a pure
    function of ``(seed, step)`` alone — host ``h`` of ``H`` takes global
    slots ``[h*B, (h+1)*B)`` of a per-step draw of ``H*B`` global slots.
    Re-partitioning the same global batch across a *different* host count
    (with the per-host batch size rescaled so ``H*B`` is constant) yields
    the identical global stream, so an elastic re-mesh neither replays
    nor skips examples;
  * a thread pool overlaps image decode with device compute;
  * :func:`prefetch_to_device` keeps ``depth`` batches in flight as sharded
    device arrays (the JAX equivalent of pinned-memory prefetch).
"""

from __future__ import annotations

import queue
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterator, Optional

import numpy as np

from diff3d_tpu.data.images import quantize_uint8
from diff3d_tpu.utils.profiling import count, span


def _collate(samples) -> Dict[str, np.ndarray]:
    return {k: np.stack([s[k] for s in samples]) for k in samples[0]}


class InfiniteLoader:
    """Yields ``{'imgs':[B,V,H,W,3], 'R':[B,V,3,3], 'T':[B,V,3], 'K':[B,3,3]}``
    forever, ``B`` = per-host batch size.

    Sampling is stateless-per-step: the *global* batch ``n`` is a pure
    function of ``(seed, n)`` and host ``h`` takes global slots
    ``[h*B, (h+1)*B)`` of it, so checkpoint resume replays the exact data
    order without any loader state (the reference's resume restores only
    the step counter, ``train.py:244-251``) and an elastic host-count
    change re-derives the same global stream under the new partition.
    """

    def __init__(self, dataset, batch_size: int, *, seed: int = 0,
                 host_id: int = 0, num_hosts: int = 1,
                 num_workers: int = 8, start_step: int = 0,
                 images_uint8: bool = True, sample_mode: str = "iid"):
        """``sample_mode``:

        * ``'iid'`` (default, training) — objects drawn independently with
          replacement per slot;
        * ``'permute'`` — without-replacement epoch permutations: global
          draw ``g = step * global_batch + global_slot`` indexes a
          per-epoch shuffle of the dataset, so every object is seen
          exactly once per ``len(dataset)`` consecutive global draws (the
          reference's epoch semantics, ``SRNdataset.py:12-40``) while
          staying a pure function of ``(seed, step, global_slot)``.
          Default for val loaders — no double-counted objects in small
          val splits.
        """
        if sample_mode not in ("iid", "permute"):
            raise ValueError(f"unknown sample_mode {sample_mode!r}")
        self.dataset = dataset
        self.batch_size = batch_size
        self.seed = seed
        self.host_id = host_id
        self.num_hosts = num_hosts
        self.images_uint8 = images_uint8
        self.sample_mode = sample_mode
        self._step = start_step
        self._quant_warn: Dict[str, bool] = {}   # see quantize_uint8
        self._perm_cache: Dict[int, np.ndarray] = {}
        self._pool = (ThreadPoolExecutor(num_workers)
                      if num_workers > 0 else None)

    # rng-lineage: stream(epoch permutation: SeedSequence entropy=(seed,
    # 0x7065726D) spawn_key=(epoch,) — entropy-disjoint from _batch's
    # per-sample tree, identical on every host)
    def _epoch_perm(self, epoch: int) -> np.ndarray:
        perm = self._perm_cache.get(epoch)
        if perm is None:
            # Distinct ENTROPY (not just spawn_key) from the per-sample
            # streams: _batch's root spawn((step,)) children are
            # (step, global_slot) keys over entropy=seed, so any key-only
            # scheme could collide (spawn appends a child index).  The
            # permutation is shared by all hosts.
            rng = np.random.default_rng(np.random.SeedSequence(
                entropy=(self.seed, 0x7065726D), spawn_key=(epoch,)))
            perm = rng.permutation(len(self.dataset))
            self._perm_cache[epoch] = perm
            for old in sorted(self._perm_cache)[:-4]:
                del self._perm_cache[old]
        return perm

    # rng-lineage: stream(global-batch seed tree: SeedSequence
    # entropy=seed spawn_key=(step,) spawned once per GLOBAL slot, host
    # takes slots [host_id*B, host_id*B+B) — the stream is a pure
    # function of (seed, step, global_slot), pinned by the 'loader'
    # manifest under runs/rngcheck/)
    def _batch(self, step: int) -> Dict[str, np.ndarray]:
        # Elasticity determinism: spawn the *global* batch's seed streams
        # (spawn_key depends on step only) and slice this host's
        # contiguous slot range.  Any (host_id, num_hosts) partition of
        # the same global batch size reproduces the identical global
        # stream, so a re-mesh resumes without replaying or skipping.
        global_batch = self.batch_size * self.num_hosts
        lo = self.host_id * self.batch_size
        root = np.random.SeedSequence(entropy=self.seed, spawn_key=(step,))
        seqs = root.spawn(global_batch)[lo:lo + self.batch_size]
        n = len(self.dataset)

        if self.sample_mode == "permute":
            g0 = step * global_batch + lo
            idxs = [int(self._epoch_perm((g0 + b) // n)[(g0 + b) % n])
                    for b in range(self.batch_size)]
        else:
            idxs = [None] * self.batch_size

        def one(args):
            idx, seq = args
            rng = np.random.default_rng(seq)
            if idx is None:
                idx = int(rng.integers(n))
            s = self.dataset.sample(idx, rng)
            if (self.images_uint8 and "imgs" in s
                    and s["imgs"].dtype != np.uint8):
                # Per sample, inside the worker pool: the batch stacks
                # directly as uint8 (4x less host RAM and host->device
                # traffic; see data/images.py) and the conversion
                # parallelizes across workers.  The jitted step
                # dequantizes on device.
                s = dict(s, imgs=quantize_uint8(s["imgs"],
                                                self._quant_warn))
            return s

        if self._pool is not None:
            samples = list(self._pool.map(one, zip(idxs, seqs)))
        else:
            samples = [one(a) for a in zip(idxs, seqs)]
        return _collate(samples)

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        return self

    def __next__(self) -> Dict[str, np.ndarray]:
        with span("loader.batch", id=self._step):
            batch = self._batch(self._step)
        self._step += 1
        return batch


def prefetch_to_device(it: Iterator, sharding=None, depth: int = 2,
                       to_device: bool = True) -> Iterator:
    """Runs ``it`` in a background thread, keeping ``depth`` batches ahead;
    each batch is ``jax.device_put`` with ``sharding`` (a NamedSharding with
    the batch axis on the mesh's data axis) so the global array lands
    already sharded.

    Spans (``utils/profiling``), each with the batch's count ``k`` as id:
    ``prefetch.put`` around the producer's upload of batch ``k``,
    ``prefetch.wait`` around the consumer's ``get`` of it; the counter
    ``prefetch.starved`` counts the gets that found the queue empty."""
    import jax

    q: "queue.Queue" = queue.Queue(maxsize=depth)
    stop = threading.Event()
    _SENTINEL = object()
    error: list = []

    def producer():
        try:
            from diff3d_tpu.parallel.multihost import shard_host_local

            for k, batch in enumerate(it):
                if stop.is_set():
                    return
                if to_device:
                    # Multi-host: each host's local slice becomes its
                    # shards of ONE global array (make_array_from_
                    # process_local_data); single-host: plain device_put.
                    with span("prefetch.put", id=k):
                        batch = shard_host_local(batch, sharding)
                q.put(batch)
        except BaseException as e:  # surface on the consumer side
            error.append(e)
        finally:
            q.put(_SENTINEL)

    t = threading.Thread(target=producer, daemon=True)
    t.start()

    class _Prefetcher:
        taken = 0               # batches handed out: the consumer's only

        def __iter__(self):
            return self

        def __next__(self):
            if q.empty():
                count("prefetch.starved")
            with span("prefetch.wait", id=self.taken):
                item = q.get()
            self.taken += 1
            if item is _SENTINEL:
                if error:
                    raise error[0]
                raise StopIteration
            return item

        def close(self):
            stop.set()
            while True:  # drain so the producer can observe `stop`
                try:
                    q.get_nowait()
                except queue.Empty:
                    break

    return _Prefetcher()
