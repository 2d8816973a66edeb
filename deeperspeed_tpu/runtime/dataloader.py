"""Deterministic data loading (equivalent of reference ``runtime/dataloader.py``).

``DeeperSpeedDataLoader`` yields *global* batches on single-host JAX (one
process feeds the whole mesh).  At ``jax.process_count() > 1`` every process
computes the IDENTICAL seeded permutation, then yields only its contiguous
``1/process_count`` slice of each global batch -- the reference
DistributedSampler contract (``runtime/dataloader.py:121``) -- which
``engine._stack_microbatches`` assembles into global arrays via
``jax.make_array_from_process_local_data``.  ``RepeatingLoader`` wraps any
loader into an infinite iterator (reference ``dataloader.py:17``).
"""

import collections

import numpy as np

from ..telemetry.trace import span


class DevicePrefetchingLoader:
    """Double-buffers device transfer of batch N+1 while step N runs.

    Wraps a host-batch iterator and a ``put_fn`` (the engine's
    ``_stack_microbatches``: stack to [gas, B, ...] + ``jax.device_put``
    sharded to the batch layout).  JAX dispatch is asynchronous, so issuing
    the put for the NEXT ``depth`` batches as soon as one is consumed means
    the host->device copy runs concurrently with the current step instead
    of serializing ahead of its dispatch (``comm.overlap.prefetch_depth``).

    Checkpointing: the wrapped iterator runs ``depth`` batches ahead of
    what the trainer consumed.  ``position()`` returns the source loader's
    ``state_dict`` snapshot taken BEFORE the oldest *unconsumed* buffered
    batch was pulled, so a resume re-delivers exactly the buffered batches
    a save threw away (``position_fn`` supplies the snapshots; without one
    ``position()`` is None and the caller falls back to the raw loader
    state).
    """

    def __init__(self, iterator, put_fn, depth=1, position_fn=None,
                 pulls_per_batch=1):
        self.iterator = iterator
        self.put_fn = put_fn
        self.depth = max(1, int(depth))
        self.position_fn = position_fn
        # items consumed from the source per delivered batch (the engine's
        # iterator yields MICRObatches: one full batch = gas pulls, which
        # put_fn stacks into the [gas, B, ...] layout)
        self.pulls_per_batch = max(1, int(pulls_per_batch))
        self._buf = collections.deque()
        self._exhausted = False

    def _fill(self):
        while not self._exhausted and len(self._buf) < self.depth:
            pos = self.position_fn() if self.position_fn is not None else None
            # one pull and its device_put: inside the engine's train/input
            with span("train/prefetch", buffered=len(self._buf)):
                try:
                    if self.pulls_per_batch == 1:
                        batch = next(self.iterator)
                    else:
                        batch = [next(self.iterator)
                                 for _ in range(self.pulls_per_batch)]
                except StopIteration:
                    self._exhausted = True
                    return
                self._buf.append((self.put_fn(batch), pos))

    def __iter__(self):
        return self

    def __next__(self):
        self._fill()
        if not self._buf:
            raise StopIteration
        batch, _pos = self._buf.popleft()
        # refill immediately: the next batch's H2D overlaps this step
        self._fill()
        return batch

    def position(self):
        if self._buf:
            return self._buf[0][1]
        return self.position_fn() if self.position_fn is not None else None


class RepeatingLoader:
    def __init__(self, loader):
        self.loader = loader
        self.data_iter = iter(self.loader)

    def __iter__(self):
        return self

    def __len__(self):
        return len(self.loader)

    def __next__(self):
        try:
            batch = next(self.data_iter)
        except StopIteration:
            self.data_iter = iter(self.loader)
            batch = next(self.data_iter)
        return batch


class DeeperSpeedDataLoader:
    """Batches a map-style dataset deterministically.

    ``dataset`` may be: a dict of numpy arrays (column store), a sequence of
    examples (dicts or tuples), or anything with ``__getitem__``/``__len__``.
    Shuffling is seeded and epoch-stable so every host computes the identical
    permutation (the determinism contract of the reference's
    DistributedSampler usage).
    """

    def __init__(self, dataset, batch_size, collate_fn=None, drop_last=True,
                 shuffle=True, seed=1234, sampler=None, num_shards=None,
                 shard_index=None):
        self.dataset = dataset
        self.batch_size = batch_size
        self.collate_fn = collate_fn
        self.drop_last = drop_last
        self.shuffle = shuffle
        self.seed = seed
        self.epoch = 0
        self._batch_idx = 0        # batches delivered in the current epoch
        self._resume_batch_idx = 0  # fast-forward target after a restore
        # optional index sampler (curriculum data sampler): an object whose
        # ``next_batch_indices()`` yields the global batch's sample ids
        # (reference DeepSpeedDataSampler consumed by ``deepspeed_io``)
        self.sampler = sampler
        # per-process slice of each global batch (multi-host): defaults to
        # the live jax process topology; explicit args make the sharding
        # math unit-testable without multiple processes
        if num_shards is None:
            import jax

            num_shards = jax.process_count()
            shard_index = jax.process_index()
        self.num_shards = num_shards
        self.shard_index = shard_index or 0
        if batch_size % num_shards:
            raise ValueError(
                f"global batch {batch_size} not divisible by "
                f"process_count {num_shards}")
        if isinstance(dataset, dict):
            lens = {k: len(v) for k, v in dataset.items()}
            assert len(set(lens.values())) == 1, f"ragged columns: {lens}"
            self._n = next(iter(lens.values()))
            self._columnar = True
        else:
            self._n = len(dataset)
            self._columnar = False

    def set_epoch(self, epoch):
        self.epoch = epoch

    # -- checkpointable iterator position (PR 3 resilience) ---------------
    # the (epoch, batch_idx) pair fully determines the next sample under
    # the seeded epoch-stable shuffle, so persisting it in
    # ``engine_state.json`` makes resume consume the exact batches an
    # uninterrupted run would -- no replay, no skips

    def state_dict(self):
        return {"epoch": int(self.epoch), "batch_idx": int(self._batch_idx)}

    def load_state_dict(self, state):
        b = int(state.get("batch_idx", 0))
        n = max(len(self), 1)
        # batch_idx == len(self) means the epoch's last batch was delivered
        # but the generator never resumed to roll the epoch over -- resume
        # at the next epoch's start, not by replaying this one
        self.epoch = int(state.get("epoch", 0)) + b // n
        self._resume_batch_idx = b % n

    def __len__(self):
        if self.drop_last:
            return self._n // self.batch_size
        return (self._n + self.batch_size - 1) // self.batch_size

    def _shard(self, idx):
        """This process's contiguous slice of a global batch's indices.

        Contiguity (not rank-striding) matters: it matches the row order
        ``make_array_from_process_local_data`` assigns to each process's
        addressable devices, so a multi-process run consumes the exact
        global batch a single-process run would."""
        if self.num_shards == 1:
            return idx
        if len(idx) % self.num_shards:
            # a ragged final batch (drop_last=False) or sampler batch would
            # silently drop samples on every rank -- refuse instead
            raise ValueError(
                f"batch of {len(idx)} samples not divisible by "
                f"process_count {self.num_shards}; use drop_last=True or a "
                "process-divisible batch size")
        per = len(idx) // self.num_shards
        return idx[self.shard_index * per:(self.shard_index + 1) * per]

    def __iter__(self):
        start, self._resume_batch_idx = self._resume_batch_idx, 0
        if self.sampler is not None:
            for i in range(len(self)):
                batch_idx = np.asarray(self.sampler.next_batch_indices())
                if i < start:
                    continue  # fast-forward: sampler state still advances
                self._batch_idx = i + 1
                yield self._gather(self._shard(batch_idx))
            self.epoch += 1
            self._batch_idx = 0
            return
        order = np.arange(self._n)
        if self.shuffle:
            rng = np.random.RandomState(self.seed + self.epoch)
            rng.shuffle(order)
        for i in range(start, len(self)):
            idx = self._shard(order[i * self.batch_size:(i + 1) * self.batch_size])
            # set BEFORE yield: while the generator is suspended mid-epoch,
            # state_dict() must equal the count of batches already delivered
            self._batch_idx = i + 1
            yield self._gather(idx)
        self.epoch += 1
        self._batch_idx = 0

    def _gather(self, idx):
        if self._columnar:
            batch = {k: np.asarray(v)[idx] for k, v in self.dataset.items()}
        else:
            examples = [self.dataset[int(i)] for i in idx]
            if self.collate_fn is not None:
                return self.collate_fn(examples)
            first = examples[0]
            if isinstance(first, dict):
                batch = {k: np.stack([e[k] for e in examples]) for k in first}
            elif isinstance(first, (tuple, list)):
                batch = tuple(np.stack([e[j] for e in examples]) for j in range(len(first)))
            else:
                batch = np.stack(examples)
        if self.collate_fn is not None:
            return self.collate_fn(batch)
        return batch
