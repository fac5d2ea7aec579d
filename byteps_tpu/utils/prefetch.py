"""Host->device input pipeline: background prefetch with double buffering.

The reference delegates data loading to the frameworks' loaders
(torchvision/gluon in its examples); on TPU the equivalent gap is the
host->device edge: a training loop that calls ``device_put`` inline
serializes the host-to-device transfer with the step it feeds.  This module
overlaps them:

- :func:`prefetch_to_device` wraps any host-batch iterator: a background
  thread stages the next ``size`` batches onto the device (with the
  caller's sharding — replicated, batch-sharded over dp, or any
  NamedSharding) while the current step runs.  JAX's async dispatch does
  the rest: by the time the consumer asks, the transfer has happened.
- :class:`ShardedBatchLoader` is the mesh-aware convenience: wraps a
  numpy-batch source and yields device batches sharded over the DP axes
  of a CommContext, ready for the fused train steps.

Shapes should be constant across batches (XLA recompiles per shape);
the loader asserts this early rather than letting the 20s recompile
surprise land mid-epoch.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterable, Iterator, Optional

import jax

from ..comm.mesh import CommContext

__all__ = ["prefetch_to_device", "ShardedBatchLoader"]

_END = object()


def prefetch_to_device(iterator: Iterable, size: int = 2,
                       sharding=None,
                       device_put: Optional[Callable] = None) -> Iterator:
    """Yield batches from ``iterator`` staged onto device ahead of use.

    ``size`` is the number of in-flight device batches (2 = classic
    double buffering; more helps jittery sources).  ``sharding`` is
    passed to ``jax.device_put`` (None = default device).  A custom
    ``device_put`` callable overrides the transfer entirely (e.g. for
    ``jax.make_array_from_process_local_data`` under multi-host).

    The background thread only *stages* (device_put is async dispatch);
    errors from the source iterator are re-raised at the consuming side.
    """
    if size < 1:
        raise ValueError(f"prefetch size must be >= 1, got {size}")
    put = device_put or (
        lambda b: jax.device_put(b, sharding) if sharding is not None
        else jax.device_put(b))
    q: "queue.Queue" = queue.Queue(maxsize=size)
    stop = threading.Event()

    def producer():
        try:
            for batch in iterator:
                staged = put(batch)
                # bounded put + stop poll: a consumer that breaks out of
                # its loop must not leave this thread parked in q.put
                # forever, pinning device batches
                while not stop.is_set():
                    try:
                        q.put(staged, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if stop.is_set():
                    return
        except BaseException as e:  # noqa: BLE001 - re-raised at consumer
            q.put((_END, e))
            return
        q.put((_END, None))

    t = threading.Thread(target=producer, name="bps-prefetch", daemon=True)
    t.start()

    try:
        while True:
            item = q.get()
            if (isinstance(item, tuple) and len(item) == 2
                    and item[0] is _END):
                if item[1] is not None:
                    raise item[1]
                return
            yield item
    finally:
        # early consumer exit (break / GeneratorExit): release the
        # producer and drop staged batches so device memory frees
        stop.set()
        while True:
            try:
                q.get_nowait()
            except queue.Empty:
                break


class ShardedBatchLoader:
    """Mesh-aware batch loader: host numpy batches -> dp-sharded device
    batches, prefetched.

    ``source`` yields pytrees of host arrays with a leading batch axis
    divisible by the mesh's rank count.  Iterating the loader yields the
    same pytrees as device arrays sharded over the DP axes (the layout
    ``make_dp_train_step`` consumes).
    """

    def __init__(self, comm: CommContext, source: Iterable,
                 prefetch: int = 2):
        self.comm = comm
        self.source = source
        self.prefetch = prefetch
        self._shapes: Optional[Any] = None
        self._consumed = False

    def _check(self, batch):
        shapes = jax.tree.map(lambda x: getattr(x, "shape", None), batch)
        if self._shapes is None:
            self._shapes = shapes
            ranks = self.comm.num_ranks
            for leaf in jax.tree.leaves(batch):
                if leaf.shape[0] % ranks:
                    raise ValueError(
                        f"batch axis {leaf.shape[0]} not divisible by "
                        f"{ranks} mesh ranks")
        elif shapes != self._shapes:
            raise ValueError(
                f"batch shapes changed mid-stream (XLA would recompile "
                f"every step): first {self._shapes}, now {shapes}")
        return batch

    def __iter__(self):
        from ..parallel import shard_batch
        it = iter(self.source)
        if it is self.source and self._consumed:
            # a generator/iterator source is one-shot: a second epoch
            # would silently yield nothing — fail loudly instead.  Pass
            # a re-iterable (list, or an object with a fresh __iter__)
            # for epoch-style loops.
            raise ValueError(
                "ShardedBatchLoader source is a one-shot iterator that "
                "was already consumed; pass a re-iterable (e.g. a list "
                "or a Dataset object) for multi-epoch iteration")
        self._consumed = True
        checked = (self._check(b) for b in it)
        return prefetch_to_device(
            checked, size=self.prefetch,
            device_put=lambda b: shard_batch(self.comm, b))
