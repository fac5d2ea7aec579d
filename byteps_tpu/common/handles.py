"""Handle manager: the async completion surface of push_pull.

Reference behavior: every async op allocates an integer handle; ``poll``
checks a handle->Status map and ``wait_and_clear`` blocks
(reference torch/handle_manager.cc:1-55, torch/ops.py:225-236).  On TPU the
underlying asynchrony is JAX async dispatch: a handle owns the (not yet
materialized) result arrays and completion means the dispatch has finished
executing on device.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional

import jax

from .types import Status


class Handle:
    """One outstanding push_pull: result future + per-chunk completion."""

    def __init__(self, handle_id: int, name: str):
        self.id = handle_id
        self.name = name
        self._done = threading.Event()
        self._status: Optional[Status] = None
        self._result: Any = None
        self._on_done: List[Callable[["Handle"], None]] = []
        self._lock = threading.Lock()

    # engine side ----------------------------------------------------------
    def set_result(self, result: Any, status: Status = None) -> None:
        with self._lock:
            self._result = result
            self._status = status or Status.ok()
            callbacks = list(self._on_done)
        self._done.set()
        for cb in callbacks:
            cb(self)

    def add_done_callback(self, cb: Callable[["Handle"], None]) -> None:
        fire_now = False
        with self._lock:
            if self._done.is_set():
                fire_now = True
            else:
                self._on_done.append(cb)
        if fire_now:
            cb(self)

    # user side ------------------------------------------------------------
    def poll(self) -> bool:
        """True once the result is assembled and device execution finished."""
        if not self._done.is_set():
            return False
        # Results may still be executing on device (async dispatch); treat
        # "committed" as done — callers that need values call wait().
        return True

    def wait(self, timeout: Optional[float] = None) -> Any:
        """Block until complete; returns the reduced array(s).

        This is synchronize()/wait_and_clear() in the reference
        (torch/ops.py:225-236): it blocks the Python thread until the device
        result is ready.
        """
        if not self._done.wait(timeout=timeout):
            raise TimeoutError(f"push_pull handle {self.id} ({self.name}) "
                               f"timed out")
        assert self._status is not None
        self._status.ok_or_raise()
        if self._result is not None:
            jax.block_until_ready(self._result)
        return self._result

    @property
    def status(self) -> Status:
        return self._status if self._status is not None else Status.in_progress()


class TreeHandle:
    """One tree-level push_pull (``PushPullEngine.push_pull_tree_async``):
    the engine handles it enqueued -- one per bucket of leaves, one per
    leaf that went alone -- and where each leaf's result lies.

    ``index[i] = (k, j)``: leaf i is element ``j`` of ``handles[k]``'s
    result (a bucket's tuple of leaves), or the whole result when ``j`` is
    None."""

    __slots__ = ("handles", "index")

    def __init__(self, handles: List[Handle], index):
        self.handles = handles
        self.index = index

    def wait(self, timeout: Optional[float] = None) -> list:
        """Block on every handle, in enqueue order; the leaves' results
        in flattening order.  A bucket that failed (or was dropped with
        its membership epoch) raises for the tree, as any of its leaves
        would have."""
        outs = [h.wait(timeout) for h in self.handles]
        return [outs[k] if j is None else outs[k][j] for k, j in self.index]


class HandleManager:
    """Allocates handles and tracks outstanding ones (handle_manager.cc)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._next = 0
        self._live: Dict[int, Handle] = {}

    def allocate(self, name: str) -> Handle:
        with self._lock:
            h = Handle(self._next, name)
            self._next += 1
            self._live[h.id] = h
            return h

    def get(self, handle_id: int) -> Optional[Handle]:
        with self._lock:
            return self._live.get(handle_id)

    def release(self, handle_id: int) -> None:
        with self._lock:
            self._live.pop(handle_id, None)

    def outstanding(self) -> List[Handle]:
        with self._lock:
            return [h for h in self._live.values() if not h.poll()]

    def clear(self) -> None:
        with self._lock:
            self._live.clear()
