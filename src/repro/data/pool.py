"""Out-of-core client population store — the ``pool="streamed"`` backend.

``pack_clients`` materializes the whole population as one device-resident
(K, n_pad, ...) array: the right trade at MNIST scale (one on-device gather
per round, zero host work, zero recompiles) and the wrong one at the
paper's (millions of phones) — K is capped by device memory. This module
bounds K by host DISK instead:

- :class:`StreamedClientPool` writes clients ONCE into sharded ``.npy``
  files (``np.lib.format.open_memmap``; ``shard_clients`` clients per
  shard, each shard padded to its own widest client) and serves sampled
  cohorts back by client id. ``gather(ids)`` tiles each client's n_k real
  rows to the global ``n_pad`` with exactly ``pack_clients``' rule
  (``rows[i % n_k]``), so a gathered cohort is byte-identical to the
  device pool's ``x[ids]`` — the foundation of the streamed == device
  bit-for-bit guarantee (tests/test_engine_pool.py).
- :class:`DeviceClientPool` wraps a ``PackedClients`` under the same
  ``gather`` interface — the existing fast path, unchanged, selected
  automatically for populations that fit the device budget.
- :func:`device_pool_budget` is that selection threshold:
  ``REPRO_DEVICE_POOL_BUDGET`` (bytes) when set, else a conservative
  fraction of the backend's reported ``bytes_limit``, else 2 GiB.

Shared metadata (counts, per-client step schedule, shape buckets) comes
from ``batching.pool_metadata`` — the same function ``pack_clients`` uses
— carried as a data-less ``PackedClients``, so the engine's masking and
weighting logic is backend-agnostic.

Memory discipline: the builder holds at most one shard of clients in RAM
(``from_generator`` never materializes the population), flushes and unmaps
each shard after writing so dirty pages leave the process RSS, and
``gather`` reads through a small LRU of read-only memmaps — host RSS stays
bounded by O(shard + cohort), not O(population). The
``round_engine_scaling`` population benchmark gates this.
"""
from __future__ import annotations

import os
import shutil
import tempfile
import weakref
from collections import OrderedDict
from typing import Iterable, Iterator, Optional, Sequence, Tuple

import numpy as np

from repro.data.batching import (
    PackedClients,
    estimate_pool_nbytes,
    pack_clients,
    pool_metadata,
)

__all__ = [
    "ClientPool",
    "DeviceClientPool",
    "StreamedClientPool",
    "device_pool_budget",
]

# Read-only shard memmaps kept open per pool. Small and bounded on purpose:
# a million-client population at the default shard width is ~1000 shard
# files, and holding every (x, y) pair open would blow the default fd
# rlimit — while reopening per gather would pay path/header parsing per
# cohort. Eviction just drops the memmap; the OS page cache keeps the hot
# bytes either way.
_MMAP_CACHE_SLOTS = 64


def device_pool_budget() -> int:
    """Device-memory budget (bytes) for the resident ``pack_clients`` pool.

    ``REPRO_DEVICE_POOL_BUDGET`` overrides (the tests' and benchmarks'
    lever); otherwise 60% of the backend's reported ``bytes_limit`` when it
    has one (TPU/GPU), else 2 GiB — the CPU backend reports no limit, and
    an unbounded default would defeat the whole guard. A device error
    propagates: it is not a missing limit.
    """
    env = os.environ.get("REPRO_DEVICE_POOL_BUDGET", "")
    if env:
        return int(env)
    import jax  # lazy: importing this module must not touch a device

    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    if limit:
        return int(limit * 0.6)
    return 2 * 1024**3


class ClientPool:
    """The backend seam: population metadata plus cohort gather-by-id.

    ``meta`` is a data-less ``PackedClients`` (x=y=None) — counts, step
    schedule, batch size, shape buckets; ``gather(ids)`` returns the
    cohort's ``(x, y)`` host arrays of shape ``(m, n_pad, ...)``, tiled
    exactly as the device pool stores them."""

    kind: str = "abstract"
    meta: PackedClients
    requested_batch_size: Optional[int]

    @property
    def num_clients(self) -> int:
        return self.meta.num_clients

    @property
    def n_pad(self) -> int:
        return self.meta.max_steps_per_epoch * self.meta.batch_size

    @property
    def counts(self) -> np.ndarray:
        return self.meta.counts

    @property
    def steps_per_epoch(self) -> np.ndarray:
        return self.meta.steps_per_epoch

    @property
    def has_labels(self) -> bool:
        raise NotImplementedError

    def gather(self, ids) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        raise NotImplementedError


class DeviceClientPool(ClientPool):
    """The existing fast path under the pool interface: one resident
    ``pack_clients`` array, ``gather`` is a plain numpy take. The engine's
    device backend does its take on device; this wrapper exists so tests
    and tools can compare backends through one API."""

    kind = "device"

    def __init__(self, packed: PackedClients,
                 requested_batch_size: Optional[int]):
        self._x = packed.x
        self._y = packed.y
        self.meta = packed._replace(x=None, y=None)
        self.requested_batch_size = requested_batch_size

    @classmethod
    def build(cls, client_data, batch_size,
              max_bytes: Optional[int] = None) -> "DeviceClientPool":
        return cls(pack_clients(client_data, batch_size,
                                max_bytes=max_bytes), batch_size)

    @property
    def has_labels(self) -> bool:
        return self._y is not None

    def gather(self, ids):
        ids = np.asarray(ids)
        return self._x[ids], (self._y[ids] if self._y is not None else None)


class StreamedClientPool(ClientPool):
    """Host/disk-backed sharded population store (see module docstring).

    Build with :meth:`build` (a materialized client list) or
    :meth:`from_generator` (a client iterator — the population never fully
    exists in host RAM). ``root=None`` uses a self-cleaning temp
    directory; pass a path to keep/reuse the shards."""

    kind = "streamed"

    def __init__(self, root: str, meta: PackedClients, shard_clients: int,
                 requested_batch_size: Optional[int],
                 x_dtype, x_tail, y_dtype, y_tail,
                 shard_rows: Sequence[int], owns_root: bool):
        self.root = root
        self.meta = meta
        self.shard_clients = int(shard_clients)
        self.requested_batch_size = requested_batch_size
        self._x_dtype, self._x_tail = x_dtype, tuple(x_tail)
        self._y_dtype = y_dtype
        self._y_tail = tuple(y_tail) if y_tail is not None else None
        self._shard_rows = list(shard_rows)
        self._counts_i = meta.counts.astype(np.int64)
        self._tile = np.arange(self.n_pad)
        self._mmaps: "OrderedDict[str, np.ndarray]" = OrderedDict()
        if owns_root:
            self._cleanup = weakref.finalize(
                self, shutil.rmtree, root, ignore_errors=True
            )

    # -- construction ------------------------------------------------------

    @classmethod
    def build(cls, client_data, batch_size, *, shard_clients: int = 1024,
              root: Optional[str] = None) -> "StreamedClientPool":
        return cls.from_generator(
            iter(client_data), batch_size,
            shard_clients=shard_clients, root=root,
        )

    @classmethod
    def from_generator(
        cls,
        clients: Iterable[Tuple[np.ndarray, Optional[np.ndarray]]],
        batch_size,
        *,
        shard_clients: int = 1024,
        root: Optional[str] = None,
    ) -> "StreamedClientPool":
        """Stream clients into shards, holding at most ``shard_clients`` of
        them in RAM at once. Each shard pads to its OWN widest client (the
        global ``n_pad`` only exists once all counts are known; the gather
        tiles to it on read), and is flushed + unmapped immediately so the
        builder's RSS is one shard, not the population."""
        if shard_clients < 1:
            raise ValueError(f"shard_clients must be >= 1, got {shard_clients}")
        owns_root = root is None
        if root is None:
            root = tempfile.mkdtemp(prefix="repro-pool-")
        os.makedirs(root, exist_ok=True)

        counts: list = []
        shard_rows: list = []
        buf: list = []
        x_dtype = x_tail = y_dtype = y_tail = None
        shard_idx = 0

        def flush():
            nonlocal shard_idx, buf
            rows = max(len(x) for x, _ in buf)
            mx = np.lib.format.open_memmap(
                os.path.join(root, f"x{shard_idx:05d}.npy"), mode="w+",
                dtype=x_dtype, shape=(len(buf), rows) + x_tail,
            )
            my = None
            if y_dtype is not None:
                my = np.lib.format.open_memmap(
                    os.path.join(root, f"y{shard_idx:05d}.npy"), mode="w+",
                    dtype=y_dtype, shape=(len(buf), rows) + y_tail,
                )
            for j, (x, y) in enumerate(buf):
                mx[j, : len(x)] = x
                if my is not None:
                    my[j, : len(y)] = y
            # Flush + unmap NOW: dirty pages move to the page cache
            # instead of sitting in this process's RSS for the rest of
            # the build.
            mx.flush()
            del mx
            if my is not None:
                my.flush()
                del my
            shard_rows.append(rows)
            shard_idx += 1
            buf = []

        for x, y in clients:
            if x_dtype is None:
                x_dtype, x_tail = x.dtype, x.shape[1:]
                y_dtype = y.dtype if y is not None else None
                y_tail = y.shape[1:] if y is not None else None
            if (y is None) != (y_dtype is None):
                raise ValueError(
                    "streamed pool: every client must consistently have "
                    "(or not have) labels"
                )
            counts.append(len(x))
            buf.append((x, y))
            if len(buf) == shard_clients:
                flush()
        if buf:
            flush()
        if not counts:
            raise ValueError("streamed pool needs at least one client")
        meta = pool_metadata(np.asarray(counts, np.int64), batch_size)
        return cls(root, meta, shard_clients, batch_size,
                   x_dtype, x_tail, y_dtype, y_tail, shard_rows, owns_root)

    # -- reads -------------------------------------------------------------

    @property
    def has_labels(self) -> bool:
        return self._y_dtype is not None

    @property
    def feature_shape(self) -> Tuple[int, ...]:
        """One example's x shape: the tail of ``gather``'s (m, n_pad, ...)."""
        return tuple(int(d) for d in self._x_tail)

    @property
    def num_shards(self) -> int:
        return len(self._shard_rows)

    def nbytes_on_disk(self) -> int:
        return sum(
            os.path.getsize(os.path.join(self.root, f))
            for f in os.listdir(self.root)
        )

    def estimated_device_nbytes(self) -> int:
        """What the device-resident pack of this population would allocate
        — the number the ``pack_clients`` budget guard compares against."""
        return estimate_pool_nbytes(
            self._counts_i, self.requested_batch_size,
            self._x_tail, np.dtype(self._x_dtype).itemsize,
            self._y_tail, (np.dtype(self._y_dtype).itemsize
                           if self._y_dtype is not None else 0),
        )

    def _open(self, prefix: str, shard: int) -> np.ndarray:
        name = f"{prefix}{shard:05d}.npy"
        mm = self._mmaps.get(name)
        if mm is None:
            mm = np.load(os.path.join(self.root, name), mmap_mode="r")
            self._mmaps[name] = mm
            while len(self._mmaps) > _MMAP_CACHE_SLOTS:
                self._mmaps.popitem(last=False)
        else:
            self._mmaps.move_to_end(name)
        return mm

    def gather(self, ids) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Cohort rows by client id, tiled to the global ``n_pad`` with
        ``pack_clients``' exact rule (``rows[i % n_k]``) so the result is
        byte-identical to the device pool's ``x[ids]``."""
        ids = np.asarray(ids, np.int64)
        n_pad = self.n_pad
        x = np.empty((len(ids), n_pad) + self._x_tail, self._x_dtype)
        y = (
            np.empty((len(ids), n_pad) + self._y_tail, self._y_dtype)
            if self.has_labels else None
        )
        for j, cid in enumerate(ids):
            cid = int(cid)
            if not 0 <= cid < self.num_clients:
                raise IndexError(
                    f"client id {cid} out of range [0, {self.num_clients})"
                )
            shard, local = divmod(cid, self.shard_clients)
            n_k = int(self._counts_i[cid])
            tile = self._tile % n_k
            x[j] = self._open("x", shard)[local, :n_k][tile]
            if y is not None:
                y[j] = self._open("y", shard)[local, :n_k][tile]
        return x, y

    def iter_clients(self) -> Iterator[Tuple[np.ndarray, Optional[np.ndarray]]]:
        """The clients back out (real rows only, original order) — for
        tools that need to re-pack or re-shard."""
        for cid in range(self.num_clients):
            shard, local = divmod(cid, self.shard_clients)
            n_k = int(self._counts_i[cid])
            x = np.array(self._open("x", shard)[local, :n_k])
            y = (np.array(self._open("y", shard)[local, :n_k])
                 if self.has_labels else None)
            yield x, y
