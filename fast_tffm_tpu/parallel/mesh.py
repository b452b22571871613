"""Device mesh + sharding layout — the GSPMD replacement for the PS runtime.

The reference spreads its ``vocabulary_block_num`` table blocks across
parameter-server tasks and replicates workers (SURVEY.md §2 #5, #10).  Here
the same two axes become one 2-D ``jax.sharding.Mesh``:

- ``data``  — batch dimension (sync data parallelism; replaces async
  between-graph worker replication),
- ``model`` — table rows (replaces PS block partitioning).

All cross-chip traffic is XLA collectives over ICI/DCN inserted by GSPMD
from these shardings; there is no user-visible comms API (SURVEY.md §2
"Distributed communication backend").
"""

from __future__ import annotations

from collections import deque
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.models.fm import FmParams

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(
    cfg: FmConfig, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """Build the (data, model) mesh.

    ``mesh_data``/``mesh_model`` come from the config; if both are 1 and
    several devices are visible, all devices go to the data axis (pure DP),
    matching the reference default of one PS "block" per worker set.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    d, m = cfg.mesh_data, cfg.mesh_model
    if d * m == 1 and n > 1:
        d, m = n, 1
    if d * m > n:
        raise ValueError(f"mesh {d}x{m} needs {d * m} devices, have {n}")
    grid = np.array(devices[: d * m]).reshape(d, m)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


def param_sharding(mesh: Mesh) -> FmParams:
    """Table rows sharded over `model`, replicated over `data`."""
    return FmParams(
        w0=NamedSharding(mesh, P()),
        table=NamedSharding(mesh, P(MODEL_AXIS, None)),
    )


def batch_sharding(mesh: Mesh):
    """Batch arrays sharded over `data`, replicated over `model`.

    Returns a dict keyed like data.libsvm.Batch fields.
    """
    ex = NamedSharding(mesh, P(DATA_AXIS))
    feat = NamedSharding(mesh, P(DATA_AXIS, None))
    return {
        "labels": ex,
        "ids": feat,
        "vals": feat,
        "fields": feat,
        "weights": ex,
    }


def super_batch_sharding(mesh: Mesh):
    """Sharding for a stacked [K, ...] super-batch: the leading scan axis
    is replicated (every device steps through all K slices), the batch
    axis behind it shards over `data` exactly like a single batch.

    Returns a dict keyed like data.libsvm.Batch fields.
    """
    ex = NamedSharding(mesh, P(None, DATA_AXIS))
    feat = NamedSharding(mesh, P(None, DATA_AXIS, None))
    return {
        "labels": ex,
        "ids": feat,
        "vals": feat,
        "fields": feat,
        "weights": ex,
    }


def shard_params(params: FmParams, mesh: Mesh) -> FmParams:
    sh = param_sharding(mesh)
    return jax.tree.map(jax.device_put, params, sh)


def data_partition(mesh: Mesh) -> tuple[int, int]:
    """This process's (block_index, num_blocks) of the data-axis partition.

    Multi-host input sharding (SURVEY.md §7 hard-part 2): each process
    parses only its own slice of the global batch, so the data axis must
    partition across processes in equal contiguous blocks — true for the
    default jax.distributed device order (devices grouped by process) and
    this module's row-major (data, model) grid.  num_blocks is the number
    of distinct data blocks; processes that share a block (model-axis-
    spanning processes) read the same input shard.
    """
    import jax

    arr = mesh.devices  # [data, model] ndarray of Devices
    pid = jax.process_index()
    mine = [
        i for i in range(arr.shape[0])
        if any(d.process_index == pid for d in arr[i])
    ]
    if not mine:
        raise ValueError("this process owns no devices on the data axis")
    k = len(mine)
    n_data = arr.shape[0]
    if mine != list(range(mine[0], mine[0] + k)) or mine[0] % k or n_data % k:
        raise ValueError(
            "data-axis rows owned by this process must form an aligned "
            f"contiguous block (got rows {mine} of {n_data}); use the "
            "default device order or reshape the mesh so each process's "
            "devices are contiguous along the data axis"
        )
    return mine[0] // k, n_data // k


def shard_batch(batch, mesh: Mesh):
    """Ship a host batch to the mesh.

    Single-process: device_put each array with its (data, model) sharding.
    Multi-process: ``batch`` holds only this process's LOCAL slice
    (global_batch / num_blocks rows); the global array is assembled with
    ``jax.make_array_from_process_local_data`` — the GSPMD replacement for
    feeding per-worker input queues (SURVEY.md §3.2), with no host ever
    materializing the global batch.
    """
    sh = batch_sharding(mesh)
    core = ("labels", "ids", "vals", "fields", "weights")
    meta = getattr(batch, "sort_meta", None)
    if jax.process_count() > 1:
        _, num_blocks = data_partition(mesh)

        def put(x, s):
            x = np.asarray(x)
            global_shape = (x.shape[0] * num_blocks,) + x.shape[1:]
            return jax.make_array_from_process_local_data(s, x, global_shape)

        # Host sort-meta describes one process's local ids; it cannot be
        # assembled into a global batch (the producer never attaches it
        # multi-process, so this is just defensive).
        return type(batch)(
            *(put(getattr(batch, k), sh[k]) for k in core), sort_meta=None
        )
    if meta is not None:
        rep = NamedSharding(mesh, P())
        meta = type(meta)(*(jax.device_put(x, rep) for x in meta))
    return type(batch)(
        *(jax.device_put(getattr(batch, k), sh[k]) for k in core),
        sort_meta=meta,
    )


_CORE_LEAVES = ("labels", "ids", "vals", "fields", "weights")
_ALIGN = 128  # TPU/host DMA friendly; also keeps every view offset aligned


def fused_h2d_enabled(mesh: Mesh) -> bool:
    """Whether the fused stack+H2D ship path may run on this mesh.

    Structural gates are unconditional: the fused buffer is shipped as
    one replicated flat array and carved on-device, which only matches
    the classic per-leaf sharding semantics on a single-device,
    single-process mesh.  Within those gates the default is
    TPU-only — on CPU ``device_put`` is zero-copy, so fusing buys
    nothing and costs one extra unpack dispatch — overridable via
    ``FAST_TFFM_FUSED_H2D`` (1 forces on, 0 forces off: tests, and
    ``chip_smoke.py --rehearse`` on the CPU).
    """
    if mesh.size != 1 or jax.process_count() > 1:
        return False
    import os

    env = os.environ.get("FAST_TFFM_FUSED_H2D", "")
    if env == "0":
        return False
    if env == "1":
        return True
    from fast_tffm_tpu import platform

    return platform.is_tpu_backend()


class FusedShipper:
    """Stack K parsed batches and ship them device-side in ONE transfer.

    The classic transfer stage stacks K host batches into a [K, ...]
    super-batch (one np.stack per leaf) and then issues one
    ``device_put`` per leaf — 5-12 host-to-device DMAs per dispatch,
    each paying launch latency.  This path instead copies every leaf of
    every batch into a single contiguous uint8 staging buffer
    (128-byte-aligned segments), ships it with ONE ``device_put``, and
    carves the leaves back out on-device with a cached jitted unpack
    (static slice -> bitcast -> reshape; bitwise-exact, no arithmetic).
    The stack and the transfer fuse: the host-side np.stack writes land
    directly in the DMA source buffer.

    Calling the shipper returns the device Batch, or ``None`` to
    decline (empty group) — the caller falls back to the classic
    stack+put path.  ``sort_meta`` rides along iff every batch in the
    group carries it, mirroring :func:`...pipeline.stack_batches`.

    Staging buffers recycle through a small in-flight ring, blocking on
    the oldest transfer before reuse — except on CPU, where
    ``device_put`` is zero-copy (the device array ALIASES the host
    buffer) so reuse would corrupt in-flight data; there every ship
    allocates fresh.
    """

    def __init__(self, mesh: Mesh, depth: int = 2):
        self._mesh = mesh
        self._depth = max(1, depth)
        self._unpack_cache: dict = {}  # spec -> jitted unpack
        self._free: dict = {}  # total_bytes -> [np buffer, ...]
        self._inflight: deque = deque()  # (dev_buf, total_bytes, host_buf)
        self._reuse = jax.default_backend() != "cpu"
        self.ships = 0  # fused dispatches completed (observability)

    # -- spec -----------------------------------------------------------
    def _spec(self, group):
        """((name, dtype_str, per-batch shape), ...) for one group — the
        unpack cache key.  Meta leaves append after core iff present on
        every batch."""
        b = group[0]
        spec = [
            (n, str(getattr(b, n).dtype), getattr(b, n).shape)
            for n in _CORE_LEAVES
        ]
        if all(g.sort_meta is not None for g in group):
            for i, x in enumerate(b.sort_meta):
                spec.append((f"meta{i}", str(x.dtype), x.shape))
        return len(group), tuple(spec)

    @staticmethod
    def _layout(k, spec):
        """[(name, dtype, stacked shape, offset, nbytes), ...], total."""
        off = 0
        out = []
        for name, dt, shape in spec:
            dtype = np.dtype(dt)
            nbytes = int(np.prod((k,) + shape, dtype=np.int64)) * dtype.itemsize
            out.append((name, dtype, (k,) + shape, off, nbytes))
            off += -(-nbytes // _ALIGN) * _ALIGN
        return out, off

    def _unpack_fn(self, key):
        """Jitted buffer -> leaves carve for one (k, spec), cached."""
        fn = self._unpack_cache.get(key)
        if fn is not None:
            return fn
        import jax.numpy as jnp
        from jax import lax

        k, spec = key
        layout, _ = self._layout(k, spec)

        def unpack(buf):
            outs = []
            for _, dtype, shape, off, nbytes in layout:
                seg = buf[off:off + nbytes]
                jdt = jnp.dtype(dtype)
                if jdt.itemsize > 1:
                    seg = seg.reshape(-1, jdt.itemsize)
                seg = lax.bitcast_convert_type(seg, jdt)
                outs.append(seg.reshape(shape))
            return tuple(outs)

        fn = jax.jit(unpack)
        self._unpack_cache[key] = fn
        return fn

    def _acquire(self, total):
        bufs = self._free.get(total)
        if bufs:
            return bufs.pop()
        return np.empty(total, dtype=np.uint8)

    def _retire(self, dev_buf, total, host_buf):
        if not self._reuse:
            return  # CPU: dev_buf aliases host_buf; never recycle
        self._inflight.append((dev_buf, total, host_buf))
        while len(self._inflight) > self._depth:
            d, t, h = self._inflight.popleft()
            jax.block_until_ready(d)
            self._free.setdefault(t, []).append(h)

    def __call__(self, group):
        if not group:
            return None
        from fast_tffm_tpu.data import libsvm

        key = self._spec(group)
        k, spec = key
        layout, total = self._layout(k, spec)
        buf = self._acquire(total)
        n_core = len(_CORE_LEAVES)
        has_meta = len(spec) > n_core
        for i, (name, dtype, shape, off, nbytes) in enumerate(layout):
            view = buf[off:off + nbytes].view(dtype).reshape(shape)
            if i < n_core:
                cols = [getattr(b, name) for b in group]
            else:
                cols = [b.sort_meta[i - n_core] for b in group]
            if k == 1:
                np.copyto(view[0], cols[0])
            else:
                np.stack(cols, out=view)
        dev_buf = jax.device_put(buf, self._mesh.devices.flat[0])
        leaves = self._unpack_fn(key)(dev_buf)
        self._retire(dev_buf, total, buf)
        self.ships += 1
        meta = None
        if has_meta:
            meta = type(group[0].sort_meta)(*leaves[n_core:])
        return libsvm.Batch(*leaves[:n_core], sort_meta=meta)


def shard_super_batch(batch, mesh: Mesh):
    """Ship a stacked [K, batch, ...] super-batch to the mesh.

    Same contract as :func:`shard_batch` with a leading scan axis: the K
    axis is replicated, the batch axis shards over `data`.  Multi-process,
    ``batch`` holds this process's local slice on axis 1 and the global
    array is assembled without any host materializing the global batch.
    ``device_put`` is async, so calling this from a transfer thread
    overlaps the H2D copies with the previous super-batch's training.
    """
    sh = super_batch_sharding(mesh)
    core = ("labels", "ids", "vals", "fields", "weights")
    meta = getattr(batch, "sort_meta", None)
    if jax.process_count() > 1:
        _, num_blocks = data_partition(mesh)

        def put(x, s):
            x = np.asarray(x)
            global_shape = (
                x.shape[0], x.shape[1] * num_blocks
            ) + x.shape[2:]
            return jax.make_array_from_process_local_data(s, x, global_shape)

        # Host sort-meta is per-process-local (see shard_batch): never
        # assembled multi-process.
        return type(batch)(
            *(put(getattr(batch, k), sh[k]) for k in core), sort_meta=None
        )
    if meta is not None:
        rep = NamedSharding(mesh, P())
        meta = type(meta)(*(jax.device_put(x, rep) for x in meta))
    return type(batch)(
        *(jax.device_put(getattr(batch, k), sh[k]) for k in core),
        sort_meta=meta,
    )
