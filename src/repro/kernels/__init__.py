"""Pallas kernel packages for the paper's compute hot-spots.

Each kernel lives in its own package as ``<name>.py`` (the Pallas kernel),
``ref.py`` (a pure-jnp oracle the kernel must match bit-for-bit), and
``ops.py`` (jit'd public wrappers handling padding and dispatch).

Kernels: ``polymul`` (R-LWE negacyclic matmul, MXU), ``motion`` (block
matching, VPU), ``quantize`` (blockwise int8, VPU), ``entropy``
(interleaved-rANS byte coder, 128 lanes on the VPU lane axis), ``seal``
(fused archival pack + ChaCha20 + XOR-seal + RAID parity, VPU).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "use_interpret", "as_payload_list", "host_rows", "stack_rows",
    "axis_devices", "host_blocks", "host_prefixes", "le_words",
]


def use_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas ``interpret=`` autodetect shared by every kernel ``ops`` module.

    Off-TPU backends (CPU/GPU hosts, CI) run kernels through the Pallas
    interpreter; on TPU the same call sites lower to real Mosaic kernels.
    Pass an explicit bool to override (tests / debugging).
    """
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def as_payload_list(payloads) -> List[jax.Array]:
    """Normalize ragged stripe payloads (list/tuple or stacked (S, N) array)
    to a list of flat int8 arrays — shared by the seal and entropy ops."""
    if isinstance(payloads, (list, tuple)):
        # already-normalized payloads (the hot path) pass through without
        # paying a per-shard reshape/astype dispatch; host payloads stay on
        # the host until they are staged into a launch
        return [
            p
            if isinstance(p, (jax.Array, np.ndarray))
            and p.dtype == jnp.int8 and p.ndim == 1
            else jnp.asarray(p).reshape(-1).astype(jnp.int8)
            for p in payloads
        ]
    arr = jnp.asarray(payloads)
    return [arr[s].reshape(-1).astype(jnp.int8) for s in range(arr.shape[0])]


def host_rows(flats: Sequence, rows: int, width: int = 128,
              dtype=np.uint32) -> np.ndarray:
    """Ragged flat arrays -> one zero-padded (S, rows, width) host array."""
    host = np.zeros((len(flats), rows * width), dtype)
    for s, f in enumerate(flats):
        v = np.asarray(f).reshape(-1).view(dtype)
        host[s, : v.shape[0]] = v
    return host.reshape(len(flats), rows, width)


def stack_rows(flats: Sequence, rows: int, width: int = 128,
               dtype=np.uint32) -> jax.Array:
    """Ragged flat arrays -> one zero-padded (S, rows, width) device array.

    Staged on the host with one transfer: a device-side pad per ragged
    length would compile a program per distinct size."""
    return jax.device_put(host_rows(flats, rows, width, dtype))


def axis_devices(arr: jax.Array, axis: int) -> Optional[List]:
    """The device holding each index of ``arr`` along ``axis``, where the
    array is split along it over several devices; None for an array that
    one device holds."""
    sharding = arr.sharding
    if len(sharding.device_set) == 1:
        return None
    n = arr.shape[axis]
    owners: List = [None] * n
    for dev, idx in sharding.devices_indices_map(arr.shape).items():
        for i in range(*idx[axis].indices(n)):
            owners[i] = dev
    return owners


def host_blocks(arr: jax.Array, axis: int) -> List[np.ndarray]:
    """``arr`` on the host as the blocks its devices hold along ``axis``, in
    order along it: each device's own host copy, not one array assembled
    from them.  Each is read from the array object whose host copy
    ``arr.copy_to_host_async()`` started: ``arr`` itself where every device
    holds all of it (one device), else its per-device shards."""
    if arr.is_fully_replicated:
        return [np.asarray(arr)]
    first = {}
    for shard in arr.addressable_shards:
        start = shard.index[axis].indices(arr.shape[axis])[0]
        first.setdefault(start, shard.data)
    return [np.asarray(first[k]) for k in sorted(first)]


def host_prefixes(arr, lengths: Sequence[int]) -> List[np.ndarray]:
    """Row s of a device array, flattened and cut to ``lengths[s]``, as host
    arrays from ONE fetch (no per-length device program)."""
    host = np.asarray(arr).reshape(len(lengths), -1)
    return [host[s, :n] for s, n in enumerate(lengths)]


def le_words(x, k: int) -> jax.Array:
    """(B, m) little-endian parts of 32/k bits each -> (B, ceil(m/k)) u32
    words (zero-filled).  Strided lane slices: a reshape to (B, m/k, k)
    would give XLA a k-wide minor axis, which it lays out lane-padded and
    compiles very slowly for a TPU."""
    x = jnp.pad(x.astype(jnp.uint32), ((0, 0), (0, -x.shape[1] % k)))
    out = x[:, 0::k]
    for i in range(1, k):
        out = out | (x[:, i::k] << jnp.uint32(32 // k * i))
    return out
