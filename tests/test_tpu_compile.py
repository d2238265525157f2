"""The archive's main-path kernels compile for a TPU v5e at real widths.

Interpret-mode tests cannot see what Mosaic refuses (unaligned blocks,
unsupported ops, too much VMEM), so these compile every kernel of the write
and read path for a *described* v5e chip — no chip is attached — at the
sizes of an 8-frame 720p GOP: a 4-shard stripe in the 32,768-row coder
bucket, whose sealed bodies are 8,192 rows of 128 words.  Each compile
takes a second or two; nothing runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import _hlo

T = 32768           # coder rows of a 720p GOP shard (4 MiB of codes)
S = 4               # data shards per stripe
R = T // 4          # sealed rows: T*128 bytes = T*32 words = T/4 rows
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # pragma: no cover - needs the TPU library
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    )
    assert used < HBM_BYTES, f"{used} bytes do not fit one v5e chip"
    return compiled.as_text()


def _launches(hlo: str, name: str) -> int:
    """Mosaic kernels named ``name`` in the compiled program."""
    return len(re.findall(rf"%{name}(\.\d+)? = .*tpu_custom_call", hlo))


def test_fused_seal_compiles(one_chip):
    from repro.kernels.fused.entropy_seal import entropy_seal_pallas

    u32, i32 = jnp.uint32, jnp.int32
    hlo = _compile(
        functools.partial(entropy_seal_pallas, n_shards=S, interpret=False),
        _spec(one_chip, (S, T, 128), jnp.int8),
        _spec(one_chip, (S, 1), i32),
        _spec(one_chip, (S, 8), u32),
        _spec(one_chip, (S, 3), u32),
        _spec(one_chip, (S, 1), u32),
    )
    for name in ("rans_histogram", "rans_encode", "seal_stripes"):
        assert _launches(hlo, name) == 1, name


def test_unseal_compiles(one_chip):
    from repro.kernels.seal.seal import unseal_stripe_pallas

    u32 = jnp.uint32
    hlo = _compile(
        functools.partial(unseal_stripe_pallas, interpret=False),
        _spec(one_chip, (S, R, 128), u32),
        _spec(one_chip, (S, 8), u32),
        _spec(one_chip, (S, 3), u32),
        _spec(one_chip, (S, 1), jnp.int32),
        _spec(one_chip, (S, 1), u32),
    )
    assert _launches(hlo, "unseal_stripes") == 1


def test_rans_decode_compiles(one_chip):
    from repro.kernels.entropy.rans import rans_decode_pallas, stream_word_cap

    hlo = _compile(
        functools.partial(rans_decode_pallas, rows=T, interpret=False),
        _spec(one_chip, (S, stream_word_cap(T)), jnp.uint16),
        _spec(one_chip, (S, 256), jnp.int32),
        _spec(one_chip, (S, 128), jnp.uint32),
        _spec(one_chip, (S, 1), jnp.int32),
    )
    assert _launches(hlo, "rans_decode") == 1


def test_motion_search_compiles(one_chip):
    from repro.kernels.motion.motion import block_motion_pallas

    block, radius = 16, 8
    hlo = _compile(
        functools.partial(block_motion_pallas, interpret=False),
        _spec(one_chip, (720, 1280), jnp.int32),
        _spec(one_chip, (720 + 2 * block, 1280 + 2 * radius), jnp.int32),
    )
    assert _launches(hlo, "motion_search") == 1


def test_kem_program_compiles(one_chip, monkeypatch):
    """The seal dispatch's KEM program: a chunk of RLWE encapsulations with
    one ring-product kernel for each of the public key's polynomials."""
    from repro.core.archival import pipeline
    from repro.core.crypto import rlwe
    from repro.kernels.polymul import ops as polymul_ops

    # the kernel picks interpret mode from the attached backend (the CPU
    # here): lower it as on the chip, and keep that trace from later tests
    monkeypatch.setattr(polymul_ops, "_use_interpret", lambda: False)
    jax.clear_caches()
    try:
        params = rlwe.RLWEParams()
        vec = _spec(one_chip, (params.n,), jnp.int32)
        compiled = pipeline._encapsulate_rows.lower(
            rlwe.PublicKey(vec, vec),
            [_spec(one_chip, (2,), jnp.uint32)] * pipeline.KEM_ROWS,
            _spec(one_chip, (pipeline.KEM_ROWS,), jnp.uint32),
            params=params,
        ).compile()
    finally:
        jax.clear_caches()
    assert _launches(compiled.as_text(), "polymul_fixed") == 2


def test_mesh_write_program_compiles(topo):
    """The four-CSD write program on a 2x2 mesh: one launch of each write
    kernel per chip over its own shard of K = 4 stripes, the codes placed
    shard by shard, and the parity partials' all-gather the only
    collective."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.distributed.archival import _mesh_write_program

    K = 4
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    placed = NamedSharding(mesh, P(None, "data"))
    everywhere = NamedSharding(mesh, P())
    u32 = jnp.uint32
    program = _mesh_write_program(mesh, "data", S, "raid6", True, False)
    compiled = program.lower(
        _spec(placed, (K, S, T, 128), jnp.int8),
        _spec(everywhere, (K * S, 1), jnp.int32),
        _spec(everywhere, (K * S, 8), u32),
        _spec(everywhere, (K * S, 3), u32),
        _spec(everywhere, (K * S, 1), u32),
    ).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used} bytes do not fit one v5e chip"
    hlo = compiled.as_text()
    for name in ("rans_histogram", "rans_encode", "seal_stripes"):
        assert _launches(hlo, name) == 1, name
    ops = set(re.findall(r"= \S+ ([a-z-]+)\(", hlo))
    assert {"all-gather", "all-gather-start"} & ops, ops
    assert not {"all-reduce", "all-to-all", "collective-permute",
                "reduce-scatter"} & ops, ops
    # the only collectives are the P and Q partials' all-gathers: each chip
    # gives its (K, R, 128) words of one strip, and receives the other
    # three chips' -- nothing of the codes or the sealed rows crosses
    D = len(topo.devices)
    gathers = _hlo.all_gathers(hlo)
    assert len(gathers) == 2, gathers
    for g in gathers:
        assert g.dtype == "u32" and g.group == D, g
        assert _hlo.squeezed(g.operand) == (K, R, 128), g
    assert _hlo.received_bytes(hlo, D) == D * (D - 1) * 2 * K * R * 128 * 4
