"""Fault-tolerant checkpointing through the Salient Store archival pipeline.

Checkpoints are archival data: each save is chunked into S logical storage
shards (stripe tiles) and pushed through the SAME archival write program
as the video archive (``repro.kernels.fused``): interleaved-rANS entropy
coding + stream pack + ChaCha20 + XOR + RAID-5 P / RAID-6 Q in one device
program over the stripe (``codec_name="zstd"``/``"zlib"`` keeps the
host codec + chained ``repro.kernels.seal`` as a fallback).  With a ``seal_key``
the per-shard ChaCha session keys are R-LWE-KEM-encapsulated (true
encryption, secret needed to restore); without one they are stored in the
manifest — whitening only, but the datapath and on-disk layout stay
identical, so the parity tier is always exercised.  Shards are committed
through the power-loss-safe ``Journal`` (write payload -> fsync -> manifest
record).  Restore tolerates:

  * torn writes (journal replay drops them),
  * up to two missing/corrupt shards per checkpoint (parity rebuild over the
    sealed bodies, then one fused unseal of the repaired stripe — the same
    recompute-and-compare integrity check the archive restore uses),
  * a different mesh on restart (elastic: arrays are saved unsharded-logical
    and resharded by the caller's NamedShardings at load).
"""

from __future__ import annotations

import io
import json
import os
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import compress as entropy
from repro.core.archival import raid
from repro.core.crypto import rlwe
from repro.core.crypto.hybrid import encapsulate_session
from repro.core.csd.failure import Journal
from repro.kernels.entropy import ops as entropy_ops
from repro.kernels.fused import ops as fused_ops
from repro.kernels.seal import ops as seal_ops

__all__ = ["save_checkpoint", "load_checkpoint", "load_checkpoint_meta",
           "latest_step", "CheckpointError"]


class CheckpointError(RuntimeError):
    pass


def _serialize_tree(tree) -> bytes:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    buf = io.BytesIO()
    np.savez(
        buf,
        __treedef__=np.frombuffer(str(treedef).encode(), dtype=np.uint8),
        **{f"leaf_{i}": np.asarray(l) for i, l in enumerate(leaves)},
    )
    return buf.getvalue()


def _deserialize_leaves(blob: bytes) -> List[np.ndarray]:
    buf = io.BytesIO(blob)
    with np.load(buf) as z:
        n = sum(1 for k in z.files if k.startswith("leaf_"))
        return [z[f"leaf_{i}"] for i in range(n)]


def _session_material(
    meta: Dict[str, Any],
    n_shards: int,
    step: int,
    seal_key: Optional[rlwe.PublicKey],
    rng: Optional[jax.Array],
) -> Tuple[jax.Array, jax.Array]:
    """(S, 8) uint32 ChaCha keys + (S, 3) nonces for the stripe launch.

    Sealed: fresh per-shard session keys under the lattice KEM (ciphertexts
    into the manifest, keys never stored).  Unsealed: manifest-stored
    whitening keys — restore needs no secret and the kernel path is shared.
    """
    if seal_key is not None:
        if rng is None:
            rng = jax.random.PRNGKey(step)
        mats = [
            encapsulate_session(seal_key, jax.random.fold_in(rng, i))
            for i in range(n_shards)
        ]
        meta["kem_c1"] = [np.asarray(m.kem_c1).tolist() for m in mats]
        meta["kem_c2"] = [np.asarray(m.kem_c2).tolist() for m in mats]
        meta["nonce"] = [np.asarray(m.nonce).tolist() for m in mats]
        return (
            jnp.stack([m.session for m in mats]),
            jnp.stack([m.nonce for m in mats]),
        )
    rk = np.random.default_rng(step)
    keys = rk.integers(0, 2**32, (n_shards, 8), dtype=np.uint32)
    nonces = rk.integers(0, 2**32, (n_shards, 3), dtype=np.uint32)
    meta["keys"] = keys.tolist()
    meta["nonce"] = nonces.tolist()
    return jnp.asarray(keys), jnp.asarray(nonces)


def save_checkpoint(
    root: str,
    step: int,
    state: Any,
    *,
    n_shards: int = 4,
    parity: str = "raid6",
    seal_key: Optional[rlwe.PublicKey] = None,
    rng: Optional[jax.Array] = None,
    zstd_level: int = 3,
    codec_name: str = "rans",
    extra_meta: Optional[Dict[str, Any]] = None,
) -> Dict:
    """state: arbitrary pytree (params/opt/extra). Returns the manifest.

    ``codec_name="rans"`` (default) chunks the RAW serialized tree into S
    shards and runs the entropy stage on-device, chained straight into the
    fused seal launch — the checkpoint bytes never visit a host codec.
    ``"zstd"``/``"zlib"`` keeps the legacy host path (must match what this
    host's ``repro.common.compress`` actually provides).

    ``extra_meta``: JSON-able caller payload stored under ``meta["extra"]``
    — the trainer persists its exemplar centroids here so novelty scoring
    (and catalog queries) survive a restart instead of re-learning the
    known distribution from scratch.  Read it back with
    ``load_checkpoint_meta``.
    """
    j = Journal(root)
    raw = _serialize_tree(state)

    meta: Dict[str, Any] = {
        "step": int(step),
        "n_shards": n_shards,
        "parity": parity,
        "raw_len": len(raw),
        "sealed": bool(seal_key is not None),
        "codec": codec_name,
        "extra": extra_meta or {},
    }

    if codec_name == "rans":
        # chunk the RAW payload into S stripe tiles; entropy + seal run as
        # one on-device program (repro.kernels.fused) — the checkpoint
        # bytes never visit a host codec.
        # Big states grow the shard count so each tile stays inside the
        # coder's per-shard bound (entropy_ops.MAX_ROWS rows of 128 lanes)
        # instead of failing the encode launch.
        max_shard = entropy_ops.MAX_ROWS * 128
        n_shards = max(n_shards, -(-len(raw) // max_shard))
        meta["n_shards"] = n_shards
        shard_len = (len(raw) + n_shards - 1) // n_shards
        padded = raw + b"\0" * (shard_len * n_shards - len(raw))
        flats = [
            jnp.asarray(
                np.frombuffer(
                    padded[i * shard_len : (i + 1) * shard_len], np.int8
                )
            )
            for i in range(n_shards)
        ]
        meta["shard_len"] = shard_len
        keys, nonces = _session_material(meta, n_shards, step, seal_key, rng)
        stripe, emetas = fused_ops.entropy_seal_stripe(
            flats, keys, nonces, parity=parity
        )
        meta["entropy"] = emetas
        meta["comp_len"] = sum(m["n_comp"] for m in emetas)
    else:
        try:
            comp = entropy.compress_as(codec_name, raw, level=zstd_level)
        except ValueError as e:
            raise CheckpointError(f"host entropy codec: {e}") from e
        meta["comp_len"] = len(comp)
        shard_len = (len(comp) + n_shards - 1) // n_shards
        padded = comp + b"\0" * (shard_len * n_shards - len(comp))
        flats = [
            jnp.asarray(
                np.frombuffer(padded[i * shard_len : (i + 1) * shard_len], np.int8)
            )
            for i in range(n_shards)
        ]
        meta["shard_len"] = shard_len
        keys, nonces = _session_material(meta, n_shards, step, seal_key, rng)
        stripe = seal_ops.seal_stripe(flats, keys, nonces, parity=parity)
    meta["n_words"] = [int(n) for n in stripe.n_words]
    meta["pad_words"] = int(stripe.pad_words)

    names = []
    for i in range(n_shards):
        name = f"ckpt_{step:08d}_shard{i}.bin"
        body = np.asarray(stripe.body(i)).astype("<u4").tobytes()
        j.commit(name, body, {"step": step, "shard": i})
        names.append(name)
    if parity != "none":
        p_u8 = np.asarray(
            jax.lax.bitcast_convert_type(stripe.p, jnp.uint8)
        ).reshape(-1)
        j.commit(f"ckpt_{step:08d}_parity_p.bin", p_u8.tobytes(), {"step": step})
        if stripe.q is not None:
            q_u8 = np.asarray(
                jax.lax.bitcast_convert_type(stripe.q, jnp.uint8)
            ).reshape(-1)
            j.commit(
                f"ckpt_{step:08d}_parity_q.bin", q_u8.tobytes(), {"step": step}
            )
    meta["shards"] = names
    j.commit(f"ckpt_{step:08d}_manifest.json", json.dumps(meta).encode(), {"step": step})
    return meta


def load_checkpoint_meta(root: str, step: Optional[int] = None) -> Dict:
    """The manifest of a checkpoint (``step=None`` -> latest) WITHOUT
    decoding the stripe — the host-metadata tier (incl. ``extra``)."""
    j = Journal(root)
    if step is None:
        step = latest_step(root)
        if step is None:
            raise CheckpointError(f"no checkpoint in {root}")
    return json.loads(j.read(f"ckpt_{step:08d}_manifest.json"))


def latest_step(root: str) -> Optional[int]:
    j = Journal(root)
    steps = [
        r["meta"]["step"]
        for r in j.replay()
        if r["name"].endswith("_manifest.json") and "step" in r.get("meta", {})
    ]
    return max(steps) if steps else None


def _read_bodies(
    j: Journal, root: str, meta: Dict
) -> Tuple[List[Optional[bytes]], List[int]]:
    bodies: List[Optional[bytes]] = []
    missing: List[int] = []
    for i, name in enumerate(meta["shards"]):
        path = os.path.join(root, name)
        want = 4 * meta["n_words"][i]
        if os.path.exists(path) and os.path.getsize(path) == want:
            bodies.append(j.read(name))
        else:
            bodies.append(None)
            missing.append(i)
    return bodies, missing


def _rebuild_missing(
    j: Journal, meta: Dict, bodies: List[Optional[bytes]], missing: List[int]
) -> List[bytes]:
    """Parity-rebuild lost sealed bodies (host RAID math over u8 rows)."""
    step, pad_u8 = meta["step"], 4 * meta["pad_words"]
    rows: List[Optional[jnp.ndarray]] = [
        None
        if b is None
        else jnp.asarray(np.frombuffer(b.ljust(pad_u8, b"\0"), np.uint8))
        for b in bodies
    ]
    p = jnp.asarray(
        np.frombuffer(j.read(f"ckpt_{step:08d}_parity_p.bin"), np.uint8)
    )
    if meta["parity"] == "raid5":
        if len(missing) != 1:
            raise CheckpointError(
                f"shards {missing} lost; RAID-5 covers one erasure"
            )
        rows[missing[0]] = raid.raid5_reconstruct(rows, p, missing[0])
    else:
        q = jnp.asarray(
            np.frombuffer(j.read(f"ckpt_{step:08d}_parity_q.bin"), np.uint8)
        )
        rows = raid.raid6_reconstruct(rows, p, q, missing)
    return [
        bytes(np.asarray(r))[: 4 * meta["n_words"][i]]
        for i, r in enumerate(rows)
    ]


def _stripe_keys(meta: Dict, secret: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    nonces = jnp.asarray(meta["nonce"], jnp.uint32)
    if meta["sealed"]:
        if secret is None:
            raise CheckpointError("checkpoint is sealed; need the R-LWE secret")
        keys = jnp.stack(
            [
                rlwe.kem_decapsulate(
                    secret,
                    rlwe.Ciphertext(
                        jnp.asarray(meta["kem_c1"][i], jnp.int32),
                        jnp.asarray(meta["kem_c2"][i], jnp.int32),
                    ),
                )
                for i in range(len(meta["shards"]))
            ]
        )
    else:
        keys = jnp.asarray(meta["keys"], jnp.uint32)
    return keys, nonces


def _verify_stripe_parity(j: Journal, meta: Dict, p2, q2) -> None:
    step = meta["step"]
    for name, got in (("p", p2), ("q", q2)):
        if got is None:
            continue
        want = np.frombuffer(
            j.read(f"ckpt_{step:08d}_parity_{name}.bin"), np.uint8
        )
        got_u8 = np.asarray(
            jax.lax.bitcast_convert_type(got, jnp.uint8)
        ).reshape(-1)
        if not np.array_equal(got_u8, want):
            raise CheckpointError(
                f"checkpoint parity mismatch on {name.upper()} "
                f"(corrupt shard beyond what erasure coding can see)"
            )


def load_checkpoint(
    root: str,
    template: Any,
    step: Optional[int] = None,
    *,
    secret: Optional[jax.Array] = None,
    shardings: Any = None,
) -> Tuple[int, Any]:
    """Restore into the structure of ``template``; reshard with ``shardings``
    (a matching pytree of NamedSharding) if given — elastic restarts pass the
    NEW mesh's shardings here."""
    j = Journal(root)
    if step is None:
        step = latest_step(root)
        if step is None:
            raise CheckpointError(f"no checkpoint in {root}")
    meta = json.loads(j.read(f"ckpt_{step:08d}_manifest.json"))
    if "n_words" not in meta:
        raise CheckpointError(
            f"checkpoint at step {step} predates the fused-kernel stripe "
            "format (manifest has no 'n_words'); re-save it with this version"
        )

    bodies, missing = _read_bodies(j, root, meta)
    if missing:
        if meta["parity"] == "none":
            raise CheckpointError(f"shards {missing} lost and no parity")
        bodies = _rebuild_missing(j, meta, bodies, missing)

    # one fused unseal of the whole stripe (keystream + XOR + unpack), with
    # parity recomputed from the bodies as stored for the integrity check
    keys, nonces = _stripe_keys(meta, secret)
    n_words = tuple(meta["n_words"])
    R = meta["pad_words"] // seal_ops.LANES
    sealed = jnp.stack(
        [
            jnp.pad(
                jnp.asarray(np.frombuffer(b, "<u4").copy()), (0, R * seal_ops.LANES - n)
            ).reshape(R, seal_ops.LANES)
            for b, n in zip(bodies, n_words)
        ]
    )
    ckpt_codec = meta.get("codec", "zstd")
    if ckpt_codec == "rans":
        n_i8 = tuple(m["n_comp"] for m in meta["entropy"])
    else:
        n_i8 = (meta["shard_len"],) * len(bodies)
    packed = seal_ops.SealedStripe(sealed, None, None, n_words, n_i8)
    flats, p2, q2 = seal_ops.unseal_stripe(
        packed, keys, nonces, parity=meta["parity"]
    )
    if meta["parity"] != "none":
        _verify_stripe_parity(j, meta, p2, q2)

    if ckpt_codec == "rans":
        # on-device entropy decode of the unsealed streams, then reassemble
        raws = entropy_ops.decode_payloads(flats, meta["entropy"])
        raw = b"".join(np.asarray(f, np.int8).tobytes() for f in raws)
        raw = raw[: meta["raw_len"]]
    else:
        payload = b"".join(np.asarray(f, np.int8).tobytes() for f in flats)
        payload = payload[: meta["comp_len"]]
        try:
            raw = entropy.decompress_as(
                ckpt_codec, payload, max_output_size=meta["raw_len"]
            )
        except ValueError as e:
            raise CheckpointError(
                f"checkpoint was written with {ckpt_codec!r}: {e}"
            ) from e
    leaves = _deserialize_leaves(raw)
    t_leaves, treedef = jax.tree_util.tree_flatten(template)
    if len(leaves) != len(t_leaves):
        raise CheckpointError(
            f"leaf count mismatch: ckpt {len(leaves)} vs template {len(t_leaves)}"
        )
    arrays = [jnp.asarray(l).astype(t.dtype) for l, t in zip(leaves, t_leaves)]
    if shardings is not None:
        s_leaves = jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda x: hasattr(x, "mesh")
        )
        arrays = [jax.device_put(a, s) for a, s in zip(arrays, s_leaves)]
    return step, jax.tree_util.tree_unflatten(treedef, arrays)
