"""What the loops share: the closed loop's step, the warm-up of
every write shape, and the comparison of acknowledged stripes with the
plain reference.

The program is driven only through its served entries
(``StreamIngestFrontend.offer/pump/drain``, ``ArchiveIngest.restore``)
and, for the warm-up, the public batched seal the frontend's ring uses.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import reference  # noqa: E402

__all__ = ["gop_key", "offer_or_pump", "warm_write_shapes",
           "check_acknowledged"]


def manifest(g: int, nbytes: int) -> Dict:
    """The packing manifest a pre-encoded GOP carries; ``g`` is the
    benchmark's own tag, read back from restored blocks."""
    return {"spec": [], "n_i8": int(nbytes), "g": int(g)}


def gop_key(stream: int, novelty: float, nbytes: int) -> Tuple:
    """How the journal's catalog record names a GOP: the novelty is a
    seeded float per GOP, so (stream, novelty, bytes) is unique."""
    return (int(stream), round(float(novelty), 9), int(nbytes))


def offer_or_pump(run, front, g: int, budget: int):
    """One turn of the backpressure loop: offer GOP ``g`` when it fits
    under the frontend's byte budget, so admission never sheds; else pump.
    Returns (next GOP index, stripes committed this turn)."""
    w = run.workload
    gop = w.gop(g)
    if front.queue_bytes + gop.nbytes <= budget:
        with run.span("offer"):
            front.offer(gop.stream, w.payload(g), manifest(g, gop.nbytes),
                        novelty=gop.novelty, feature=w.features[g])
        return g + 1, []
    with run.span("pump"):
        return g, front.pump()


def _extremes(sizes: Iterable[int], bucket_of) -> Dict[int, Tuple[int, int]]:
    """Per coalescer row bucket, the smallest and largest GOP size."""
    out: Dict[int, Tuple[int, int]] = {}
    for n in sorted(set(int(s) for s in sizes)):
        r = bucket_of(n)
        lo, hi = out.get(r, (n, n))
        out[r] = (min(lo, n), max(hi, n))
    return out


def warm_write_shapes(run) -> int:
    """Seal one batch of every (stripes per launch, shards, row bucket)
    the cell's traffic can form, so that every write program and every
    slice of its output is built before the window.  Full stripes come up
    to ``batch_stripes`` to a launch; partial stripes (straggler drains,
    the final flush) one or two.

    The output is sliced per stripe to the row bucket of its stored
    (compressed) words, so each row bucket is sealed at its smallest GOP,
    and again at its largest only where that stores into another bucket
    of rows: a full stripe at the largest GOP is sealed first, and the
    rest follow only if its stored rows differ.  Returns the launches
    made."""
    import jax

    from repro.distributed.archival import (
        CoalescedStripe, PendingGOP, seal_coalesced_stripes)
    from repro.kernels.seal.ops import bucket_rows_for

    w = run.workload
    pub, _ = run.keys()
    cfg = run.ingest_config()
    S_full = int(run.cfg["data_shards"])
    K_max = int(run.cfg["frontend"]["batch_stripes"])
    combos = [(S_full, k) for k in range(1, K_max + 1)]
    combos += [(s, k) for s in range(1, S_full) for k in (1, 2)]
    pool = w.pool
    launches = 0

    def seal(r, n, S, K):
        batch = [
            CoalescedStripe(
                [PendingGOP(s, pool[(k * S + s) % pool.shape[0], :n],
                            manifest(-1, n), {})
                 for s in range(S)], r)
            for k in range(K)
        ]
        keys = [jax.random.fold_in(jax.random.PRNGKey(7), i)
                for i in range(K)]
        out = seal_coalesced_stripes(pub, batch, keys, cfg.archive)
        return out[0].parity["pad_to"] if out[0].parity else None

    for r, (lo, hi) in sorted(_extremes(
            w.sizes, lambda n: bucket_rows_for(-(-n // 4))).items()):
        stored = {}
        for S, K in combos:
            stored[S, K] = seal(r, lo, S, K)
            launches += 1
        if hi == lo:
            continue
        launches += 1
        if seal(r, hi, S_full, 1) == stored[S_full, 1]:
            continue
        for S, K in combos[1:]:
            seal(r, hi, S, K)
            launches += 1
    return launches


def stripe_gops(stripe) -> List[int]:
    return [int(b.manifest["g"]) for b in stripe.blocks]


def _unsynced(events: List[Tuple[int, str]], jdir: str, sid: Optional[str],
              t_ack: int) -> bool:
    """True unless, before the stripe was acknowledged at ``t_ack``, its
    catalog record file was fsynced, and the journal log and the journal
    directory were each fsynced after that.  ``events`` are the fsyncs as
    (return time, real path); ``jdir`` is the journal's real path.  One
    log or directory fsync may cover several records, so a group commit
    passes."""
    if sid is None:
        return True
    rec = os.path.join(jdir, f"catalog_{sid}.json")
    t_rec = [t for t, path in events
             if t <= t_ack and path in (rec, rec + ".tmp")]
    if not t_rec:
        return True
    after = {path for t, path in events if min(t_rec) <= t <= t_ack}
    return not {os.path.join(jdir, "journal.jsonl"), jdir} <= after


def check_acknowledged(run, ingest, jdir: str, acknowledged: List,
                       ack_ns: List[int], offered: Iterable[int],
                       sample: List) -> Tuple[Dict[str, float], int]:
    """Compare what the archive acknowledged with the reference.

    ``acknowledged``: every stripe the frontend committed, and ``ack_ns``
    when each was handed back; ``offered``: the GOP indices offered;
    ``sample``: the acknowledged stripes to read back in full.  Returns
    the numbers compared:

    * ``gops_lost``: offered GOPs not in exactly one acknowledged stripe;
    * ``gops_unjournaled``: acknowledged GOPs with no catalog record in
      the journal directory;
    * ``stripes_unsynced``: acknowledged stripes whose catalog record was
      not fsynced, with the journal log and directory after it, before
      the acknowledgement;
    * ``bodies_mismatched``: GOPs of the sampled stripes whose stored body
      does not open, by the reference alone (its own KEM secret, ChaCha20,
      rANS decode), to the offered bytes;
    * ``stored_pct``: the sampled stripes' stored body bytes over their
      offered bytes, in percent;
    * ``gops_mismatched``: GOPs of the sampled stripes that do not read
      back, through the program's ``restore``, as the offered bytes;
    * ``parity_mismatched``: sampled stripes whose stored P/Q are not the
      reference's over their stored bodies.
    """
    w = run.workload
    _, sk = run.keys()
    count: Dict[int, int] = {}
    for st in acknowledged:
        for g in stripe_gops(st):
            count[g] = count.get(g, 0) + 1
    offered = list(offered)
    lost = sum(1 for g in offered if count.get(g, 0) != 1)
    lost += sum(1 for g, c in count.items() if c != 1 or g < 0)

    journal = reference.journal_gops(jdir)
    by_key = {k: sid for sid, keys in journal.items() for k in keys}

    def sid_of(g: int) -> Optional[str]:
        gop = w.gop(g)
        return by_key.get(gop_key(gop.stream, gop.novelty, gop.nbytes))

    unjournaled = sum(1 for g in count if sid_of(g) is None)
    real = os.path.realpath(jdir)
    events = [(t, path) for t, path, _ in run.syncs.events]
    unsynced = [st for st, t in zip(acknowledged, ack_ns)
                if _unsynced(events, real, sid_of(stripe_gops(st)[0]), t)]

    shards, stored, offered_bytes = [], 0, 0
    for st in sample:
        for b in st.blocks:
            body = np.asarray(b.sealed.body)
            n = int(w.gop(int(b.manifest["g"])).nbytes)
            shards.append({"body": body, "c1": np.asarray(b.sealed.kem_c1),
                           "c2": np.asarray(b.sealed.kem_c2),
                           "nonce": np.asarray(b.sealed.nonce), "n_bytes": n})
            stored += 4 * body.size
            offered_bytes += n
    opened = reference.open_bodies(shards, run.secret,
                                   int(run.cfg["kem"]["modulus_q"]))
    bodies_bad = 0
    gs_sampled = [int(b.manifest["g"]) for st in sample for b in st.blocks]
    for g, got in zip(gs_sampled, opened):
        if got is None or not np.array_equal(got, w.payload(g).view(np.uint8)):
            bodies_bad += 1

    mismatched = parity_bad = 0
    for st in sample:
        gs = stripe_gops(st)
        bodies = [np.asarray(b.sealed.body) for b in st.blocks]
        parity_bad += reference.parity_mismatch(
            bodies, st.parity, run.cfg["parity"])
        sid = sid_of(gs[0])
        if sid is None:
            mismatched += len(gs)
            continue
        try:
            got, blocks = ingest.restore(sk, sid)
        except ValueError as e:  # the program's own parity check refused
            run.log(f"restore of {sid} failed: {e}")
            mismatched += len(gs)
            continue
        for payload, b in zip(got, blocks):
            g = int(b.manifest["g"])
            if not np.array_equal(np.asarray(payload).reshape(-1),
                                  w.payload(g)):
                mismatched += 1
        mismatched += max(0, len(gs) - len(got))
    counts = {"gops_lost": lost, "gops_unjournaled": unjournaled,
              "stripes_unsynced": len(unsynced),
              "bodies_mismatched": bodies_bad,
              "stored_pct": 100.0 * stored / max(offered_bytes, 1),
              "gops_mismatched": mismatched, "parity_mismatched": parity_bad}
    failed = (lost + unjournaled + sum(len(st.blocks) for st in unsynced)
              + bodies_bad + mismatched)
    return counts, failed


def draw_sample(run, items: List, n: int, must: Optional[List] = None) -> List:
    """``n`` items drawn from the seed, plus ``must`` (kept first)."""
    rng = np.random.default_rng([run.seed, 0x5A3])
    must = list(must or [])
    rest = [x for x in items if not any(x is m for m in must)]
    pick = rng.choice(len(rest), size=min(n, len(rest)), replace=False)
    return must + [rest[i] for i in sorted(pick)]
