"""One benchmark function per paper table/figure (Salient Store §5).

Measured numbers run the real JAX implementations on this host; model-derived
numbers come from the calibrated cost model (core/csd/costmodel.py) whose
parameters reproduce the paper's published ratios — each row's ``derived``
column names the paper target so EXPERIMENTS.md can report model-vs-paper
error.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, smooth_clip, timeit
from repro.core.csd import costmodel as cm

GB = 1e9


# ------------------------------------------------------------------ Table 1
def table1_resource() -> List[Row]:
    """Resource profile of archival algorithms (paper Table 1 analogue):
    measured time per MiB on this host for each pipeline stage."""
    from repro.common import compress as entropy
    from repro.core.archival import raid
    from repro.core.crypto import rlwe
    from repro.core.crypto.chacha import xor_stream
    from repro.core.crypto.hybrid import bytes_to_u32

    rows: List[Row] = []
    rng = np.random.default_rng(0)
    mib = rng.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes()
    words = bytes_to_u32(mib)

    pub, s = rlwe.keygen(jax.random.PRNGKey(0))
    key8 = jnp.arange(8, dtype=jnp.uint32)
    nonce = jnp.ones(3, jnp.uint32)

    us = timeit(lambda: xor_stream(key8, nonce, words))
    rows.append(("table1/encrypt_chacha20_per_MiB", us, "bulk layer of RSA512 row"))
    m = jax.random.bernoulli(jax.random.PRNGKey(1), 0.5, (64, 256)).astype(jnp.int32)
    us = timeit(lambda: rlwe.encrypt_bits(pub, m, jax.random.PRNGKey(2)))
    rows.append(("table1/rlwe_encrypt_64blk", us, "quantum-safe key layer"))

    us = timeit(lambda: entropy.compress(mib, level=3), warmup=1, iters=3)
    rows.append((f"table1/{entropy.CODEC_NAME}_compress_per_MiB", us, "ZStd row"))
    blob = entropy.compress(mib, level=3)
    us = timeit(lambda: entropy.decompress(blob, max_output_size=len(mib)))
    rows.append((f"table1/{entropy.CODEC_NAME}_inflate_per_MiB", us, "ZStd inflate row"))

    shards = jnp.asarray(rng.integers(0, 256, (4, 1 << 18)), jnp.uint8)
    us = timeit(lambda: raid.raid6_encode(shards))
    rows.append(("table1/raid6_encode_per_MiB", us, "(un)RAID row"))
    return rows


# ------------------------------------------------------------------ Table 2
def table2_placement() -> List[Row]:
    """Data-distribution speedups vs CPU baseline (paper Table 2)."""
    sys = cm.SystemModel()
    base = cm.cpu_on_csd_data(sys, GB).latency_s
    paper = {
        "csd1_only": ((1.0,), 3.9),
        "split_90_10": ((0.9, 0.1), 4.46),
        "split_70_30": ((0.7, 0.3), 5.608),
        "split_60_40": ((0.6, 0.4), 6.67),
        "split_50_50": ((0.5, 0.5), 7.7),
    }
    rows = []
    for name, (split, target) in paper.items():
        got = base / cm.csd_archive(sys, GB, split).latency_s
        err = abs(got - target) / target * 100
        rows.append(
            (f"table2/{name}", cm.csd_archive(sys, GB, split).latency_s * 1e6,
             f"speedup={got:.2f}x paper={target}x err={err:.1f}%")
        )
    return rows


# ------------------------------------------------------------------- Fig. 4
def fig4_workstation() -> List[Row]:
    """Workstation (2 CSDs): Salient Store vs the classical storage path,
    normalized as in Fig. 4 (~1.99x).  The paper normalizes to an Alveo-class
    host accelerator, so the baseline keeps the host-link staging but runs
    the archival kernels ~4x faster than the storage CPU — the residual win
    is pure data-movement avoidance, the paper's thesis."""
    sys = cm.SystemModel()
    sal = cm.csd_archive(sys, GB, (0.5, 0.5)).latency_s
    alveo = cm.SystemModel(cpu_rate_GBps=sys.cpu_rate_GBps * 4.0)
    base = cm.classical_archive(alveo, GB).latency_s
    got = base / sal
    return [("fig4/salient_vs_alveo_host", sal * 1e6,
             f"speedup={got:.2f}x paper~1.99x err={abs(got-1.99)/1.99*100:.1f}%")]


# ------------------------------------------------------------------- Fig. 5
def fig5_consolidated() -> List[Row]:
    """Consolidated edge server: latency vs VSS/classical + data movement."""
    # Fig. 5's platform is an Alveo-class accelerator: csd_speedup 6.3
    sys = cm.SystemModel(csd_speedup=6.33)
    sal = cm.csd_archive(sys, GB).latency_s
    cla = cm.classical_archive(sys, GB)
    vss = cm.vss_archive(sys, GB)
    move = cla.moved_bytes / cm.csd_archive(sys, GB).moved_bytes
    # kernel-measured counterpart to the model-derived row: HBM-byte
    # accounting of the fused seal datapath vs the staged pipeline for a
    # representative 4-shard stripe of 1 MiB bodies (repro.kernels.seal)
    from repro.kernels.seal import datapath_traffic

    t = datapath_traffic(S=4, n_words=(1 << 20) // 4, parity="raid6")
    return [
        ("fig5b/vs_classical", sal * 1e6,
         f"speedup={cla.latency_s / sal:.2f}x paper=6.18x err={abs(cla.latency_s/sal-6.18)/6.18*100:.1f}%"),
        ("fig5b/vs_vss", sal * 1e6,
         f"speedup={vss.latency_s / sal:.2f}x paper=4.49x err={abs(vss.latency_s/sal-4.49)/4.49*100:.1f}%"),
        ("fig5c/data_movement_reduction", 0.0,
         f"reduction={move:.2f}x paper=5.63x err={abs(move-5.63)/5.63*100:.1f}%"),
        ("fig5c/seal_datapath_kernel_traffic", 0.0,
         f"staged={t['staged_bytes']}B fused={t['fused_bytes']}B "
         f"hbm_reduction={t['reduction']:.2f}x launches={t['fused_launches']} "
         f"vs {t['staged_passes']} staged passes"),
    ]


# ------------------------------------------------------------------- Fig. 6
def fig6_multinode() -> List[Row]:
    sys = cm.SystemModel()
    sal = cm.multinode_latency(sys, 8 * GB, 5).latency_s
    cla = cm.classical_multinode_latency(sys, 8 * GB, 5).latency_s
    vss = cla / sys.vss_factor
    return [
        ("fig6/vs_classical_5node", sal * 1e6,
         f"speedup={cla / sal:.2f}x paper=4.77x err={abs(cla/sal-4.77)/4.77*100:.1f}%"),
        ("fig6/vs_vss_5node", sal * 1e6,
         f"speedup={vss / sal:.2f}x paper=3.0x err={abs(vss/sal-3.0)/3.0*100:.1f}%"),
    ]


# ------------------------------------------------------------------- Fig. 7
def fig7_encryption() -> List[Row]:
    """Lattice encryption vs RSA.

    In-kind measured comparison: the accelerated polymul path (Pallas kernel,
    the FPGA/HSPM analogue) vs the software schoolbook path — the paper's
    "FPGA-LBC = 3.2x sw-LBC" claim.  The absolute host wall-clock of RLWE vs
    python RSA is NOT comparable (interpret-mode kernel on CPU), so the RSA
    rows are context + a derived MXU-cycle estimate gives the TPU-side ratio.
    """
    from repro.core.crypto import rlwe
    from repro.core.crypto.rsa_baseline import rsa_encrypt_blocks, rsa_keypair
    from repro.kernels.polymul.ops import polymul_fixed
    from repro.kernels.polymul.ref import negacyclic_matmul_ref

    rows: List[Row] = []
    payload = bytes(range(256)) * 24  # 6 KiB
    pub_rsa, _ = rsa_keypair()
    us_rsa = timeit(lambda: rsa_encrypt_blocks(payload, pub_rsa), warmup=0, iters=3)
    rows.append(("fig7/rsa512_sw_6KiB", us_rsa, "software RSA-512 (host CPU)"))

    rng = np.random.default_rng(0)
    q, n, B = 12289, 256, 192
    a = jnp.asarray(rng.integers(0, q, (n,)), jnp.int32)
    b = jnp.asarray(rng.integers(0, q, (B, n)), jnp.int32)
    us_sw = timeit(lambda: negacyclic_matmul_ref(a, b, q))
    us_hw = timeit(lambda: polymul_fixed(a, b, q))
    ratio = us_sw / us_hw
    rows.append(("fig7/lbc_polymul_sw", us_sw, "software schoolbook (sw-LBC)"))
    rows.append((
        "fig7/lbc_polymul_kernel", us_hw,
        f"accelerated-vs-sw={ratio:.1f}x (paper FPGA-vs-sw-LBC=3.2x)",
    ))
    # derived MXU estimate: 4 int8 limb matmuls of (n,n)@(n,B)
    mxu_flops = 4 * 2 * n * n * B
    est_us = mxu_flops / 197e12 * 1e6 * 4  # ~25% MXU util on small tiles
    rows.append((
        "fig7/lbc_mxu_derived", est_us,
        f"TPU-derived {est_us:.2f}us per 192 ciphertext polys "
        f"(paper: quantum-safe at ~RSA-class cost)",
    ))
    return rows


# ------------------------------------------------------------------- Fig. 8/9
def fig8_fig9_codec(quick: bool = True) -> List[Row]:
    """PSNR rate-distortion + encode latency: neural codec vs h264/hevc-like.

    The neural codec's AE is trained briefly on the content class first
    (the paper trains its codec); classical codecs need no training.
    """
    from repro.core.codec.layered_codec import (
        CodecConfig, encode_gop, init_codec, psnr, serialize_bitstream,
    )
    from repro.core.codec.reference_codecs import h264_like, hevc_like
    from repro.core.codec.training import (
        CodecTrainConfig, codec_pretrain_step, codec_train_step, init_codec_trainer,
    )
    from repro.core.codec.optimizer import AdamWConfig, adamw_init

    rows: List[Row] = []
    cfg = CodecConfig(n_layers=3, latent_ch=6, feat_ch=16, mv_cond_ch=4)
    params = init_codec(jax.random.PRNGKey(0), cfg)
    tcfg = CodecTrainConfig(codec=cfg, opt=AdamWConfig(lr=1e-3, grad_clip=1.0))
    # phase 1: joint pretraining (stands in for the pretrained MobileNet).
    # quick mode is rate-limited by the 1-core CPU host: PSNR here is the
    # *reduced-scale* operating point (~28-31 dB); BENCH_FULL trains longer.
    pre_steps = 60 if quick else 400
    opt_all = adamw_init(params)
    for i in range(pre_steps):
        clips = smooth_clip(jax.random.PRNGKey(100 + i), t=3)
        params, opt_all, m = codec_pretrain_step(params, opt_all, tcfg, clips)
    # phase 2: Alg. 2 — freeze extractor, train AE only
    trainable, frozen, opt = init_codec_trainer(params, tcfg)
    steps = 20 if quick else 150
    for i in range(steps):
        clips = smooth_clip(jax.random.PRNGKey(500 + i), t=3)
        trainable, opt, m = codec_train_step(trainable, frozen, opt, tcfg, clips)
    params = dict(frozen, **trainable)

    test = smooth_clip(jax.random.PRNGKey(999), t=4)
    # neural codec at K = 1..3 quality layers (rate points)
    for k in range(1, cfg.n_layers + 1):
        us = timeit(
            lambda k=k: encode_gop(params, cfg, test, n_layers=k)[1], warmup=1, iters=2
        )
        codes, recons = encode_gop(params, cfg, test, n_layers=k)
        blob, _ = serialize_bitstream(codes)
        p = float(psnr(recons, test))
        bpp = len(blob) * 8 / test[:, 0].size * 3
        rows.append(
            (f"fig8/salient_K{k}", us, f"psnr={p:.2f}dB bytes={len(blob)}")
        )
    frames = test[:, 0]
    for name, codec, qp in (
        ("h264_like_q1", h264_like(), 1.0),
        ("h264_like_q4", h264_like(), 4.0),
        ("hevc_like_q1", hevc_like(), 1.0),
        ("hevc_like_q4", hevc_like(), 4.0),
    ):
        us = timeit(lambda c=codec, q=qp: c.encode_gop(frames, qp=q)[1], warmup=1, iters=2)
        coded, recons = codec.encode_gop(frames, qp=qp)
        p = float(psnr(recons, frames))
        blob = codec.bitstream_bytes(coded)
        rows.append((f"fig8/{name}", us, f"psnr={p:.2f}dB bytes={len(blob)}"))
    return rows


# ------------------------------------------------------------------ Fig. 10
def fig10_movement_scaling() -> List[Row]:
    sys = cm.SystemModel()
    rows = []
    prev = None
    for n in (1, 2, 3, 4, 5, 8):
        lat = cm.multinode_movement_latency(sys, 8 * GB, n)
        growth = "" if prev in (None, 0) else f" growth={lat / prev:.2f}x"
        rows.append((f"fig10/nodes_{n}", lat * 1e6, f"super-linear latency{growth}"))
        prev = lat
    return rows


# ------------------------------------------------------------------ Fig. 11
def fig11_csd_ratio() -> List[Row]:
    sys = cm.SystemModel()
    rows = []
    best = (None, -1.0)
    for n_csd in (1, 2, 4, 8, 16):
        sp, eff = cm.csd_ratio_tradeoff(sys, 64 * GB, n_ssd=8, n_csd=n_csd)
        rows.append(
            (f"fig11/ssd8_csd{n_csd}", 0.0, f"speedup={sp:.2f}x cost_eff={eff:.4f}")
        )
        if eff > best[1]:
            best = (n_csd, eff)
    rows.append(
        ("fig11/knee", 0.0, f"best=8:{best[0]} (paper: 8:1 SSD:CSD)")
    )
    return rows
