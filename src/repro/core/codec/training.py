"""Codec training (Alg. 2): freeze the inference backbone, train the AE.

Loss = sum_t ||F_t - F^_t||_2^2 (the paper's reconstruction objective)
     + lambda_rate * L1(codes)   (rate proxy; true rate is measured with zstd
                                  at eval — the proxy only shapes sparsity).

Only the ``ae`` and ``mv_embed`` subtrees receive gradients; ``extractor``
(MobileNet stand-in) stays frozen, exactly Alg. 2's "Backpropagate loss and
update weights of A only".
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.codec.layered_codec import CodecConfig, encode_gop
from repro.core.codec.optimizer import AdamWConfig, AdamWState, adamw_init, adamw_update

__all__ = ["CodecTrainConfig", "codec_loss", "make_codec_train_step", "init_codec_trainer"]


class CodecTrainConfig(NamedTuple):
    codec: CodecConfig = CodecConfig()
    opt: AdamWConfig = AdamWConfig(lr=3e-4, grad_clip=1.0)
    lambda_rate: float = 1e-5


def codec_loss(trainable, frozen, cfg: CodecTrainConfig, clips):
    """clips: (T, B, H, W, 3). Returns (loss, metrics)."""
    params = dict(frozen, **trainable)
    frame_codes, recons = encode_gop(params, cfg.codec, clips, train=True)
    recon_mse = jnp.mean((recons - clips) ** 2)
    rate = sum(jnp.mean(jnp.abs(z)) for fc in frame_codes for z in fc.codes) / len(
        frame_codes
    )
    loss = recon_mse + cfg.lambda_rate * rate
    return loss, {"recon_mse": recon_mse, "rate_l1": rate, "loss": loss}


def init_codec_trainer(params, cfg: CodecTrainConfig):
    trainable = {k: params[k] for k in ("ae", "mv_embed")}
    frozen = {k: params[k] for k in ("extractor",)}
    return trainable, frozen, adamw_init(trainable)


@functools.partial(jax.jit, static_argnames=("cfg",))
def codec_train_step(trainable, frozen, opt_state: AdamWState, cfg: CodecTrainConfig, clips):
    (loss, metrics), grads = jax.value_and_grad(codec_loss, has_aux=True)(
        trainable, frozen, cfg, clips
    )
    trainable, opt_state = adamw_update(trainable, grads, opt_state, cfg.opt)
    return trainable, opt_state, metrics


def make_codec_train_step(cfg: CodecTrainConfig):
    return functools.partial(codec_train_step, cfg=cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def codec_pretrain_step(params, opt_state: AdamWState, cfg: CodecTrainConfig, clips):
    """Backbone pretraining phase: ALL params trainable (stands in for the
    paper's pretrained MobileNet); Alg. 2 then freezes the extractor."""
    def loss(p):
        return codec_loss({k: p[k] for k in ("ae", "mv_embed")},
                          {"extractor": p["extractor"]}, cfg, clips)

    (l, metrics), grads = jax.value_and_grad(loss, has_aux=True)(params)
    params, opt_state = adamw_update(params, grads, opt_state, cfg.opt)
    return params, opt_state, metrics
