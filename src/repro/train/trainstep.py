"""train_step / prefill_step factories: loss, grad accumulation, pjit wiring."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.context import ConvContext, as_context, reject_legacy_kwargs
from repro.nn.conv import BlockedCNN
from repro.nn.models import EncDec
from repro.utils import spans as _spans  # noqa: F401  (counts compiles)
from .losses import cross_entropy
from .optimizer import AdamW, OptState

__all__ = ["TrainSettings", "forward", "make_loss_fn", "make_train_step",
           "make_prefill_step"]


@dataclasses.dataclass(frozen=True)
class TrainSettings:
    remat: str = "full"              # none | full | dots
    accum_steps: int = 1             # gradient accumulation microbatches
    chunk: int = 2048                # attention KV chunk
    unroll: bool = False             # unroll the layer scan (cost extraction)
    fused_loss: bool = False         # chunked CE: never materialize logits
    loss_chunks: int = 8
    context: Optional[ConvContext] = None
                                     # conv models: the one execution
                                     # context (core/context.py) — which
                                     # dispatcher, forced impl, precision
                                     # policy, interpret mode — for every
                                     # conv of the run.  The loose
                                     # dispatch/impl/precision fields are
                                     # gone (ISSUE 10); constructing with
                                     # one raises the migration TypeError

    def conv_context(self) -> ConvContext:
        """The settings' conv execution context (empty when unset)."""
        return as_context(self.context)


# The removed loose fields fail with the migration TypeError (naming
# ConvContext) instead of dataclass's bare "unexpected keyword argument" —
# same contract as the conv entry points' **legacy rejection.
_TRAINSETTINGS_INIT = TrainSettings.__init__


def _trainsettings_guarded_init(self, *args, **kwargs):
    removed = {k: kwargs[k] for k in ("dispatch", "impl", "precision")
               if k in kwargs}
    reject_legacy_kwargs("TrainSettings", removed)
    _TRAINSETTINGS_INIT(self, *args, **kwargs)


TrainSettings.__init__ = _trainsettings_guarded_init


def forward(model, params, batch: Dict[str, Any], *, train=True,
            remat="full", chunk=2048, unroll=False, return_hidden=False,
            context=None, **legacy):
    """Uniform forward over model families."""
    reject_legacy_kwargs("forward", legacy)
    if isinstance(model, BlockedCNN):
        # blocked-layout image classifier: NHWC batch in, class logits out;
        # every conv (fwd AND bwd) routes through the dispatch subsystem as
        # one ConvContext (DESIGN.md §12/§15) — the only spelling
        ctx = as_context(context)
        return (model(params, batch["images"], context=ctx),
                jnp.zeros((), jnp.float32))
    if isinstance(model, EncDec):
        return model(params, batch["tokens"], batch["frames"], train=train,
                     remat=remat, chunk=chunk, unroll=unroll,
                     return_hidden=return_hidden)
    memory = batch.get("img_embed")
    return model(params, batch["tokens"], memory=memory, train=train,
                 remat=remat, chunk=chunk, unroll=unroll,
                 return_hidden=return_hidden)


def make_loss_fn(model, cfg: Optional[ModelConfig], settings: TrainSettings):
    if isinstance(model, BlockedCNN):
        # image classification: cfg is not needed (the class count lives on
        # the model); cross_entropy over a singleton "sequence" axis
        def conv_loss_fn(params, batch):
            logits, aux = forward(model, params, batch, train=True,
                                  context=settings.conv_context())
            # the single up-cast of the compute dtype: CE runs in f32
            logits = logits.astype(jnp.float32)
            loss, metrics = cross_entropy(
                logits[:, None, :], batch["targets"][:, None].astype(jnp.int32),
                model.n_classes)
            metrics["aux_loss"] = aux
            return loss + aux, metrics
        return conv_loss_fn

    from repro.nn.models import EncDec as _EncDec
    lm = model.decoder if isinstance(model, _EncDec) else model

    def loss_fn(params, batch):
        if settings.fused_loss:
            hidden, aux = forward(model, params, batch, train=True,
                                  remat=settings.remat, chunk=settings.chunk,
                                  unroll=settings.unroll, return_hidden=True)
            p = params["decoder"] if isinstance(model, _EncDec) else params
            if cfg.tie_embeddings:
                head, tr = p["embed"]["w"], True
            else:
                head, tr = p["lm_head"], False
            from .losses import fused_cross_entropy
            loss, metrics = fused_cross_entropy(
                hidden, head, batch["targets"], cfg.vocab_size,
                transpose_head=tr, cap=cfg.final_softcap,
                chunks=settings.loss_chunks, px=lm.px,
                unroll=settings.unroll)
        else:
            logits, aux = forward(model, params, batch, train=True,
                                  remat=settings.remat, chunk=settings.chunk,
                                  unroll=settings.unroll)
            loss, metrics = cross_entropy(logits, batch["targets"],
                                          cfg.vocab_size,
                                          mask=batch.get("loss_mask"))
        metrics["aux_loss"] = aux
        return loss + aux, metrics
    return loss_fn


def make_train_step(model, cfg: Optional[ModelConfig], optimizer: AdamW,
                    settings: TrainSettings = TrainSettings()):
    """-> train_step(params, opt_state, batch) -> (params, state, metrics).

    With accum_steps > 1 the global batch is split along dim 0 into
    microbatches scanned sequentially — activation memory drops by the same
    factor while the gradient math is identical (mean of microbatch grads).

    Works for LM/EncDec token models and for ``BlockedCNN`` image
    classifiers (``cfg`` may be None there; batches carry ``images`` +
    ``targets``, and every conv routes through the dispatch subsystem via
    ``settings.context``, DESIGN.md §12/§15 — so training through the
    Pallas custom-VJP kernel families includes gradient accumulation).
    """
    loss_fn = make_loss_fn(model, cfg, settings)
    grad_fn = jax.grad(loss_fn, has_aux=True)

    def train_step(params, opt_state: OptState, batch):
        n = settings.accum_steps
        if n == 1:
            grads, metrics = grad_fn(params, batch)
        else:
            def micro(b):
                return jax.tree.map(
                    lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), b)

            def acc_step(g, mb):
                gi, mi = grad_fn(params, mb)
                return jax.tree.map(jnp.add, g, gi), mi

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params)
            grads, metrics_stack = jax.lax.scan(acc_step, zeros, micro(batch))
            metrics = jax.tree.map(lambda m: m.mean(0), metrics_stack)
            grads = jax.tree.map(lambda g: g / n, grads)

        params, opt_state, opt_metrics = optimizer.update(
            grads, opt_state, params)
        metrics = dict(metrics, **opt_metrics)
        return params, opt_state, metrics

    return train_step


def make_prefill_step(model, cfg: ModelConfig, settings: TrainSettings = TrainSettings()):
    """Full-sequence forward (inference prefill): logits for every position."""
    def prefill_step(params, batch):
        logits, _ = forward(model, params, batch, train=False,
                            remat=settings.remat, chunk=settings.chunk,
                            unroll=settings.unroll)
        return logits
    return prefill_step
