"""Paper-native example: train a small CNN classifier whose convolutions run
through the zero-memory-overhead direct path (blocked layouts end to end —
layers chain without repacking, exactly the paper's §4 design point).

Two models (``--model``):

  dense      ``BlockedCNN`` of plain convs: conv(relu, SAME) -> conv(relu,
             SAME, stride 2) -> GAP -> linear head.
  separable  the MobileNet factorization on the same layout: two
             ``DepthwiseSeparableBlock``s (depthwise 3x3 + pointwise 1x1),
             exercising the grouped/depthwise/pointwise kernel zoo — the
             dispatcher routes each leg to its specialized Pallas kernel.

Input images are blocked once at entry; every layer boundary after that —
including the separable blocks' interior depthwise->pointwise boundary —
stays in ``[N, C/Cb, H, W, Cb]``.

Synthetic 16x16 task: each class is a fixed 3x3 stamp pattern placed at a
*random* position (translation-invariant — which is why GAP classifies it).

``--pallas`` trains *through the Pallas kernel families*: the forward
kernels plus their custom VJPs (dgrad + wgrad in the blocked layout too —
DESIGN.md §9, §13).  The dense model pins ``ConvContext(impl="window")``;
the separable
model routes through a prior-tier dispatcher, whose geometry-aware prior
selects the depthwise and pointwise kernels.  Whichever path trains, the
final-batch loss is cross-checked against the jnp-oracle path (same params,
same batch — the formulations must agree to rounding).

``--dtype bf16`` engages the mixed-precision policy (DESIGN.md §10): bf16
operands/residuals, f32 accumulators and master params.  The final-loss
parity tolerance is policy-aware — two bf16 formulations agree to bf16
rounding, not f32 rounding.

Usage:  PYTHONPATH=src python examples/train_conv_net.py --steps 150
        PYTHONPATH=src python examples/train_conv_net.py --steps 3 --pallas
        PYTHONPATH=src python examples/train_conv_net.py --steps 3 --pallas \
            --model separable --dtype bf16
(accuracy assertions only engage for runs long enough to learn, >= 100
steps; short runs are CI training smokes.)
"""
import argparse

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.context import ConvContext
from repro.core.dispatch import ConvDispatcher
from repro.nn.conv import BlockedCNN, BlockedConv2D, DepthwiseSeparableBlock
from repro.nn.module import init_tree
from repro.train.optimizer import AdamW, cosine_schedule
from repro.utils.cache import enable_compile_cache

CB = 8   # channel pencil for this toy net (lane=128 on real TPU)

MODELS = {
    "dense": BlockedCNN(
        convs=(
            BlockedConv2D(ci=8, co=16, hf=3, wf=3, stride=1, padding="SAME",
                          activation="relu", lane=CB),
            BlockedConv2D(ci=16, co=32, hf=3, wf=3, stride=2, padding="SAME",
                          activation="relu", lane=CB),
        ),
        n_classes=8,
    ),
    "separable": BlockedCNN(
        convs=(
            DepthwiseSeparableBlock(ci=8, co=16, hf=3, wf=3, stride=1,
                                    padding="SAME", activation="relu",
                                    lane=CB),
            DepthwiseSeparableBlock(ci=16, co=32, hf=3, wf=3, stride=2,
                                    padding="SAME", activation="relu",
                                    lane=CB),
        ),
        n_classes=8,
    ),
}

# final-loss parity tolerance per policy: two f32 formulations agree to
# float32 rounding; two bf16 formulations each quantize operands/outputs to
# 8 mantissa bits (eps ~ 2^-8 ≈ 4e-3), compounded over the conv layers +
# the head — an f32-tuned 1e-4 would spuriously fail a *correct* bf16 run.
PARITY_TOL = {"f32": 1e-4, "bf16": 5e-2}

# 8 fixed, mutually distinct 3x3 stamps (the classes); generated once from a
# fixed seed so train batches are consistent.
_STAMPS = np.sign(np.random.default_rng(1234).normal(size=(8, 3, 3))) * 3.0


def make_batch(rng, n=128):
    """Class-specific 3x3 stamp at a random position + background noise."""
    ys = rng.integers(0, 8, n)
    xs = rng.normal(0, 0.1, (n, 16, 16, 1)).astype(np.float32)
    for i, y in enumerate(ys):
        r, c = rng.integers(0, 14, 2)       # 3x3 stamp: top-left in 0..13
        xs[i, r:r + 3, c:c + 3, 0] += _STAMPS[y]
    return jnp.asarray(xs.repeat(8, axis=-1)), jnp.asarray(ys)


def pallas_routing(model_name, precision="f32"):
    """ConvContext that trains this model through the Pallas kernels.

    The dense model pins the window kernel.  The separable model leaves the
    impl free and routes through an empty (prior-tier) dispatcher: the
    geometry-aware prior puts the depthwise and pointwise Pallas kernels
    first for their layers, so every leg runs its specialized kernel +
    custom VJP.
    """
    if model_name == "dense":
        return ConvContext(impl="window", precision=precision)
    return ConvContext(dispatch=ConvDispatcher(), precision=precision)


def make_loss(model, context):
    def loss_fn(p, x, y):
        logits = model(p, x, context=context)
        # the policy's single up-cast: CE in f32 whatever the compute dtype
        ll = jax.nn.log_softmax(logits.astype(jnp.float32))
        loss = -jnp.take_along_axis(ll, y[:, None], 1).mean()
        acc = (logits.argmax(-1) == y).mean()
        return loss, acc
    return loss_fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--pallas", action="store_true",
                    help="train through the Pallas kernels (custom VJP: "
                         "dgrad + wgrad run in the blocked layout too)")
    ap.add_argument("--model", choices=sorted(MODELS), default="dense",
                    help="dense convs, or depthwise-separable blocks "
                         "(the grouped/depthwise/pointwise kernel zoo)")
    ap.add_argument("--dtype", choices=sorted(PARITY_TOL), default="f32",
                    help="mixed-precision policy: bf16 operands/residuals "
                         "with f32 accumulators + master params")
    args = ap.parse_args()
    enable_compile_cache()

    model = MODELS[args.model]
    p = init_tree(model.specs(), jax.random.PRNGKey(0))
    opt = AdamW(lr=cosine_schedule(1e-2, 10, args.steps), weight_decay=0.0)
    st = opt.init(p)
    if args.pallas:
        ctx = pallas_routing(args.model, args.dtype)
    else:
        ctx = ConvContext(impl="jnp", precision=args.dtype)
    loss_fn = make_loss(model, ctx)

    @jax.jit
    def step(p, st, x, y):
        (loss, acc), g = jax.value_and_grad(loss_fn, has_aux=True)(p, x, y)
        p, st, _ = opt.update(g, st, p)
        return p, st, loss, acc

    path = "pallas" if args.pallas else "jnp"
    path = f"{args.model}/{path}/{args.dtype}"
    rng = np.random.default_rng(0)
    for s in range(args.steps):
        x, y = make_batch(rng)
        p, st, loss, acc = step(p, st, x, y)
        if (s + 1) % 25 == 0 or s + 1 == args.steps:
            print(f"[{path}] step {s + 1}: loss={float(loss):.4f} "
                  f"acc={float(acc):.2f}")

    # the formulations are one semantics: the final-batch loss through the
    # *other* path must agree to float tolerance on the trained params
    # (tolerance is policy-aware — bf16 agreement is bf16-rounding-tight)
    mine, _ = loss_fn(p, x, y)
    if args.pallas:
        other_fn = make_loss(model, ConvContext(impl="jnp",
                                                precision=args.dtype))
    else:
        other_fn = make_loss(model, pallas_routing(args.model, args.dtype))
    other, _ = other_fn(p, x, y)
    tol = PARITY_TOL[args.dtype]
    print(f"final loss parity: {path}={float(mine):.6f} "
          f"other={float(other):.6f} (tol={tol:g})")
    assert abs(float(mine) - float(other)) < tol + tol * abs(float(mine)), (
        "paths disagree on the trained params")

    if args.steps >= 100:
        assert float(acc) > 0.9, "conv net failed to learn"
        print("direct-conv CNN learned the task (acc > 0.9)")

    # trained params run unchanged through the fused Pallas inference path
    x, y = make_batch(rng)
    logits = model(p, x, context=pallas_routing(args.model))
    pacc = float((logits.argmax(-1) == y).mean())
    print(f"pallas-kernel inference path: acc={pacc:.2f}")
    if args.steps >= 100:
        assert pacc > 0.9

    return 0


if __name__ == "__main__":
    main()
