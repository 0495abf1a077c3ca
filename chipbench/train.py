"""Back-to-back training steps through ``make_train_step`` (traffic
``kind: train``).

Parameters of a mix (``chipbench/traffic/<mix>.json``):

  batch              images per step
  distinct_batches   batches made on the device from the seed and cycled
                     (at least three: the checked steps see rows that all
                     differ)
  optimizer          AdamW: ``lr`` reached by linear warm-up over
                     ``warmup_steps``, ``b1``, ``b2``, ``eps``,
                     ``weight_decay``; no gradient clipping
  ref_block          rows per block of the reference's gradient

Set-up compiles the step, then drives it through its first three steps with
the window's own call and feed; the window continues the same object.  Two
steps stay in flight: before dispatching a step the loop waits for the loss
of the step two back, as a training loop that logs its loss does.  The
window ends at a ``block_until_ready`` on the parameters.

Correctness: after the window the program's state is freed and the plain
reference (float32, ``HIGHEST``) runs the first three steps from the same
weights on the same rows, with the same AdamW.  :func:`numbers` reads, each
by its worst case: the three losses (relative), each leaf's norm of the
first gradient (as the optimizer got it, read back from its first moment)
and of the parameters' change over the three steps, each gap measured
against the larger of the reference leaf's norm and the median leaf's, and
the classifier's first gradient (relative norm of the difference).  Leaves
whose reference gradient norm is under a thousandth of the median leaf's
move under Adam by round-off alone and are left out of the change.
"""
from __future__ import annotations

import collections
import gc
import time

import numpy as np

from chipbench import harness, trace as tracing

CHECKED_STEPS = 3
IN_FLIGHT = 2
HEAD = ("head",)


class Setup:
    """The compiled step, its state after the checked steps and its feed."""

    def __init__(self, run: harness.Run, seed: int):
        import jax
        from repro.core.context import ConvContext
        from repro.train.optimizer import AdamW
        from repro.train.trainstep import TrainSettings, make_train_step
        cfg, t = run.cfg, run.traffic
        o = t["optimizer"]
        fam, ref = harness.family(cfg)
        self.ref, self.fam = ref, fam
        key = ref.key_for(seed)
        self.key = key
        weights = jax.jit(lambda k: ref.make_weights(cfg, k))(
            jax.random.fold_in(key, 0))
        model = fam.build(cfg)
        params = fam.to_program(cfg, model, weights)
        del weights
        self.batches = make_batches(cfg, t, jax.random.fold_in(key, 1))
        opt = AdamW(lr=lambda s: ref.lr_at(o, s.astype(np.float32)),
                    b1=o["b1"], b2=o["b2"], eps=o["eps"],
                    weight_decay=o["weight_decay"], grad_clip=None)
        settings = TrainSettings(context=ConvContext(precision=cfg["precision"]))
        state = opt.init(params)
        self.step = jax.jit(make_train_step(model, None, opt, settings),
                            donate_argnums=(0, 1)).lower(
            params, state, self.batches[0]).compile()
        self.p0 = host(params)
        self.losses = []
        for i in range(CHECKED_STEPS):
            params, state, m = self.step(params, state, self.batches[i])
            self.losses.append(float(m["nll"]))
            if i == 0:
                self.g1 = jax.tree.map(
                    lambda mu: np.asarray(mu) / (1 - o["b1"]), state.mu)
        self.p3 = host(params)
        self.params, self.state = params, state
        self.n = CHECKED_STEPS


def host(tree):
    import jax
    return jax.tree.map(np.asarray, tree)


def make_batches(cfg, t, key):
    """``distinct_batches`` batches of NHWC float32 images and labels, made
    on the device in one call."""
    import jax
    import jax.numpy as jnp
    k, b, s = t["distinct_batches"], t["batch"], cfg["input_size"]
    if k < CHECKED_STEPS:
        raise ValueError(f"distinct_batches {k} < the {CHECKED_STEPS} "
                         "checked steps, whose rows must all differ")

    def gen(key):
        ki, kt = jax.random.split(key)
        return (jax.random.normal(ki, (k, b, s, s, cfg["in_channels"]),
                                  jnp.float32),
                jax.random.randint(kt, (k, b), 0, cfg["n_classes"]))
    images, targets = jax.jit(gen)(key)
    return [{"images": images[i], "targets": targets[i]} for i in range(k)]


def window(run: harness.Run, st: Setup, seconds: float):
    """Steps back to back for ``seconds``; -> (steps, elapsed seconds)."""
    import jax
    pending = collections.deque()
    params, state = st.params, st.state
    k = len(st.batches)
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        if len(pending) >= IN_FLIGHT:
            with run.spans("bench.wait_step"):
                pending.popleft().block_until_ready()
        with run.spans("bench.train_step"):
            params, state, m = st.step(params, state,
                                       st.batches[(st.n + n) % k])
        pending.append(m["nll"])
        n += 1
    with run.spans("bench.wait_step"):
        jax.block_until_ready(params)
    elapsed = time.perf_counter() - t0
    st.last_loss = float(m["nll"]) if n else float("nan")
    st.params, st.state = params, state
    return n, elapsed


def run(run: harness.Run):
    st = Setup(run, run.seed)
    gc.collect()
    if run.tracing:
        tracer = tracing.Tracer()
        tracer.start()
    run.e2e["setup_s"] = time.perf_counter() - run.t_process
    with run.spans(tracing.WINDOW):
        n, elapsed = window(run, st, run.seconds)
    if run.tracing:
        run.trace = tracer.stop(run.spans, harness.kernel_families())
    run.attempted = n
    run.failed = 0 if np.isfinite(st.last_loss) else 1
    run.e2e["train_step_ms"] = elapsed / max(n, 1) * 1e3
    run.counters.update(steps=n, elapsed_s=elapsed,
                        images=n * run.traffic["batch"])
    run.memory_peak_bytes = harness.memory_peak(run.devices)
    for name, value in errors(run, st)["program"].items():
        run.check(name, value)


def reference_steps(run, st, operand=None, rows=None):
    """The plain reference's first three steps on the checked batches (the
    first ``rows`` of each); -> (losses, first gradient, change)."""
    import jax
    import jax.numpy as jnp
    cfg, o, ref = run.cfg, run.traffic["optimizer"], st.ref
    blk = run.traffic["ref_block"]
    rows = rows or run.traffic["batch"]
    if rows % blk:
        raise ValueError(f"ref_block {blk} must divide the rows {rows}")

    def block(w, x, y):
        return jax.value_and_grad(
            lambda w: ref.loss(cfg, w, x, y, operand))(w)
    block = jax.jit(block)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    upd = jax.jit(lambda w, g, m, v, t: ref.adamw(o, w, g, m, v, t))
    w = w0 = jax.jit(lambda k: ref.make_weights(cfg, k))(
        jax.random.fold_in(st.key, 0))
    m = v = jax.tree.map(jnp.zeros_like, w)
    losses, g1 = [], None
    nb = rows // blk
    with jax.default_matmul_precision("highest"):
        for i in range(CHECKED_STEPS):
            b = st.batches[i]
            tot, gsum = 0.0, None
            for j in range(nb):
                sl = slice(j * blk, (j + 1) * blk)
                lv, g = block(w, b["images"][sl], b["targets"][sl])
                tot += float(lv)
                gsum = g if gsum is None else add(gsum, g)
            g = jax.tree.map(lambda x: x / nb, gsum)
            losses.append(tot / nb)
            if i == 0:
                g1 = flat(st, run.cfg, g)
            w, m, v = upd(w, g, m, v, jnp.float32(i + 1))
    return losses, g1, change(st, run.cfg, w0, w)


def flat(st, cfg, tree, program=False):
    """``{leaf path: float64 array}`` of a parameter-shaped tree, a
    program's one laid out as the reference's."""
    out = {p: np.asarray(st.ref.get(tree, p), np.float64)
           for p in st.fam.leaf_paths(cfg)}
    if program:
        out = {p: st.fam.reference_layout(p, x) for p, x in out.items()}
    return out


def change(st, cfg, before, after, program=False):
    a = flat(st, cfg, before, program)
    b = flat(st, cfg, after, program)
    return {p: b[p] - a[p] for p in a}


def leaf_gaps(prog: dict, ref: dict, keys):
    """Each leaf's gap between its norm in ``prog`` and in ``ref``, against
    the larger of the reference leaf's norm and the median leaf's."""
    rn = np.array([np.linalg.norm(ref[k]) for k in keys])
    pn = np.array([np.linalg.norm(prog[k]) for k in keys])
    return np.abs(pn - rn) / np.maximum(rn, np.median(rn))


def numbers(prog, want):
    """The numbers a run compares, of ``prog`` against ``want`` (each
    ``(losses, first gradient, change)``): the worst step's relative loss
    gap; the worst leaf's gap of norms of the first gradient and of the
    change; and the classifier's first gradient, by the relative norm of
    the difference (``head_grad_diff``: the forward pass's features alone
    set it, so it reads the precision of the forward pass)."""
    gn = {k: np.linalg.norm(g) for k, g in want[1].items()}
    med = np.median(list(gn.values()))
    moved = [k for k in gn if gn[k] >= 1e-3 * med]
    return {
        "loss_err": float(max(abs(a - b) / abs(b)
                              for a, b in zip(prog[0], want[0]))),
        "grad_err": float(leaf_gaps(prog[1], want[1], list(gn)).max()),
        "change_err": float(leaf_gaps(prog[2], want[2], moved).max()),
        "head_grad_diff": float(np.linalg.norm(prog[1][HEAD] - want[1][HEAD])
                                / np.linalg.norm(want[1][HEAD])),
    }


def errors(run, st, controls=(), half_batch=False):
    """-> {"program": numbers} and, for each control operand dtype, the
    numbers of the reference at that operand precision in the program's
    place; with ``half_batch``, those of the reference that averages over
    half of each batch.  The program's state is freed first."""
    prog = (st.losses, flat(st, run.cfg, st.g1, program=True),
            change(st, run.cfg, st.p0, st.p3, program=True))
    st.params = st.state = st.step = None
    gc.collect()
    want = reference_steps(run, st)
    out = {"program": numbers(prog, want), "reference_losses": want[0]}
    for c in controls:
        out[str(c)] = numbers(reference_steps(run, st, c), want)
    if half_batch:
        out["half_batch"] = numbers(
            reference_steps(run, st, rows=run.traffic["batch"] // 2), want)
    return out
