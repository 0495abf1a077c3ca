"""Continuous-batching request scheduler (vLLM-style slots, simplified).

A fixed decode batch of B slots; finished sequences (EOS or max_len) release
their slot, the next queued request prefills into it.  Per-slot position
tracking lets sequences of different lengths share one batched serve_step.

Single-token-at-a-time slot prefill keeps the implementation exact w.r.t.
the decode path; a chunked prefill (throughput mode) is a documented
extension point.

The conv serving tier (DESIGN.md §15) reuses the same slot vocabulary for
image requests: :class:`ConvRequest` carries an arbitrary-size image,
:class:`SpatialBucketer` maps it onto one of a small set of
dispatch-table-tuned ``(H, W)`` buckets (pad on entry, slice on exit), and
:class:`SlotPool` does the per-bucket slot acquire/release + occupancy
accounting that ``launch.conv_serve.ConvServer`` drives.  Conv inference is
single-shot (no iterative decode), so a slot's lifetime is one batch step —
the "continuous" part is that admission refills freed slots from the queue
every step instead of waiting for a full batch.
"""
from __future__ import annotations

import dataclasses
import enum
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.faults import inject as _inject_fault

__all__ = ["Request", "ContinuousBatcher", "ConvRequest", "Outcome",
           "SpatialBucketer", "SlotPool"]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                   # [S] int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class ContinuousBatcher:
    def __init__(self, model, params, batch: int, cache_len: int,
                 sampler: Callable = None):
        from .decode import greedy, make_serve_step
        lm = getattr(model, "decoder", model)
        self.model, self.params = model, params
        self.batch, self.cache_len = batch, cache_len
        self.serve_step = jax.jit(make_serve_step(model))
        self.sampler = sampler or greedy
        self.cache = lm.init_cache(batch, cache_len)
        self.slots: List[Optional[Request]] = [None] * batch
        # per-slot: position and last token; idle slots run a dummy token
        self.pos = np.zeros(batch, np.int64)
        self.last = np.zeros(batch, np.int32)
        self.remaining_prompt: List[deque] = [deque() for _ in range(batch)]
        self.queue: deque[Request] = deque()
        self.completed: List[Request] = []
        self._lm = lm

    # -- queue management ------------------------------------------------
    def submit(self, req: Request):
        self.queue.append(req)

    def _fill_slots(self):
        for b in range(self.batch):
            if self.slots[b] is None and self.queue:
                req = self.queue.popleft()
                self.slots[b] = req
                self.remaining_prompt[b] = deque(req.prompt.tolist())
                # slot cache is stale from the previous occupant; position
                # restarts and ring validity masks the old entries out only
                # for pos<W — so zero the slot's cache.
                self.cache = _zero_slot(self.cache, b)
                self.pos[b] = 0
                self.last[b] = self.remaining_prompt[b].popleft()

    # -- one engine step ---------------------------------------------------
    def step(self):
        """One batched serve_step: prefilling slots consume prompt tokens,
        decoding slots sample; idle slots run a masked dummy."""
        self._fill_slots()
        if all(s is None for s in self.slots):
            return False
        # NOTE: positions differ per slot; the decode path takes one scalar
        # pos, so we step slots grouped by position — the common case
        # (uniform decode after warmup) is a single group.
        groups: Dict[int, List[int]] = {}
        for b, req in enumerate(self.slots):
            if req is not None:
                groups.setdefault(int(self.pos[b]), []).append(b)
        for pos, bs in sorted(groups.items()):
            toks = jnp.asarray(self.last[:, None])
            logits, self.cache = self.serve_step(
                self.params, self.cache, toks, jnp.int32(pos))
            nxt = np.asarray(self.sampler(logits))
            for b in bs:
                req = self.slots[b]
                self.pos[b] += 1
                if self.remaining_prompt[b]:
                    self.last[b] = self.remaining_prompt[b].popleft()
                else:
                    tok = int(nxt[b])
                    req.out_tokens.append(tok)
                    self.last[b] = tok
                    if ((req.eos_id is not None and tok == req.eos_id)
                            or len(req.out_tokens) >= req.max_new_tokens
                            or self.pos[b] >= self.cache_len - 1):
                        req.done = True
                        self.completed.append(req)
                        self.slots[b] = None
        return True

    def run(self, max_steps: int = 10 ** 6):
        steps = 0
        while (self.queue or any(self.slots)) and steps < max_steps:
            self.step()
            steps += 1
        return self.completed


# ---------------------------------------------------------------------------
# Conv serving: ragged image requests onto bucketed blocked-layout batches
# ---------------------------------------------------------------------------

class Outcome(enum.Enum):
    """The request outcome lattice (DESIGN.md §16): every submitted request
    terminates in exactly one of the three bottom states.

      PENDING    in flight (queued or slotted)
      OK         served — ``logits`` holds the answer
      TIMED_OUT  deadline passed before a slot; completed without running
      REJECTED   shed at admission — the bounded queue was full
    """

    PENDING = "pending"
    OK = "ok"
    TIMED_OUT = "timed_out"
    REJECTED = "rejected"


@dataclasses.dataclass
class ConvRequest:
    """One image-classification request through the conv serving tier.

    ``image`` is host-side NHWC-without-N (``[H, W, C]``) of arbitrary
    spatial size; the bucketer pads it up to its bucket on admission.  The
    server stamps ``t_submit``/``t_done`` with its injected clock (tests
    pass a deterministic counter; the chip benchmark passes
    ``time.perf_counter``), so ``latency`` is queue wait + batched service
    time.

    ``deadline`` is absolute on the server's clock (``submit(timeout=...)``
    derives it from t_submit); a queued request past its deadline completes
    as ``TIMED_OUT`` without ever occupying a slot.  ``outcome`` is the
    :class:`Outcome` lattice state; ``done`` means "terminated" (any
    non-PENDING outcome), not "served".
    """

    rid: int
    image: np.ndarray                    # [H, W, C] float
    t_submit: float = 0.0                # stamped by ConvServer.submit
    t_done: float = 0.0                  # stamped on completion
    bucket: Optional[Tuple[int, int]] = None
    logits: Optional[np.ndarray] = None  # [n_classes] when outcome is OK
    done: bool = False
    deadline: Optional[float] = None     # absolute, server-clock seconds
    outcome: Outcome = Outcome.PENDING

    @property
    def latency(self) -> float:
        return self.t_done - self.t_submit


class SpatialBucketer:
    """Map arbitrary ``(H, W)`` requests onto a small tuned bucket set.

    Buckets are the ``(H, W)`` shapes the dispatch table was tuned for —
    one compiled executable and one measured routing decision per bucket,
    instead of a fresh trace per distinct request shape.  ``bucket_for``
    picks the smallest bucket (by padded area) that contains the image;
    ``pad``/``crop`` are the exact inverse pair the round-trip test pins:
    zero-pad bottom/right on entry, slice the same extents off on exit.
    (For the classifier models the exit slice is at the *batch* level —
    GAP + head already collapsed the spatial dims — but feature-map
    serving crops spatially, so the inverse lives here.)
    """

    def __init__(self, buckets: Sequence[Tuple[int, int]]):
        if not buckets:
            raise ValueError("need at least one (H, W) bucket")
        self.buckets = tuple(sorted((int(h), int(w)) for h, w in buckets))

    def bucket_for(self, h: int, w: int) -> Tuple[int, int]:
        fits = [(bh * bw, (bh, bw)) for bh, bw in self.buckets
                if bh >= h and bw >= w]
        if not fits:
            raise ValueError(f"image ({h}, {w}) exceeds every bucket "
                             f"{list(self.buckets)}")
        return min(fits)[1]

    def pad(self, image: np.ndarray,
            bucket: Optional[Tuple[int, int]] = None) -> np.ndarray:
        """Zero-pad ``[H, W, C]`` bottom/right up to its bucket."""
        h, w = image.shape[:2]
        bh, bw = bucket if bucket is not None else self.bucket_for(h, w)
        pad = [(0, bh - h), (0, bw - w)] + [(0, 0)] * (image.ndim - 2)
        return np.pad(image, pad)

    @staticmethod
    def crop(padded: np.ndarray, h: int, w: int) -> np.ndarray:
        """The inverse of :meth:`pad`: slice the original extents back."""
        return padded[:h, :w]


class SlotPool:
    """Per-bucket slot accounting + achieved-occupancy bookkeeping.

    Each bucket owns ``batch`` slots (the compiled executable's batch dim).
    ``admit`` moves queued requests into free slots; ``drain`` empties the
    filled slots for one batch step and records ``filled / batch`` — the
    occupancy sample the bench reports (mean over executed steps; padding
    rows the data axis needs are *not* occupancy, which is the point of
    measuring it).

    ``max_queue`` bounds each bucket's pending queue: a full queue makes
    ``enqueue`` return False (the server sheds the request as REJECTED)
    instead of growing without limit under overload — backpressure at the
    front door, not an OOM in the engine loop.  None keeps the historical
    unbounded behavior.
    """

    def __init__(self, buckets: Sequence[Tuple[int, int]], batch: int,
                 max_queue: Optional[int] = None):
        self.batch = int(batch)
        self.max_queue = None if max_queue is None else int(max_queue)
        self.queues: Dict[Tuple[int, int], deque] = {
            b: deque() for b in buckets}
        self.slots: Dict[Tuple[int, int], List[ConvRequest]] = {
            b: [] for b in buckets}
        self._occ_samples: Dict[Tuple[int, int], List[float]] = {
            b: [] for b in buckets}

    def enqueue(self, req: ConvRequest) -> bool:
        """Queue for admission; -> False (untouched queue) when the
        bucket's bounded queue is full — the caller owns the shed."""
        q = self.queues[req.bucket]
        if self.max_queue is not None and len(q) >= self.max_queue:
            return False
        q.append(req)
        return True

    def admit(self) -> int:
        """Fill free slots from each bucket's queue; -> requests admitted.

        ``slots.admit`` is an injection seam (DESIGN.md §16): a transient
        fault here leaves every queue intact — admission simply retries
        next step — which the server counts rather than crashes on.
        """
        _inject_fault("slots.admit")
        moved = 0
        for b, q in self.queues.items():
            free = self.batch - len(self.slots[b])
            for _ in range(min(free, len(q))):
                self.slots[b].append(q.popleft())
                moved += 1
        return moved

    def sweep(self, predicate) -> List[ConvRequest]:
        """Remove and return every *queued* request matching ``predicate``
        (slotted requests are already committed to the next batch).  The
        server's deadline pass: expired requests leave through here and
        never occupy a slot."""
        removed: List[ConvRequest] = []
        for b, q in self.queues.items():
            kept: deque = deque()
            for r in q:
                (removed if predicate(r) else kept).append(r)
            self.queues[b] = kept
        return removed

    @property
    def queue_depth(self) -> int:
        """Requests waiting for a slot (excludes slotted ones)."""
        return sum(len(q) for q in self.queues.values())

    def drain(self, bucket: Tuple[int, int]) -> List[ConvRequest]:
        """Take the bucket's filled slots for one step (slots free here —
        conv inference completes in one step) and record occupancy."""
        batch = self.slots[bucket]
        if batch:
            self._occ_samples[bucket].append(len(batch) / self.batch)
        self.slots[bucket] = []
        return batch

    @property
    def pending(self) -> int:
        return (sum(len(q) for q in self.queues.values())
                + sum(len(s) for s in self.slots.values()))

    def occupancy(self, bucket: Optional[Tuple[int, int]] = None) -> float:
        """Mean achieved batch occupancy over executed steps (0 if none) —
        pooled over every bucket, or for one bucket when given."""
        samples = (self._occ_samples[bucket] if bucket is not None else
                   [s for ss in self._occ_samples.values() for s in ss])
        if not samples:
            return 0.0
        return float(np.mean(samples))


def _zero_slot(cache, b: int):
    def zero(leaf):
        if leaf.ndim >= 2 and leaf.shape[1] > b:   # [periods, B, ...]
            return leaf.at[:, b].set(0)
        return leaf
    return jax.tree.map(zero, cache)
