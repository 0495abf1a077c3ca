"""ConvContext: the one execution-context object every conv call accepts.

Before ISSUE 9 the *how* of a convolution — which dispatcher, which forced
impl, interpret mode, machine model, window-vs-stream, precision policy —
was five or six loose keyword arguments threaded separately through
``nn/conv.py``, ``kernels/ops.py``, ``train/trainstep.py`` and
``launch/conv_serve.py``.  Every new knob meant touching every layer of the
call stack, every serving cache had to key on the full kwarg tuple, and a
call site could not hand "run it exactly like this" to another call site as
one value.

``ConvContext`` is that value: a frozen, hashable record of the execution
context (never the geometry — geometry lives in :class:`ConvSpec` and on
the layer).  Each field is ``None`` for "defer": the layer's own field
(``machine``/``stream``/``precision``) or the process default
(``get_dispatcher()``, backend-derived ``interpret``) fills it at the point
of use, exactly as the loose kwargs did.  Because it is frozen and
hashable it rides ``functools.lru_cache`` (the sharded-serving forward
caches on the single context object), ``jax.jit`` static arguments and
dict keys without unpacking.

The legacy loose kwargs (``dispatch=``, ``impl=``, ``interpret=``,
``stream=``, ``precision=``) got exactly one release of deprecation shim
(the ISSUE 9 contract) and are now gone: every conv entry point takes
``context=`` and nothing else, and a stale call site fails with a
``TypeError`` that names :class:`ConvContext` and shows the migration
(:func:`reject_legacy_kwargs` is the shared raiser).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

from .backend import resolve_machine
from .blocking import MachineModel
from .dispatch import ConvDispatcher, Impl, KernelRoute
from .precision import Precision, resolve_precision

__all__ = ["ConvContext", "as_context", "reject_legacy_kwargs"]

# stream accepts the legacy bool knob or a resolved per-direction route
Stream = Union[bool, KernelRoute, None]


@dataclasses.dataclass(frozen=True)
class ConvContext:
    """How to run a conv (not what conv to run).  Frozen + hashable.

    Every field defaults to ``None`` = "defer to the layer field / process
    default", so ``ConvContext()`` is the do-nothing context and a partial
    context (say, ``ConvContext(impl="jnp")``) overrides exactly one
    decision.  String shorthands normalize on construction (``impl="jnp"``
    -> :class:`Impl`, ``precision="bf16"`` -> :class:`Precision`), so two
    spellings of the same context compare and hash equal — the property the
    serving tier's ``lru_cache`` relies on.

      dispatch   the :class:`ConvDispatcher` resolving keys (None -> the
                 process-wide one over the checked-in table).  Hashes by
                 identity, like the dispatcher itself.
      impl       force one :class:`Impl` for every conv — beats table and
                 prior (the per-call override tier).
      interpret  run Pallas kernels in interpret mode (None -> auto:
                 interpret off-TPU).
      machine    :class:`MachineModel` the blocking models fit against
                 (None -> the layer's ``machine`` field, else the running
                 device's model).
      stream     window-vs-stream override inside the dense Pallas family:
                 bool forces all three directions, a :class:`KernelRoute`
                 pins them per direction, None lets the dispatcher resolve.
      precision  mixed-precision policy (None -> the layer's ``precision``
                 field; a concrete policy overrides every layer it reaches,
                 the ``BlockedCNN``/``TrainSettings`` pass-down contract).
    """

    dispatch: Optional[ConvDispatcher] = None
    impl: Union[Impl, str, None] = None
    interpret: Optional[bool] = None
    machine: Optional[MachineModel] = None
    stream: Stream = None
    precision: Union[Precision, str, None] = None

    def __post_init__(self):
        if self.impl is not None and not isinstance(self.impl, Impl):
            object.__setattr__(self, "impl", Impl(self.impl))
        if self.precision is not None and not isinstance(self.precision,
                                                         Precision):
            object.__setattr__(self, "precision",
                               resolve_precision(self.precision))

    # -- composition -------------------------------------------------------
    def override(self, **fields) -> "ConvContext":
        """A new context with the given non-None fields replaced (None
        arguments are "no opinion" and leave this context's value alone)."""
        live = {k: v for k, v in fields.items() if v is not None}
        return dataclasses.replace(self, **live) if live else self

    def resolve_precision_for(self, layer_default) -> Precision:
        """The policy this context implies for a layer with the given
        default — the single reader for the precision pass-down rule."""
        return resolve_precision(
            layer_default if self.precision is None else self.precision)

    def resolve_machine_for(self, layer_default: Optional[MachineModel]
                            ) -> MachineModel:
        """This context's model, else the layer's, else the running
        device's (``core.backend.machine_for_device``)."""
        return resolve_machine(
            layer_default if self.machine is None else self.machine)

    def resolve_stream_for(self, layer_default) -> Stream:
        return layer_default if self.stream is None else self.stream


# the do-nothing context every defaulted call site resolves to (one shared
# instance so `as_context(None)` allocates nothing)
_EMPTY = ConvContext()


def as_context(context: Optional[ConvContext]) -> ConvContext:
    """``None`` -> the shared do-nothing context; a context passes through.

    The one defaulting rule for every conv entry point — a non-context
    value (say a stray string) fails here, close to the call site, instead
    of deep inside a kernel wrapper.
    """
    if context is None:
        return _EMPTY
    if not isinstance(context, ConvContext):
        raise TypeError(
            f"context= expects a ConvContext, got {type(context).__name__}")
    return context


def reject_legacy_kwargs(where: str, kwargs: dict) -> None:
    """Raise the one migration ``TypeError`` for removed loose conv kwargs.

    Entry points accept ``**legacy`` and route it here, so a pre-ISSUE-10
    call site (``impl=``/``dispatch=``/``interpret=``/``precision=``/
    ``stream=``) fails with the fix in the message rather than a bare
    "unexpected keyword argument".
    """
    if kwargs:
        names = ", ".join(sorted(kwargs))
        raise TypeError(
            f"{where}: the loose conv kwargs are gone ({names}); pass the "
            f"one execution-context object instead — "
            f"context=ConvContext({names.replace(', ', '=..., ')}=...) "
            "(repro.core.context.ConvContext)")
