"""What the running backend decides for the Pallas kernels: the machine
model their tiles are fitted against, and whether they run compiled or in
interpret mode.

On a TPU both follow from the device: the model is looked up by
``device_kind`` (an unknown kind is an error, never a default) and the
kernels always compile.  Off the TPU the kernels run in Pallas interpret
mode, emulating the v5e launches, so the v5e model applies.
"""
from __future__ import annotations

from typing import Optional

import jax

from .blocking import MachineModel, TPU_V5E

__all__ = ["DEVICE_KINDS", "machine_for_device", "resolve_machine",
           "resolve_interpret"]

# ``jax.devices()[0].device_kind`` -> the machine model of that chip; each
# model names the source of its peaks (``MachineModel.source``).
DEVICE_KINDS = {
    "TPU v5 lite": TPU_V5E,
    "TPU v5e": TPU_V5E,
}


def machine_for_device(device=None) -> MachineModel:
    """The machine model of ``device`` (default: the first JAX device)."""
    device = jax.devices()[0] if device is None else device
    if device.platform != "tpu":
        return TPU_V5E
    try:
        return DEVICE_KINDS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no machine model for TPU device_kind {device.device_kind!r}; "
            f"known kinds: {sorted(DEVICE_KINDS)} (core/backend.py "
            f"DEVICE_KINDS)") from None


def resolve_machine(machine: Optional[MachineModel]) -> MachineModel:
    """An explicit model, else the running device's."""
    return machine_for_device() if machine is None else machine


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` follows the backend: interpret mode exactly when the
    default backend is not a TPU.  On a TPU, interpret mode is refused —
    a kernel that silently ran in the interpreter there would hide the
    device."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("interpret=True on a TPU backend: the Pallas "
                         "kernels run compiled on the chip")
    return bool(interpret)
