"""Pallas interpret mode is asked for, never inferred.

The kernels of this package compile for the TPU. A caller without one (the
CPU test tier) asks for the Pallas interpreter per call
(``interpret=True``) or, where it reaches a kernel only through a model or
an engine, for the whole process (:func:`set_interpret`, which
``tests/conftest.py`` calls once). Nothing here looks at ``jax.devices()``:
a kernel that drops to the interpreter because of what the first device
happens to be hides the very failure a chip run exists to find.
"""

from __future__ import annotations

from typing import Optional

_process_wide = False


def set_interpret(on: bool) -> None:
    """Interpret every kernel call that does not say otherwise. Read when
    a kernel is traced, so set it before the first jit."""
    global _process_wide
    _process_wide = bool(on)


def resolve(interpret: Optional[bool]) -> bool:
    """A call's own ``interpret=`` wins; ``None`` takes the process's."""
    return _process_wide if interpret is None else bool(interpret)


def tpu_params(interpret: Optional[bool]):
    """What a kernel that moves data by DMA and waits on semaphores hands
    to ``pallas_call(interpret=...)``: the TPU interpreter, which models
    HBM, VMEM, DMAs and semaphores (the generic one knows none of them).
    Scratch memory starts as NaN there, as it may on the chip."""
    if not resolve(interpret):
        return False
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.InterpretParams()
