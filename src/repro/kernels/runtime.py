"""Execution-mode resolution shared by every Pallas kernel wrapper.

The kernels take ``interpret=None`` by default and resolve it here: on a
TPU backend they lower to compiled Mosaic, on the CPU backend (tests,
``JAX_PLATFORMS=cpu``) they run the Pallas interpreter — same semantics,
no hand-edited flags when moving between machines.  Any other backend is
an error: the kernels are written for Mosaic, and silently interpreting
them on an accelerator would hide a missing chip path.  Pass an explicit
``True``/``False`` to override (e.g. force interpret mode on TPU while
debugging a kernel).
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> compiled on TPU, interpreted on CPU; bools pass through.

    Called inside the jitted kernel wrappers, where ``interpret`` is a
    static argument — the resolved value is a plain python bool by the time
    ``pl.pallas_call`` sees it.  Raises ``RuntimeError`` for ``None`` on a
    backend that is neither.
    """
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for TPU and interpret on CPU; backend "
        f"{backend!r} is neither (pass interpret=True to force the "
        "interpreter)")
