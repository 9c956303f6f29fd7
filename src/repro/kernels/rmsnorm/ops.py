"""Jitted wrapper for the fused RMSNorm kernel."""

from __future__ import annotations

from functools import partial

import jax

from .. import interpret_default
from .ref import rmsnorm_ref
from .rmsnorm import rmsnorm_pallas
from ...obs.profiling import profiled


@partial(jax.jit, static_argnames=("eps", "interpret", "use_kernel"))
def _rmsnorm_jit(x, scale, *, eps: float, interpret: bool, use_kernel: bool):
    if use_kernel:
        return rmsnorm_pallas(x, scale, eps=eps, interpret=interpret)
    return rmsnorm_ref(x, scale, eps=eps)


def rmsnorm(x, scale, eps: float = 1e-5, interpret: bool | None = None,
            use_kernel: bool = True):
    if interpret is None:
        interpret = interpret_default()
    # launches route through the (no-op by default) kernel profiler
    return profiled("rmsnorm", _rmsnorm_jit, x, scale, eps=eps,
                    interpret=interpret, use_kernel=use_kernel)
