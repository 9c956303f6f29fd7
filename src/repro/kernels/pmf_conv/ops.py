"""Jitted front door for the batched PMF-convolution kernel.

``batched_success`` is what a TPU-resident scheduler calls once per mapping
event: all (task x machine-tail) chances in a single launch, replacing the
per-pair Python convolutions of the CPU path.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import interpret_default
from .pmf_conv import pmf_conv_pallas
from .ref import pmf_conv_ref
from ...obs.profiling import profiled


@partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def _pmf_conv_jit(pet, pct, dl, *, interpret: bool, use_kernel: bool):
    if use_kernel:
        return pmf_conv_pallas(pet, pct, dl, interpret=interpret)
    return pmf_conv_ref(pet, pct, dl)


def pmf_conv(pet, pct, dl, interpret: bool | None = None,
             use_kernel: bool = True):
    """(out, success) for a batch of PEND_DROP convolutions.

    ``interpret=None`` resolves through ``interpret_default()``: native on
    a TPU/GPU, the Pallas interpreter only where JAX has no accelerator.
    Launches route through ``repro.obs.profiling.profiled`` — a plain
    call unless a recorder is installed (``repro.obs.install``), whose
    ``kernel.pmf_conv`` span then splits dispatch (trace/compile) from
    execute (``block_until_ready``) per launch."""
    if interpret is None:
        interpret = interpret_default()
    return profiled("pmf_conv", _pmf_conv_jit, pet, pct, dl,
                    interpret=interpret, use_kernel=use_kernel)


def pack_pmfs(pmfs, length: int) -> tuple[np.ndarray, np.ndarray]:
    """Compact + pad a list of core.pmf.PMF onto a fixed grid.

    Returns (values (N, length), offsets (N,)).  Mass beyond the grid is
    folded into the last bucket (impulse compaction's max-range clamp)."""
    vals = np.zeros((len(pmfs), length), np.float32)
    offs = np.zeros((len(pmfs),), np.int64)
    for i, p in enumerate(pmfs):
        offs[i] = p.offset
        v = np.asarray(p.values, np.float32)
        if len(v) > length:
            head, tail = v[:length - 1], v[length - 1:]
            vals[i, :length - 1] = head
            vals[i, length - 1] = tail.sum()
        else:
            vals[i, :len(v)] = v
    return vals, offs


def batched_success(pets, pcts, deadlines, length: int = 128,
                    interpret: bool | None = None) -> np.ndarray:
    """Chance-of-success for N (task, machine-tail) pairs.

    ``pets``/``pcts``: lists of PMF; ``deadlines``: absolute times.
    Offsets are folded into the per-row deadline index.  Matches
    ``core.pmf.chance_of_success`` (droppable previous task) exactly for
    PMFs that fit the grid.
    """
    pet_v, pet_o = pack_pmfs(pets, length)
    pct_v, pct_o = pack_pmfs(pcts, length)
    # The kernel reads one index, dl - pet_origin - pct_origin, both as the
    # success bound on the out grid and as the PEND cut on the PCT grid.
    # For a PET impulse k the previous task must free the machine at
    # c <= dl - k (and c < dl).  With the PET grid starting one step before
    # its first impulse k0 >= 1, the cut admits exactly c <= dl - k0, so
    # both uses of the one index are exact.
    lead = pet_o >= 1
    shifted = np.zeros_like(pet_v)
    shifted[:, 1:] = pet_v[:, :-1]
    shifted[:, -1] += pet_v[:, -1]      # keep mass already clamped there
    pet_v = np.where(lead[:, None], shifted, pet_v)
    pet_o = pet_o - lead
    dl_idx = np.asarray(deadlines, np.int64) - pet_o - pct_o
    dl_kernel = np.maximum(dl_idx, -1).astype(np.float32)
    _, suc = pmf_conv(jnp.asarray(pet_v), jnp.asarray(pct_v),
                      jnp.asarray(dl_kernel), interpret=interpret)
    return np.asarray(suc)
