"""Kernel launches on the span API (DESIGN.md §2.9).

Every kernel front door (``pmf_conv``, ``decode_attention``,
``paged_decode_attention``, ``rmsnorm``) routes its call through
:func:`profiled`:

  * called while JAX traces an enclosing program (some argument is a
    tracer), it opens ``jax.named_scope(name)``: the kernel's operations
    carry the name in their metadata, and nothing is timed, since tracing
    is not execution;
  * called eagerly with a recorder installed (:func:`install`), the launch
    becomes a span ``kernel.<name>`` with children ``dispatch`` (until the
    jitted call returns: tracing and compilation on a new shape) and
    ``sync`` (until ``jax.block_until_ready`` returns: device execution),
    both tagged ``program=<name>`` so that the recorder's ``programs``
    sums them.  The first launch per (kernel, shape key) carries
    ``cold=True``.  The engine's two step programs are launched by the
    unit's own step loop, whose ``launch`` / ``sync`` spans carry their
    program's name the same way;
  * otherwise it is a plain call.

No JAX import happens at module scope, so the pure-numpy simulation path
can import ``repro.obs`` freely.
"""

from __future__ import annotations

import sys

__all__ = ["install", "profiled", "current"]

_TEL = None
_SEEN: set = set()


def install(tel) -> None:
    """Install (or with ``None``, remove) the process-wide recorder that
    eager kernel launches report to."""
    global _TEL
    _TEL = tel
    _SEEN.clear()


def current():
    return _TEL


def _shape_key(args, kwargs) -> tuple:
    parts = []
    for a in list(args) + sorted(kwargs.items(), key=lambda kv: kv[0]):
        v = a[1] if isinstance(a, tuple) and len(a) == 2 else a
        shape = getattr(v, "shape", None)
        if shape is not None:
            parts.append(("arr", tuple(shape), str(getattr(v, "dtype", ""))))
        elif isinstance(v, (int, float, bool, str, type(None))):
            parts.append(v)
        else:
            parts.append(type(v).__name__)
    return tuple(parts)


def _traced(args, kwargs) -> bool:
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    tracer = jax.core.Tracer
    return any(isinstance(a, tracer) for a in args) or any(
        isinstance(v, tracer) for v in kwargs.values())


def profiled(name: str, fn, *args, **kwargs):
    """Launch ``fn(*args, **kwargs)`` as the kernel ``name`` (see module
    doc)."""
    if _traced(args, kwargs):
        import jax

        with jax.named_scope(name):
            return fn(*args, **kwargs)
    tel = _TEL
    if tel is None:
        return fn(*args, **kwargs)
    import jax

    key = (name, _shape_key(args, kwargs))
    cold = key not in _SEEN
    _SEEN.add(key)
    with tel.span(f"kernel.{name}", cold=cold):
        with tel.span("dispatch", program=name, cold=cold) as d:
            out = fn(*args, **kwargs)
        with tel.span("sync", program=name) as s:
            # a device fault surfaces here and propagates to the caller
            jax.block_until_ready(out)
    if tel.enabled:
        tel.metrics.observe("kernel_dispatch_s", d.duration, kernel=name,
                            cold=str(cold).lower())
        tel.metrics.observe("kernel_execute_s", s.duration, kernel=name)
        tel.metrics.inc("kernel_launches", kernel=name)
    return out

