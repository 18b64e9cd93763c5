"""Profiler range annotation (ref: deepspeed/utils/nvtx.py:12
instrument_w_nvtx + accelerator range_push/pop).

On TPU the analog of an NVTX range on a host thread is
``jax.profiler.TraceAnnotation``: an event on the host plane of a running
``jax.profiler`` trace, on the same clock as the device planes, and one
inactive ``TraceMe`` when no profile runs.  (``jax.named_scope`` names
operations inside a traced function; on a host thread it names nothing.)"""

import functools

import jax


def profiler_range(name: str):
    """A host-side profiler range: a context manager whose
    ``set_metadata(**kw)`` may add to the range's metadata until it
    closes.  This is the ``annotate`` factory of
    ``telemetry.step_anatomy.StepAnatomy``, which stays free of jax."""
    return jax.profiler.TraceAnnotation(name)


def instrument_w_nvtx(func):
    """Decorate ``func`` so its execution appears as a named range in
    profiler traces (ref: nvtx.py instrument_w_nvtx)."""

    @functools.wraps(func)
    def wrapped(*args, **kwargs):
        with profiler_range(func.__qualname__):
            return func(*args, **kwargs)

    return wrapped


def range_push(name: str):
    """ref: accelerator.range_push — host-side profiler range begin."""
    ann = profiler_range(name)
    ann.__enter__()
    _STACK.append(ann)


def range_pop():
    if _STACK:
        _STACK.pop().__exit__(None, None, None)


_STACK = []
