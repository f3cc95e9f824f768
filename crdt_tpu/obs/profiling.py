"""Device-side profiling hooks: XProf capture + dispatch annotations.

- :func:`jax_profile` wraps ``jax.profiler.start_trace/stop_trace``
  so a convergence dispatch can be captured for TensorBoard/XProf.
  Hardened (vs the old ``utils/trace.py`` version): a failure inside
  the block can never leave the profiler running, a failing
  ``stop_trace`` never masks the body's exception, and environments
  whose jax lacks a profiler (or ``ProfileOptions`` — absent in the
  pinned jax 0.4.x) degrade with a clear ``RuntimeError`` instead of
  an opaque ``AttributeError`` mid-setup.
- :func:`device_annotation` is the annotation seam for code with no
  tracer span of its own (the streaming executor's per-shard
  bracket): a ``jax.profiler.TraceAnnotation`` context manager when
  available, a shared no-op otherwise. Every enabled tracer span
  opens the same annotation as ``crdt.<span name>``
  (:class:`crdt_tpu.obs.tracer.Tracer`), so the program's phases land
  on the host plane of any running capture, on the device ops' clock.
  Resolution is cached after the first call (:func:`annotation_cls`);
  the steady-state cost without a profiler is one global check.
"""

from __future__ import annotations

from contextlib import contextmanager, nullcontext
from typing import Iterator, Optional

_NULL_CTX = nullcontext()  # reusable/reentrant stdlib no-op
_annotation_cls: Optional[object] = None  # None = unresolved, False = absent


def annotation_cls():
    """The profiler's ``TraceAnnotation`` class, resolved once; False
    where this jax has no profiler."""
    global _annotation_cls
    if _annotation_cls is None:
        try:
            import jax

            _annotation_cls = jax.profiler.TraceAnnotation
        except Exception:
            _annotation_cls = False
    return _annotation_cls


def device_annotation(name: str):
    """Context manager annotating enclosed dispatches for XProf."""
    cls = annotation_cls()
    if not cls:
        return _NULL_CTX
    return cls(name)


@contextmanager
def jax_profile(log_dir: str, *, host_tracer_level: int = 2) -> Iterator[None]:
    """Capture a device trace (TensorBoard/XProf format) around a
    block — e.g. one ``converge_maps`` dispatch or a fleet step."""
    try:
        import jax

        profiler = jax.profiler
        start = profiler.start_trace
        stop = profiler.stop_trace
    except (ImportError, AttributeError) as exc:
        raise RuntimeError(
            "jax profiler unavailable (CPU-only or stripped jax build): "
            f"{exc!r}"
        ) from exc
    kwargs = {}
    opts_cls = getattr(profiler, "ProfileOptions", None)
    if opts_cls is not None:
        # newer jax: host tracer level rides ProfileOptions; absent on
        # the pinned 0.4.x line, where start_trace takes no options
        try:
            opts = opts_cls()
            opts.host_tracer_level = host_tracer_level
            kwargs["profiler_options"] = opts
        except Exception:
            pass
    try:
        start(log_dir, **kwargs)
    except Exception as exc:
        raise RuntimeError(
            f"jax profiler failed to start ({log_dir!r}): {exc!r}"
        ) from exc
    try:
        yield
    except BaseException:
        # the body failed: stop the profiler so it cannot leak into
        # (and corrupt) the next capture, but never mask the real
        # error with a stop_trace failure
        try:
            stop()
        except Exception:
            pass
        raise
    else:
        stop()
