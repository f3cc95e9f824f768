"""Device failure policy: the guarded converge-dispatch ladder.

A converge dispatch that dies mid-merge (TPU OOM, preemption, a
transient XLA ``RuntimeError``) used to propagate straight through
``Crdt.apply_updates`` and kill the apply path. Every guarded dispatch
now runs the ladder

    attempt → retry once → split the work in half → host route

where each rung is strictly cheaper in assumptions: the retry covers
transient faults (preemption, a dropped device interaction), the split
covers size-dependent faults (an OOM that a half-size batch survives —
only offered where the work genuinely halves, e.g. independent
parents), and the host route covers a dead device entirely (the scalar
path is the semantics oracle, so the answer is bit-identical, just
slower). Counters: ``device.retries``, ``device.fallback`` (+
``device.fallback_by{route=...}``), ``device.dispatch_errors``.

Fault injection rides :func:`crdt_tpu.ops.device.set_device_fault_hook`
— the hook fires BEFORE each guarded attempt and may raise
``RuntimeError`` to simulate a device fault, so chaos schedules never
need a real dying accelerator (see
:class:`crdt_tpu.guard.faults.DeviceFaultPlan`).
"""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple

from crdt_tpu.obs.recorder import get_recorder
from crdt_tpu.obs.tracer import get_tracer


def _attempt(stage: str, run: Callable, attempt: int):
    from crdt_tpu.ops.device import device_fault_hook

    hook = device_fault_hook()
    if hook is not None:
        hook(stage, attempt)  # may raise RuntimeError (injected fault)
    return run()


def dispatch_guarded(
    stage: str,
    run: Callable[[], object],
    *,
    split: Optional[Callable[[], Optional[List[Tuple[Callable, Callable]]]]] = None,
    host: Optional[Callable[[], object]] = None,
):
    """Run ``run()`` (a device dispatch) under the failure ladder.

    ``split``, when given, returns a list of ``(run_half, host_half)``
    thunk pairs covering the same work in independent pieces (or
    ``None``/a single pair when the work cannot split); each piece is
    re-guarded individually. ``host`` recomputes the WHOLE result on
    host. With neither rung available the second failure re-raises —
    the caller opted out of degradation.

    Only ``RuntimeError`` (the class XLA device errors subclass) is a
    ladder trigger; anything else is a programming error and
    propagates immediately.
    """
    tracer = get_tracer()
    err: Optional[RuntimeError] = None
    for attempt in (0, 1):
        try:
            if attempt:
                tracer.count("device.retries")
            return _attempt(stage, run, attempt)
        except RuntimeError as e:
            err = e
            tracer.count("device.dispatch_errors")
    rec = get_recorder()
    if rec.enabled:
        rec.record("device.fault", stage=stage, error=repr(err)[:200])
    halves = split() if split is not None else None
    if halves and len(halves) > 1:
        tracer.count("device.fallback")
        tracer.count("device.fallback_by", labels={"route": "split"})
        return [
            dispatch_guarded(stage, run_half, host=host_half)
            for run_half, host_half in halves
        ]
    if host is not None:
        tracer.count("device.fallback")
        tracer.count("device.fallback_by", labels={"route": "host"})
        return host()
    raise err
