"""Wall-clock spans of the serve path, written into the JAX profiler trace.

The rest of this package runs on the virtual cost-model clock and is
deterministic.  These spans are its wall-clock counterpart: each is a
``jax.profiler.TraceAnnotation`` on the profiler's host plane, on the same
clock as the device ops, so a trace shows which stage of
``SearchSystem.serve`` the host was in while the chip ran or sat idle.
Outside a profiler trace a span costs one annotation enter/exit (about a
microsecond) and records nothing.

``cascade.serve`` is the root of one ``SearchSystem.serve`` call; the
stage spans below it are disjoint.  Inside ``cascade.stage1`` each engine
branch that serves rows is one ``cascade.jass`` or ``cascade.bmw``.  Three
spans may nest inside any stage: ``cascade.dispatch`` where the host builds
arguments, copies them to the device and enqueues device programs, ending
before the read-back it feeds; ``cascade.sync`` where it waits for the
device (:func:`fetch`, the serve path's one blocking read per stage or
engine branch); and ``cascade.account`` where it does virtual-clock
bookkeeping (cost-model times, stats), which also has spans of its own
between the stages.  A stage's time outside its child spans is its host
numpy work.

Every Python garbage collection is a ``python.gc`` span, in every run,
carrying the collection's ``generation``: the hook is installed when this
module is imported.
"""

from __future__ import annotations

import gc

import jax
from jax.profiler import TraceAnnotation

SERVE = "cascade.serve"        # one SearchSystem.serve call
STAGE0 = "cascade.stage0"      # features, stacked forest, route, modality
STAGE1 = "cascade.stage1"      # level-cut split, engines per segment, merge, dense
STAGE1_JASS = "cascade.jass"   # JASS calls over every segment, its read-back
STAGE1_BMW = "cascade.bmw"     # BMW calls over every segment, its read-back
STAGE2 = "cascade.stage2"      # stage2_afford, lane budget, re-rank, skips
REPLICAS = "cascade.replicas"  # replica picks, fault plan, pool feedback
ACCOUNT = "cascade.account"    # virtual-clock latencies, stats, traces
CACHE = "cascade.cache"        # serving-cache lookup and fill
SYNC = "cascade.sync"          # one blocking device-to-host read
DISPATCH = "cascade.dispatch"  # arguments built, copied in, programs enqueued
GC = "python.gc"               # a Python garbage collection
NAMES = (SERVE, STAGE0, STAGE1, STAGE1_JASS, STAGE1_BMW, STAGE2, REPLICAS,
         ACCOUNT, CACHE, SYNC, DISPATCH, GC)


def span(name: str) -> TraceAnnotation:
    """A context manager that marks ``name`` on the profiler's host plane."""
    return TraceAnnotation(name)


def fetch(*arrays):
    """Copy ``arrays`` (pytrees of device arrays) to the host in one wait,
    inside a ``cascade.sync`` span; returns the tuple of host copies."""
    with TraceAnnotation(SYNC):
        return jax.device_get(arrays)


_gc_open: list = []


def _gc_hook(phase: str, info: dict) -> None:
    """Open a ``python.gc`` span when a collection starts, close it when
    the collection stops (both on the collecting thread)."""
    if phase == "start":
        ann = TraceAnnotation(GC, generation=info["generation"])
        ann.__enter__()
        _gc_open.append(ann)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


gc.callbacks.append(_gc_hook)
