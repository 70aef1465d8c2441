"""The readings behind the metric files in ``bench/metrics``.

A metric file is found by its name and holds that metric's reader:
``read(ctx)``, and optionally ``before(ctx)``, called just before the
window opens.  A reading that finds nothing to read returns ``None`` and
the harness leaves the metric out.
"""
from __future__ import annotations

#: XLA module names the trace prints for the program's two device programs:
#: the vmapped Hanoi lane step (hanoi_jax batches and sm_jax's warp phase)
#: and sm_jax's issue scheduler
LANE_STEP_MODULE = "jit_one"
SCHEDULER_MODULE = "jit_schedule"


def warp_instr_per_s(ctx):
    """Simulated warp instructions (issue slots) of every unit completed in
    the window, over the whole window on the host clock."""
    if ctx.window_s <= 0:
        return None
    return sum(o.warp_instr for o in ctx.outcomes) / ctx.window_s


def setup_s(ctx):
    """Seconds from process start to the start of the window."""
    return ctx.setup_s


def idle_share(ctx):
    """% of the traced window in which no program ran on the device."""
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)


def peak_bytes(ctx):
    """Peak device memory in use on the fullest chip, read after the
    window."""
    return ctx.device.get("memory_peak_bytes")


def lane_step_ns_per_warp_step(ctx):
    """Device time of the lane-step program per useful warp-step: the
    executed rows' simulated steps, so padding rows and lockstep idling
    raise it."""
    if ctx.trace is None:
        return None
    device_s = ctx.trace.module_seconds(LANE_STEP_MODULE)
    steps = sum(o.useful_warp_steps for o in ctx.outcomes)
    if device_s <= 0 or steps <= 0:
        return None
    return 1e9 * device_s / steps


def scheduler_ns_per_slot(ctx):
    """Device time of the issue-scheduler program per issued SM slot."""
    if ctx.trace is None:
        return None
    device_s = ctx.trace.module_seconds(SCHEDULER_MODULE)
    slots = sum(o.issued_slots for o in ctx.outcomes)
    if device_s <= 0 or slots <= 0:
        return None
    return 1e9 * device_s / slots


def host_self_share(ctx):
    """% of the window outside the program's own device-call wall time (its
    ``wall_time_s`` around ``block_until_ready``)."""
    if ctx.window_s <= 0:
        return None
    device_s = sum(o.device_call_s for o in ctx.outcomes)
    return 100.0 * (ctx.window_s - device_s) / ctx.window_s


def _compiles():
    try:
        from repro.engine.adapters import batch_cache_stats
        from repro.engine.mechanisms import sm_jax
        return batch_cache_stats()["misses"] + len(sm_jax._SCHED_CACHE)
    except (ImportError, AttributeError, KeyError):
        return None


def compiles_before(ctx):
    ctx.store["compiles_before_window"] = _compiles()


def compiles_in_window(ctx):
    """New lane-step executables (``batch_cache_stats()["misses"]``) plus new
    scheduler executables (``sm_jax._SCHED_CACHE``) across the window."""
    start, end = ctx.store.get("compiles_before_window"), _compiles()
    if start is None or end is None:
        return None
    return end - start
