"""The program's own spans and counters (``repro.obs``), and the readings
behind the metric files that read them: ``host.dispatch.share``,
``host.pack.share``, ``host.assemble.share``, ``lane_step.useful_share``
and ``scheduler.useful_share``.

``before(ctx)`` resets the recorder and turns it on just before the window
opens; the harness calls it in ``--trace 1`` runs only, so untraced runs
keep the recorder off.  The first reading takes one snapshot, keeps it in
``ctx.store`` and turns the recorder off again, so the reference check
after the window, and the next run in the same process, run with it off.
Where the program has no recorder every reading returns ``None``.
"""
from __future__ import annotations

ARMED = "recorder.armed"
SNAPSHOT = "recorder.snapshot"


def _obs():
    try:
        from repro import obs
    except ImportError:
        return None
    return obs


def before(ctx):
    if ctx.store.get(ARMED):
        return
    ctx.store[ARMED] = True
    obs = _obs()
    if obs is not None:
        obs.reset()
        obs.enable()


def snapshot(ctx) -> "dict | None":
    """The recorder's totals over the window, taken once."""
    if SNAPSHOT not in ctx.store:
        obs = _obs()
        ctx.store[SNAPSHOT] = None
        if obs is not None and ctx.store.get(ARMED):
            ctx.store[SNAPSHOT] = obs.snapshot()
            obs.disable()
    return ctx.store[SNAPSHOT]


def _self_share(ctx, names) -> "float | None":
    """% of the window in the self time of the spans ``names``."""
    snap = snapshot(ctx)
    if snap is None or ctx.window_s <= 0:
        return None
    spans = [snap["spans"][n] for n in names if n in snap["spans"]]
    if not spans:
        return None
    return 100.0 * sum(s["self_s"] for s in spans) / ctx.window_s


def _ratio(ctx, part: str, whole: str) -> "float | None":
    """100 x counter ``part`` / counter ``whole``."""
    snap = snapshot(ctx)
    if snap is None or not snap["counters"].get(whole):
        return None
    return 100.0 * snap["counters"].get(part, 0) / snap["counters"][whole]


def dispatch_share(ctx):
    """% of the window in the self time of ``sim.run_batch`` and
    ``sim.run_cells``: the façade, the planner, request checks and the
    executable lookup."""
    return _self_share(ctx, ("sim.run_batch", "sim.run_cells"))


def pack_share(ctx):
    """% of the window in the self time of ``sim.pack``: operand arrays,
    row hash-consing and gathers."""
    return _self_share(ctx, ("sim.pack",))


def assemble_share(ctx):
    """% of the window in the self time of ``sim.assemble``: device-to-host
    copies of the results and the per-warp and per-SM result objects."""
    return _self_share(ctx, ("sim.assemble",))


def lane_step_useful_share(ctx):
    """% of the lane step's row-iterations (rows, padding included, x the
    batch's loop trip count) that executed an instruction of a row that is
    not padding."""
    return _ratio(ctx, "lane_step.useful_steps", "lane_step.row_iterations")


def scheduler_useful_share(ctx):
    """% of the scheduler's scanned cell-slots (cells x slot capacity) that
    issued an instruction."""
    return _ratio(ctx, "schedule.slots_issued", "schedule.slots_scanned")
