"""The queue-fed simulation service: signatures, coalescing, dispatch,
order-preserving reassembly, native-batch routing, archival, metrics.

Acceptance contract (ISSUE 3): for a mixed batch spanning >= 3 mechanisms,
heterogeneous configs/shapes, and an SM job, the service returns results
identical (status / final regs / mem / fuel) to sequential
``Simulator.run`` / ``run_sm`` calls, in submission order, while routing
every homogeneous ``hanoi_jax`` group through the native vmap
``batch_runner``.
"""
import json
import threading
import time

import numpy as np
import pytest

from repro.core import MachineConfig
from repro.core.programs import make_suite
from repro.engine import (RotatingJsonlSink, SimRequest, Simulator,
                          as_request, available_mechanisms, get_mechanism,
                          iter_mechanisms, register_mechanism,
                          unregister_mechanism)
from repro.service import (BatchCoalescer, SimulationService, execute_plan,
                           plan_dispatch, signature_of)

CFG = MachineConfig(n_threads=8, mem_size=64, max_steps=8192)
SUITE = make_suite(CFG, datasets=1)
SIM = Simulator("hanoi")


def _bench(name):
    return next(b for b in SUITE if b.name == name)


def _same_outcome(a, b):
    """status / final regs / mem / fuel equality — the acceptance fields."""
    assert a.status == b.status
    assert a.fuel_left == b.fuel_left
    assert a.finished == b.finished
    np.testing.assert_array_equal(a.regs, b.regs)
    np.testing.assert_array_equal(a.mem, b.mem)
    assert a.trace == b.trace


# ---------------------------------------------------------------------------
# execution signatures
# ---------------------------------------------------------------------------

def test_signature_groups_compatible_requests():
    a = signature_of("hanoi_jax", as_request(_bench("DIAMOND"), CFG))
    b = signature_of("hanoi_jax", as_request(_bench("GAUS0"), CFG))
    # different programs and memory images, same execution signature
    assert a == b and hash(a) == hash(b)
    assert a.batchable


@pytest.mark.parametrize("override,field", [
    (dict(fuel=17), "cfg"),                      # fuel folds into the cfg
    (dict(cfg=CFG._replace(n_threads=4)), "cfg"),
    (dict(majority_first=False), "majority_first"),
    (dict(active0=0b0011), "batchable"),
    (dict(record_trace=False), "record_trace"),
    (dict(bsync_skip_pcs=(3,)), "skip_pcs"),
    (dict(meta={"itps_patience": 1}), "meta"),
])
def test_signature_splits_on(override, field):
    # turing_oracle reads every field, the skip pcs included
    base = signature_of("turing_oracle", as_request(_bench("DIAMOND"), CFG))
    cfg = override.pop("cfg", CFG)
    changed = signature_of("turing_oracle", as_request(
        _bench("DIAMOND"), cfg, **override))
    assert base != changed
    assert getattr(base, field) != getattr(changed, field)


@pytest.mark.parametrize("mechanism,groups", [("hanoi_jax", 1),
                                              ("turing_oracle", 2)])
def test_skip_pcs_split_only_a_mechanism_that_reads_them(mechanism, groups):
    """BFSD carries oracle skip pcs.  hanoi_jax ignores them, so BFSD joins
    the others' batch and runs on one executable, matching numpy Hanoi;
    turing_oracle reads them and keeps BFSD apart."""
    from repro.engine.adapters import batch_cache_stats
    reqs = [as_request(_bench(n), CFG) for n in ("DIAMOND", "BFSD", "GAUS0")]
    assert reqs[1].bsync_skip_pcs and not reqs[0].bsync_skip_pcs
    mech = get_mechanism(mechanism)
    assert len(plan_dispatch(mech, reqs)) == groups
    ref = "hanoi" if mechanism == "hanoi_jax" else mechanism
    def lookups():
        s = batch_cache_stats()
        return s["hits"] + s["misses"] + s["disk_hits"]

    before = lookups()
    got = Simulator(mechanism).run_batch(reqs)
    assert lookups() - before == (1 if mechanism == "hanoi_jax" else 0)
    for req, res in zip(reqs, got):
        _same_outcome(res, Simulator(ref).run(req))


def test_signature_pad_class():
    short = signature_of("hanoi_jax", as_request(
        np.asarray(_bench("DIAMOND").program), CFG))
    assert short.pad_len % 32 == 0
    long_prog = np.concatenate([_bench("DIAMOND").program] * 8, axis=0)
    longer = signature_of("hanoi_jax", as_request(long_prog, CFG))
    assert longer.pad_len > short.pad_len     # different padding class


# ---------------------------------------------------------------------------
# coalescer flush rules (pure bookkeeping, fake clock)
# ---------------------------------------------------------------------------

def test_coalescer_size_flush():
    now = [0.0]
    c = BatchCoalescer(max_batch=3, max_wait_s=10.0, clock=lambda: now[0])
    sig = signature_of("hanoi", as_request(_bench("DIAMOND"), CFG))
    assert c.add(sig, "a") == (None, True)       # new bucket created
    assert c.add(sig, "b") == (None, False)      # joins the existing bucket
    full, created = c.add(sig, "c")
    assert not created
    assert full is not None and full.cause == "size"
    assert [e.payload for e in full.entries] == ["a", "b", "c"]
    assert c.depth() == 0


def test_coalescer_deadline_flush_only_when_due():
    now = [100.0]
    c = BatchCoalescer(max_batch=64, max_wait_s=0.5, clock=lambda: now[0])
    sig_a = signature_of("hanoi", as_request(_bench("DIAMOND"), CFG))
    sig_b = signature_of("simt_stack", as_request(_bench("DIAMOND"), CFG))
    c.add(sig_a, "a1")
    now[0] = 100.3
    c.add(sig_b, "b1")
    assert c.due() == []                          # nothing aged out yet
    assert c.next_deadline() == pytest.approx(100.5)
    now[0] = 100.6                                # only sig_a is due
    due = c.due()
    assert [g.signature for g in due] == [sig_a]
    assert due[0].cause == "deadline"
    assert c.depth() == 1                         # b1 still pending
    now[0] = 101.0
    assert [g.signature for g in c.due()] == [sig_b]


def test_coalescer_manual_flush_and_validation():
    c = BatchCoalescer(max_batch=4, max_wait_s=60.0)
    sig = signature_of("hanoi", as_request(_bench("DIAMOND"), CFG))
    c.add(sig, "x")
    groups = c.flush_all()
    assert len(groups) == 1 and groups[0].cause == "manual"
    assert c.depth() == 0 and c.next_deadline() is None
    with pytest.raises(ValueError):
        BatchCoalescer(max_batch=0)
    with pytest.raises(ValueError):
        BatchCoalescer(max_wait_s=-1)


# ---------------------------------------------------------------------------
# planner: the shared dispatch path
# ---------------------------------------------------------------------------

def test_plan_routes_homogeneous_subgroups_natively():
    mech = get_mechanism("hanoi_jax")
    reqs = [as_request(_bench("DIAMOND"), CFG),
            as_request(_bench("GAUS0"), CFG),
            as_request(_bench("DIAMOND"), CFG, fuel=64),    # different fuel
            as_request(_bench("DIAMOND"), CFG, active0=0b1)]  # masked entry
    plan = plan_dispatch(mech, reqs)
    routed = {i: g.native for g in plan for i in g.indices}
    assert routed == {0: True, 1: True, 2: True, 3: False}
    sizes = sorted(g.size for g in plan)
    assert sizes == [1, 1, 2]                    # mixed batch, 3 groups


def test_execute_plan_preserves_order_and_matches_singles():
    mech = get_mechanism("hanoi")
    names = ["HOTS0", "GAUS0", "RBFS0", "DIAMOND"]
    reqs = [as_request(_bench(n), CFG) for n in names]
    out = execute_plan(mech, reqs)
    for req, res in zip(reqs, out):
        _same_outcome(res, SIM.run(req))


def test_run_batch_mixed_jax_batch_still_uses_native_groups():
    """The façade regression the planner fixes: a heterogeneous batch no
    longer forfeits native execution for its homogeneous sub-groups."""
    reqs = [as_request(_bench("DIAMOND"), CFG),
            as_request(_bench("GAUS0"), CFG),
            as_request(_bench("DIAMOND"), CFG, fuel=64)]
    mech = get_mechanism("hanoi_jax")
    plan = plan_dispatch(mech, reqs)
    assert all(g.native for g in plan) and len(plan) == 2
    out = SIM.run_batch(reqs, mechanism="hanoi_jax")
    for req, res in zip(reqs, out):
        _same_outcome(res, SIM.run(req, mechanism="hanoi_jax"))


# ---------------------------------------------------------------------------
# service: equivalence across every registered mechanism
# ---------------------------------------------------------------------------

def test_service_matches_per_request_run_for_every_mechanism():
    bench = _bench("DIAMOND")
    mechs = [m.name for m in iter_mechanisms()]
    assert len(mechs) >= 6
    with SimulationService(default_mechanism="hanoi", max_batch=8,
                           max_wait_s=0.01, workers=2) as svc:
        tickets = [(name, svc.submit(bench, CFG, mechanism=name))
                   for name in mechs]
        svc.flush()
        for name, t in tickets:
            _same_outcome(t.result(120), SIM.run(bench, CFG, mechanism=name))


# ---------------------------------------------------------------------------
# service: the acceptance-criterion mixed batch
# ---------------------------------------------------------------------------

def test_service_mixed_batch_order_and_equivalence():
    """>= 3 mechanisms, heterogeneous cfgs/shapes, an SM job: identical to
    sequential run()/run_sm(), in submission order, with every homogeneous
    hanoi_jax group natively batched."""
    small = MachineConfig(n_threads=4, mem_size=64, max_steps=4096)
    jobs = [
        ("hanoi_jax", as_request(_bench("DIAMOND"), CFG)),
        ("hanoi", as_request(_bench("GAUS0"), CFG)),
        ("hanoi_jax", as_request(_bench("GAUS0"), CFG)),
        ("simt_stack", as_request(_bench("HOTS0"), CFG)),
        ("hanoi_jax", as_request(_bench("RBFS0"), small)),   # other cfg
        ("volta_itps", as_request(_bench("DIAMOND"), CFG)),
        ("hanoi_jax", as_request(_bench("HOTS0"), CFG)),
        ("dualpath", as_request(_bench("DIAMOND"), small)),
    ]
    expected = [SIM.run(req, mechanism=name) for name, req in jobs]
    sm_expected = SIM.run_sm(_bench("RBFS0"), CFG, n_warps=4, inner="hanoi",
                             policy="greedy_then_oldest")
    # max_wait_s is deliberately long: grouping assertions below depend on
    # the deadline flusher NOT firing mid-submission; flush() drives dispatch
    with SimulationService(default_mechanism="hanoi_jax", max_batch=16,
                           max_wait_s=30.0, workers=3) as svc:
        tickets = [svc.submit(req, mechanism=name) for name, req in jobs]
        sm_ticket = svc.submit_sm(_bench("RBFS0"), CFG, n_warps=4,
                                  inner="hanoi",
                                  policy="greedy_then_oldest")
        svc.flush()
        results = [t.result(180) for t in tickets]
        sm = sm_ticket.result(180)
        stats = svc.stats()
    # submission order and architectural equivalence
    for res, exp in zip(results, expected):
        assert res.mechanism == exp.mechanism
        _same_outcome(res, exp)
    # the instrumentation assert: homogeneous hanoi_jax groups (3 CFG warps
    # in one group; the small-cfg one alone) actually hit the batch_runner
    for i, (name, _) in enumerate(jobs):
        if name == "hanoi_jax":
            assert results[i].meta["service"]["native"] is True
    cfg_group = [results[i].meta["service"] for i, (n, _) in enumerate(jobs)
                 if n == "hanoi_jax"
                 and results[i].meta["service"]["batch_size"] == 3]
    assert len(cfg_group) == 3                   # coalesced into ONE batch
    assert stats.native_batches >= 2
    assert stats.native_warps == 4
    # the SM cell: one sharded run_sm call, identical aggregate
    assert sm.policy == sm_expected.policy and sm.inner == sm_expected.inner
    assert sm.sm_trace == sm_expected.sm_trace
    assert sm.cycles == sm_expected.cycles
    assert sm.status == sm_expected.status
    for w_res, w_exp in zip(sm.warps, sm_expected.warps):
        _same_outcome(w_res, w_exp)
    assert stats.sm_jobs == 1
    # the SM cell counts per warp into the warp-level counters
    assert stats.completed == len(jobs) + sm.n_warps
    assert stats.failed == 0 and stats.inflight == 0


def test_service_native_batch_instrumented_probe():
    """White-box routing proof: a probe mechanism whose batch_runner counts
    invocations — the service must execute a homogeneous group through it
    exactly once and never fall back to the per-request runner."""
    calls = {"batch": 0, "single": 0, "sizes": []}

    def probe_batch(reqs):
        calls["batch"] += 1
        calls["sizes"].append(len(reqs))
        return [SIM.run(r) for r in reqs]

    @register_mechanism("probe_native", backend="numpy",
                        batch_runner=probe_batch,
                        description="test probe: counting batch_runner")
    def probe_single(req):
        calls["single"] += 1
        return SIM.run(req)

    try:
        with SimulationService(default_mechanism="probe_native",
                               max_batch=4, max_wait_s=5.0,
                               workers=1) as svc:
            tickets = svc.submit_many([_bench("DIAMOND")] * 4, CFG)
            results = [t.result(60) for t in tickets]   # size-flush: no wait
            stats = svc.stats()
    finally:
        unregister_mechanism("probe_native")
    assert calls == {"batch": 1, "single": 0, "sizes": [4]}
    assert stats.flush_size == 1 and stats.native_batches == 1
    assert all(r.meta["service"]["flush"] == "size" for r in results)
    assert dict(stats.batch_fill) == {4: 1}


# ---------------------------------------------------------------------------
# service: flush rules end to end, stats, failure path
# ---------------------------------------------------------------------------

def test_service_deadline_flush_resolves_without_manual_flush():
    with SimulationService(default_mechanism="hanoi", max_batch=64,
                           max_wait_s=0.05, workers=1) as svc:
        t = svc.submit(_bench("DIAMOND"), CFG)
        res = t.result(timeout=30)               # deadline flush must fire
        stats = svc.stats()
    assert res.ok
    assert stats.flush_deadline == 1 and stats.flush_size == 0
    assert res.meta["service"]["flush"] == "deadline"


def test_service_stats_shape_and_latency():
    with SimulationService(default_mechanism="hanoi", max_batch=2,
                           max_wait_s=30.0, workers=2) as svc:
        svc.run([_bench("DIAMOND")] * 4, CFG)   # two size-flushes of 2
        stats = svc.stats()
    assert stats.submitted == stats.completed == 4
    assert stats.queue_depth == 0 and stats.inflight == 0
    assert stats.latency_p50_s <= stats.latency_p99_s
    assert stats.warps_per_s > 0
    assert stats.mean_fill == pytest.approx(2.0)
    assert stats.uptime_s > 0


def test_service_failure_resolves_ticket_with_exception():
    @register_mechanism("probe_boom", backend="numpy",
                        description="test probe: always raises")
    def _boom(req):
        raise RuntimeError("probe exploded")

    try:
        with SimulationService(default_mechanism="probe_boom",
                               max_batch=2, max_wait_s=0.01,
                               workers=1) as svc:
            t = svc.submit(_bench("DIAMOND"), CFG)
            svc.flush()
            with pytest.raises(RuntimeError, match="probe exploded"):
                t.result(30)
            stats = svc.stats()
    finally:
        unregister_mechanism("probe_boom")
    assert stats.failed == 1 and stats.completed == 0
    assert stats.inflight == 0                    # accounting stays balanced


def test_short_batch_runner_is_an_error_not_a_hang():
    """A plugin batch_runner that drops results must resolve every ticket
    with a diagnosable error — never leave the tail hanging."""
    @register_mechanism("probe_short", backend="numpy",
                        batch_runner=lambda reqs:
                            [SIM.run(r) for r in reqs[:-1]],
                        description="test probe: drops the last result")
    def _probe_short(req):
        return SIM.run(req)

    try:
        with pytest.raises(RuntimeError, match="returned 1 results for 2"):
            SIM.run_batch([_bench("DIAMOND")] * 2, CFG,
                          mechanism="probe_short")
        with SimulationService(default_mechanism="probe_short", max_batch=2,
                               max_wait_s=5.0, workers=1) as svc:
            tickets = svc.submit_many([_bench("DIAMOND")] * 2, CFG)
            for t in tickets:
                with pytest.raises(RuntimeError, match="batch_runner"):
                    t.result(30)
            assert svc.stats().failed == 2
    finally:
        unregister_mechanism("probe_short")


def test_service_restarts_after_stop():
    """stop() drains and joins; a later submit transparently restarts the
    service (lazy start is the same path first use takes)."""
    svc = SimulationService(default_mechanism="hanoi", max_batch=1,
                            workers=1)
    assert svc.run([_bench("DIAMOND")], CFG)[0].ok
    svc.stop()
    t = svc.submit(_bench("DIAMOND"), CFG)      # auto-restart
    svc.flush()
    assert t.result(30).ok
    svc.stop()


def test_run_sm_grid_shards_cells():
    cells = [dict(programs=_bench("RBFS0"), cfg=CFG, n_warps=w,
                  inner="hanoi", policy=p)
             for w in (2, 4) for p in ("round_robin", "greedy_then_oldest")]
    with SimulationService(default_mechanism="hanoi", workers=3) as svc:
        grid = svc.run_sm_grid(cells, timeout=120)
        stats = svc.stats()
    assert stats.sm_jobs == len(cells)
    for cell, sm in zip(cells, grid):
        exp = SIM.run_sm(cell["programs"], CFG, n_warps=cell["n_warps"],
                         inner="hanoi", policy=cell["policy"])
        assert sm.n_warps == cell["n_warps"] and sm.policy == cell["policy"]
        assert sm.sm_trace == exp.sm_trace and sm.cycles == exp.cycles


def test_sm_cell_stats_count_per_warp():
    """ISSUE 5 satellite regression: an SM cell used to bump submitted/
    completed by 1 regardless of width, undercounting warps_per_s by
    n_warps x.  Fixed samples: a 3-warp replicated cell + a 2-warp
    heterogeneous cell = 5 warps, 2 cells, 2 latency samples."""
    with SimulationService(default_mechanism="hanoi", workers=1) as svc:
        rep = svc.submit_sm(_bench("DIAMOND"), CFG, n_warps=3,
                            inner="hanoi").result(120)
        het = svc.submit_sm([_bench("DIAMOND"), _bench("HOTS0")], CFG,
                            inner="hanoi").result(120)
        stats = svc.stats()
    assert rep.n_warps == 3 and het.n_warps == 2
    assert stats.submitted == stats.completed == 5    # warps, not cells
    assert stats.sm_jobs == 2
    assert stats.failed == 0 and stats.inflight == 0
    assert stats.warps_per_s == pytest.approx(5 / stats.uptime_s)
    assert len(svc._latencies) == 2                   # cell latency: once


def test_sm_cell_failure_counts_per_warp():
    with SimulationService(default_mechanism="hanoi", workers=1) as svc:
        # 2 per-warp programs conflicting with n_warps=3 -> run_sm raises
        t = svc.submit_sm([_bench("DIAMOND"), _bench("HOTS0")], CFG,
                          n_warps=3, inner="hanoi")
        with pytest.raises(ValueError, match="conflicts"):
            t.result(120)
        stats = svc.stats()
    assert stats.failed == 2 and stats.completed == 0
    assert stats.inflight == 0                        # accounting balanced


def test_stop_shared_deadline_reports_stragglers():
    """ISSUE 5 satellite: stop(timeout=T) must be ONE deadline across all
    joins — per-thread budgets made worst-case shutdown (workers+1) x T —
    and must report the threads still alive at expiry."""
    svc = SimulationService(default_mechanism="hanoi", workers=2)
    svc.start()
    assert svc.run([_bench("DIAMOND")], CFG)[0].ok
    sleepers = [threading.Thread(target=time.sleep, args=(30,),
                                 daemon=True, name=f"wedged-{i}")
                for i in range(3)]
    for t in sleepers:
        t.start()
        svc._threads.append(t)                       # simulate wedged threads
    t0 = time.monotonic()
    stragglers = svc.stop(timeout=0.5)
    elapsed = time.monotonic() - t0
    # per-thread budgets would take >= 3 x 0.5s on the sleepers alone
    assert elapsed < 1.2, elapsed
    assert sorted(stragglers) == [f"wedged-{i}" for i in range(3)]
    # a clean stop reports no stragglers
    with SimulationService(default_mechanism="hanoi", workers=1) as svc2:
        svc2.run([_bench("DIAMOND")], CFG)
    assert svc2.stop() == []                         # idempotent, clean


# ---------------------------------------------------------------------------
# durable archival: rotating buffered sink
# ---------------------------------------------------------------------------

def test_rotating_sink_rotates_and_preserves_runs(tmp_path):
    sink = RotatingJsonlSink(str(tmp_path), prefix="t", max_bytes=2000)
    r = SIM.run(_bench("DIAMOND"), CFG)
    for i in range(12):
        from repro.engine import feed_result
        feed_result(sink, r, {"mechanism": "hanoi", "program": f"p{i}"})
    sink.flush()
    sink.close()
    assert len(sink.paths) > 1                   # rotation happened
    assert sink.runs_written == 12
    begins, ends = [], []
    for path in sink.paths:
        state = None
        for line in open(path, encoding="utf-8"):
            ev = json.loads(line)
            if ev["event"] == "begin":
                assert state in (None, "end")    # runs never interleave
                state = "begin"
                begins.append(ev["program"])
            elif ev["event"] == "end":
                state = "end"
                ends.append(ev["status"])
    assert sorted(begins) == sorted(f"p{i}" for i in range(12))
    assert len(ends) == 12 and set(ends) == {"ok"}
    with pytest.raises(RuntimeError):
        sink.begin({})                           # closed sink refuses events


def test_rotating_sink_survives_io_failure(tmp_path, monkeypatch):
    """A writer-side IO error must degrade (drop + record), never wedge
    producers in end() or flush() — the failure mode is a dead archive,
    not a hung service."""
    from repro.engine import feed_result
    sink = RotatingJsonlSink(str(tmp_path), max_bytes=1 << 20)
    r = SIM.run(_bench("DIAMOND"), CFG)
    feed_result(sink, r, {"mechanism": "hanoi", "program": "ok"})
    sink.flush()
    assert sink.runs_written == 1 and sink.write_error is None
    monkeypatch.setattr(sink, "_rotate",
                        lambda: (_ for _ in ()).throw(OSError("disk full")))
    sink._fh.close()                             # force the rotate path
    sink._fh = None
    for i in range(3):                           # producers never block
        feed_result(sink, r, {"mechanism": "hanoi", "program": f"bad{i}"})
    sink.flush()                                 # returns: queue fully acked
    assert isinstance(sink.write_error, OSError)
    assert sink.runs_dropped == 3 and sink.runs_written == 1
    sink.close()


def test_service_archives_whole_runs_concurrently(tmp_path):
    sink = RotatingJsonlSink(str(tmp_path), max_bytes=1 << 20)
    names = ["HOTS0", "GAUS0", "RBFS0", "DIAMOND"] * 2
    with SimulationService(default_mechanism="hanoi", max_batch=2,
                           max_wait_s=0.01, workers=3,
                           archive=sink) as svc:
        svc.run([_bench(n) for n in names], CFG)
    sink.flush()
    sink.close()
    assert sink.runs_written == len(names)
    events = [json.loads(l) for p in sink.paths
              for l in open(p, encoding="utf-8")]
    assert sum(e["event"] == "begin" for e in events) == len(names)
    assert sum(e["event"] == "end" for e in events) == len(names)
    # every run's events are contiguous (begin ... end with no foreign run)
    depth = 0
    for e in events:
        if e["event"] == "begin":
            depth += 1
        elif e["event"] == "end":
            depth -= 1
        assert depth in (0, 1)


# ---------------------------------------------------------------------------
# serve_simulations: the thin client keeps its contract
# ---------------------------------------------------------------------------

def test_serve_simulations_thin_client():
    from repro.launch.serve import serve_simulations
    reqs = [SimRequest(program=_bench("DIAMOND").program, cfg=CFG,
                       name=f"req{i}") for i in range(4)]
    out = serve_simulations(reqs, mechanism="hanoi", max_workers=2)
    assert out["mechanism"] == "hanoi"
    assert out["ok"] == 4 and out["failed"] == 0
    assert len(out["results"]) == 4 and out["warps_per_s"] > 0
    assert out["stats"].completed == 4
    for res, req in zip(out["results"], reqs):
        _same_outcome(res, SIM.run(req))


@pytest.mark.parametrize("bench,mechanism,code", [
    ("DIAMOND", "hanoi", None),
    ("SLOCK", "simt_stack", 1),          # pre-Volta stack deadlocks (Fig 3)
])
def test_serve_sim_cli_exit_code(bench, mechanism, code, tmp_path,
                                 monkeypatch, capsys):
    """``serve --mode sim`` serves the 32-lane machine and exits 1 when
    any request failed."""
    import sys
    from repro.launch import serve as serve_mod
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(sys, "argv", [
        "serve", "--mode", "sim", "--bench", bench, "--mechanism", mechanism,
        "--batch", "2"])
    if code is None:
        serve_mod.main()
    else:
        with pytest.raises(SystemExit) as ei:
            serve_mod.main()
        assert ei.value.code == code
    out = capsys.readouterr().out
    assert f"2 x {bench} via {mechanism}" in out


# ---------------------------------------------------------------------------
# regressions (ISSUE 4 satellites): percentile indexing + sink accounting
# ---------------------------------------------------------------------------

def test_stats_percentiles_nearest_rank():
    """pct() must be ceil(p*n)-1 nearest-rank: int(p*n) was one-off-high
    (p50 of 2 samples returned the max; index 500 instead of 499 at
    n=1000)."""
    svc = SimulationService(default_mechanism="hanoi")
    svc._latencies.extend([0.2, 0.1])
    s = svc.stats()
    assert s.latency_p50_s == 0.1                  # the lower sample
    assert s.latency_p99_s == 0.2
    svc._latencies.clear()
    svc._latencies.extend(float(i) for i in range(1, 1001))
    s = svc.stats()
    assert s.latency_p50_s == 500.0                # index 499, not 500
    assert s.latency_p99_s == 990.0                # ceil(990)-1 = 989
    svc._latencies.clear()
    svc._latencies.append(5.0)
    s = svc.stats()
    assert s.latency_p50_s == 5.0 and s.latency_p99_s == 5.0
    svc._latencies.clear()
    assert np.isnan(svc.stats().latency_p50_s)


def test_rotating_sink_measures_encoded_bytes(tmp_path):
    """max_bytes rotation and bytes_written must count encoded UTF-8
    bytes; len(chunk) (characters) undercounts multi-byte meta."""
    import os
    from repro.engine import feed_result
    meta = {"mechanism": "hanoi", "program": "é" * 120}   # 2-byte chars
    r = SIM.run(_bench("DIAMOND"), CFG)
    probe = RotatingJsonlSink(str(tmp_path / "probe"))
    feed_result(probe, r, meta)
    probe.flush()
    probe.close()
    chunk_bytes = os.path.getsize(probe.paths[0])
    chunk_chars = len(open(probe.paths[0], encoding="utf-8").read())
    assert chunk_bytes > chunk_chars                      # multi-byte meta
    # character accounting would pack 2 runs per file; byte accounting
    # rotates after every run
    max_bytes = 2 * chunk_chars
    assert max_bytes < 2 * chunk_bytes
    sink = RotatingJsonlSink(str(tmp_path / "real"), max_bytes=max_bytes)
    for _ in range(4):
        feed_result(sink, r, meta)
    sink.flush()
    sink.close()
    assert len(sink.paths) == 4                           # crossed per run
    sizes = [os.path.getsize(p) for p in sink.paths]
    assert all(s <= max_bytes for s in sizes)             # never overshoot
    assert sink.bytes_written == sum(sizes)               # on-disk truth
    for path in sink.paths:                               # still valid JSONL
        for line in open(path, encoding="utf-8"):
            json.loads(line)


def test_rotating_sink_guards_protocol_violations(tmp_path):
    """end() without begin() and emit() outside a run are dropped and
    counted (an enqueued chunk with no begin event would be unreadable by
    ArchiveReader); a begin() over a stale unfinished buffer discards it."""
    from repro.engine import feed_result
    sink = RotatingJsonlSink(str(tmp_path))
    r = SIM.run(_bench("DIAMOND"), CFG)
    sink.end(r)                                    # no begin: drop + count
    sink.emit(1, 3)                                # orphan emit: drop + count
    assert sink.runs_malformed == 1
    assert sink.events_orphaned == 1
    feed_result(sink, r, {"mechanism": "hanoi", "program": "good"})
    # producer that errored between begin and end leaves a stale buffer...
    sink.begin({"mechanism": "hanoi", "program": "halfdone"})
    sink.emit(0, 1)
    # ...which the next begin() on that thread discards
    sink.begin({"mechanism": "hanoi", "program": "fresh"})
    sink.emit(0, 1)
    sink.end(r)
    sink.flush()
    sink.close()
    assert sink.runs_stale == 1
    assert sink.runs_written == 2                  # "good" and "fresh" only
    events = [json.loads(l) for p in sink.paths
              for l in open(p, encoding="utf-8")]
    begins = [e["program"] for e in events if e["event"] == "begin"]
    assert begins == ["good", "fresh"]             # no "halfdone" on disk
    assert sum(e["event"] == "end" for e in events) == 2
