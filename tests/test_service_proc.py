"""Process-tier service tests: cross-process bit-equality, per-shard
archives, warm start, shutdown semantics, and the bounded compile caches.

The spawn boundary is the point: every result that crosses it must be
bit-identical to the single-process façade, every shard's archive family
must self-replay to exactly 0.0, and a restarted warm-started service must
re-trace zero hot signatures.  ``_register_shard_probes`` is the shard
init hook — spawned shards import this module by reference (no
registration happens at import time, so collection never pollutes the
parent registry) and call it to install the probe mechanisms.
"""
from __future__ import annotations

import glob
import os
import queue
import time

import jax
import numpy as np
import pytest

from repro.archive import ArchiveReader, Replayer
from repro.archive.index import compact
from repro.core.isa import MachineConfig
from repro.core.programs import diamond_program, make_suite
from repro.engine import (RotatingJsonlSink, Simulator, adapters,
                          iter_mechanisms, register_mechanism,
                          unregister_mechanism)
from repro.engine import compile_cache
from repro.engine.compile_cache import (CompileCache, affinity_token,
                                        install_jax_cache, shard_of_token)
from repro.engine.simulator import as_request
from repro.service import ServiceStopped, SimulationService
from repro.service.procpool import ProcPool

CFG = MachineConfig(n_threads=8, mem_size=64, max_steps=4096)
SUITE = make_suite(CFG, datasets=1)
SIM = Simulator("hanoi")


def _reqs(n=6, **kw):
    return [as_request(b, CFG, **kw) for b in SUITE[:n]]


def _same_outcome(a, b):
    """status / final regs / mem / fuel / trace equality."""
    assert a.status == b.status
    assert a.fuel_left == b.fuel_left
    assert a.finished == b.finished
    np.testing.assert_array_equal(a.regs, b.regs)
    np.testing.assert_array_equal(a.mem, b.mem)
    assert a.trace == b.trace


# ---------------------------------------------------------------------------
# shard init hook (pickled by reference into spawned shards)
# ---------------------------------------------------------------------------

def _register_shard_probes(shard: int) -> None:
    """Runs inside every spawned shard: install the probe mechanisms the
    tests below route to.  A parent-process ``register_mechanism`` call
    does not cross the spawn boundary — this hook is how plugins reach
    shard processes."""
    import time as _time

    from repro.engine import register_mechanism
    from repro.engine.types import SimStatus

    @register_mechanism("proc_probe", backend="numpy",
                        description="shard-side echo probe")
    def _probe(req):
        from repro.engine.adapters import result_from_runresult  # noqa: F401
        import dataclasses
        res = Simulator("hanoi").run(req)
        return dataclasses.replace(res, meta={**res.meta, "shard": shard})

    @register_mechanism("proc_sleeper", backend="numpy",
                        description="wedges the shard (shutdown tests)")
    def _sleeper(req):
        _time.sleep(120)
        raise RuntimeError("unreachable")


def _parent_stub(name):
    """Parent-side registration so signature_of/get_mechanism admit the
    request; execution happens in the shard."""
    def _never_runs(req):
        raise AssertionError(f"{name} must execute in a shard process")
    return register_mechanism(name, backend="numpy")(_never_runs)


# ---------------------------------------------------------------------------
# cross-process bit-equality
# ---------------------------------------------------------------------------

def test_every_mechanism_bit_equal_through_two_procs():
    """Every registered mechanism, run through a 2-process service, returns
    results bit-identical to the single-process ``Simulator.run_batch``."""
    names = sorted(m.name for m in iter_mechanisms())
    reqs = _reqs(3)
    with SimulationService(default_mechanism="hanoi", procs=2,
                           annotate=False) as svc:
        for name in names:
            got = svc.run(reqs, mechanism=name, timeout=600)
            want = Simulator(name).run_batch(reqs)
            for g, w in zip(got, want):
                _same_outcome(g, w)


def test_proc_results_annotated_with_shard():
    with SimulationService(default_mechanism="hanoi", procs=2) as svc:
        res = svc.run(_reqs(4), timeout=120)
    for r in res:
        svc_meta = r.meta["service"]
        assert svc_meta["shard"] in (0, 1)
        assert svc_meta["batch_size"] >= 1


def test_numpy_groups_spread_across_shards():
    """A homogeneous numpy group must NOT pin to one shard (that is the
    single-core ceiling the process tier exists to break)."""
    with SimulationService(default_mechanism="hanoi", procs=2,
                           max_batch=64) as svc:
        res = svc.run(_reqs(6), timeout=120)
        shards = {r.meta["service"]["shard"] for r in res}
        st = svc.stats()
    assert shards == {0, 1}
    assert {s.shard for s in st.shards if s.completed > 0} == {0, 1}


def test_jax_groups_route_affine_to_one_shard():
    """Jax groups run whole on shard 0, the one process that owns the
    device, while numpy groups still spread over every shard."""
    with SimulationService(default_mechanism="hanoi_jax", procs=2,
                           max_batch=64) as svc:
        res = svc.run(_reqs(6), timeout=300)
        host = svc.run(_reqs(6), mechanism="hanoi", timeout=120)
    assert {r.meta["service"]["shard"] for r in res} == {0}
    assert {r.meta["service"]["platform"] for r in res} == {
        jax.devices()[0].platform}
    assert {r.meta["service"]["shard"] for r in host} == {0, 1}


def test_device_sm_cells_run_on_shard_zero():
    """SM cells whose inner mechanism (or engine) is jax-backed route to
    the device shard; bit-equal to the single-process façade."""
    progs = [b.program for b in SUITE[:2]]
    cells = [dict(programs=progs, cfg=CFG, n_warps=2, inner="hanoi_jax",
                  policy=p) for p in ("round_robin", "greedy_then_oldest")]
    with SimulationService(default_mechanism="hanoi", procs=2) as svc:
        got = svc.run_sm_grid(cells, timeout=300)
        st = svc.stats()
    assert [s.jobs for s in st.shards] == [2, 0]
    for cell, sm in zip(cells, got):
        want = SIM.run_sm(progs, CFG, n_warps=2, inner="hanoi",
                          policy=cell["policy"])
        assert sm.sm_trace == want.sm_trace and sm.cycles == want.cycles


def test_non_device_shard_refuses_device_work():
    """Handed jax work directly, shard 1 raises instead of running it on
    the host CPU; its numpy work still runs."""
    replies: queue.Queue = queue.Queue()
    pool = ProcPool(2, default_mechanism="hanoi", annotate=False,
                    on_reply=lambda ctx, payload, error:
                    replies.put((ctx, error)))
    try:
        assert pool.wait_ready(timeout=120)
        req = _reqs(1)[0]
        for ctx, mech in (("jax", "hanoi_jax"), ("host", "hanoi")):
            pool.submit_group(1, mechanism=mech, native=False, cause="manual",
                              sig_key=ctx, requests=[req], ctx=ctx)
        pool.submit_sm(1, programs=[req.program] * 2, cfg=CFG,
                       kwargs={"n_warps": 2, "inner": "hanoi_jax"}, ctx="sm")
        pool.submit_sm(1, programs=[req.program] * 2, cfg=CFG,
                       kwargs={"n_warps": 2, "inner": "hanoi",
                               "sm_mechanism": "sm_jax"}, ctx="sm_jax")
        errors = dict(replies.get(timeout=120) for _ in range(4))
    finally:
        pool.stop(deadline=time.monotonic() + 30)
    assert errors["host"] is None
    for ctx in ("jax", "sm", "sm_jax"):
        assert isinstance(errors[ctx], RuntimeError)
        assert "only shard 0 owns the device" in str(errors[ctx])


def test_sm_grid_bit_equal_through_two_procs():
    progs = [b.program for b in SUITE[:4]]
    cells = [dict(programs=progs, cfg=CFG, n_warps=4, inner="hanoi",
                  policy=p) for p in ("round_robin", "greedy_then_oldest")]
    with SimulationService(default_mechanism="hanoi", procs=2) as svc:
        got = svc.run_sm_grid(cells, timeout=300)
        st = svc.stats()
    assert st.sm_jobs == 2
    for cell, sm in zip(cells, got):
        want = SIM.run_sm(progs, CFG, n_warps=4, inner="hanoi",
                          policy=cell["policy"])
        assert sm.sm_trace == want.sm_trace
        assert sm.cycles == want.cycles
        assert sm.stall_breakdown == want.stall_breakdown
        for g, w in zip(sm.warps, want.warps):
            _same_outcome(g, w)


def test_shard_init_registers_plugin_mechanisms_in_shards():
    _parent_stub("proc_probe")
    try:
        with SimulationService(default_mechanism="hanoi", procs=2,
                               shard_init=_register_shard_probes) as svc:
            got = svc.run(_reqs(4), mechanism="proc_probe", timeout=120)
        want = SIM.run_batch(_reqs(4))
        for g, w in zip(got, want):
            _same_outcome(g, w)
            assert g.meta["shard"] in (0, 1)
    finally:
        unregister_mechanism("proc_probe")


def test_shard_exception_rebuilt_parent_side():
    with SimulationService(default_mechanism="hanoi", procs=1) as svc:
        # a mechanism unknown to the shard raises there and crosses back
        _parent_stub("proc_parent_only")
        try:
            t2 = svc.submit(diamond_program(), CFG,
                            mechanism="proc_parent_only")
            svc.flush()
            with pytest.raises(Exception) as ei:
                t2.result(timeout=120)
            assert "proc_parent_only" in str(ei.value)
        finally:
            unregister_mechanism("proc_parent_only")
        st = svc.stats()
        assert st.failed >= 1


# ---------------------------------------------------------------------------
# per-shard archive families
# ---------------------------------------------------------------------------

def test_per_shard_archives_self_replay_to_zero(tmp_path):
    d = str(tmp_path)
    sink = RotatingJsonlSink(d, prefix="traces", max_bytes=1 << 20)
    with SimulationService(default_mechanism="hanoi", procs=2,
                           archive=sink) as svc:
        svc.run(_reqs(6), mechanism="hanoi", timeout=120)
        svc.run(_reqs(6), mechanism="hanoi_jax", timeout=300)
        svc.submit_sm([b.program for b in SUITE[:4]], CFG, n_warps=4,
                      inner="hanoi").result(120)
    sink.close()
    families = sorted(os.path.basename(p)
                      for p in glob.glob(os.path.join(d, "*.jsonl")))
    assert any("traces-shard0-" in f for f in families)
    assert any("traces-shard1-" in f for f in families)
    total = 0
    for k in range(2):
        reader = ArchiveReader(d, prefix=f"traces-shard{k}")
        runs = reader.runs()
        total += len(runs)
        rep = Replayer().replay(reader)
        assert rep.mean_discrepancy() == 0.0
        assert rep.replayed == len(runs)
        # archive stamps carry the shard id
        assert all(r.meta.get("shard") == k for r in runs)
    assert total == 16   # 6 hanoi + 6 hanoi_jax + 4 SM warps


def test_shard_family_index_and_compaction_still_work(tmp_path):
    d = str(tmp_path)
    sink = RotatingJsonlSink(d, prefix="traces", max_bytes=1 << 20)
    with SimulationService(default_mechanism="hanoi", procs=2,
                           archive=sink) as svc:
        svc.run(_reqs(6), mechanism="hanoi", timeout=120)
    sink.close()
    from repro.archive.index import ArchiveIndex
    for k in range(2):
        prefix = f"traces-shard{k}"
        reader = ArchiveReader(d, prefix=prefix)
        runs = reader.runs()
        if not runs:
            continue
        idx = ArchiveIndex.ensure(d, prefix=prefix)
        assert len(idx.entries) == len(runs)
        got = reader.get(idx.entries[0].run_id)  # sidecar index path
        assert got.meta == runs[0].meta and got.steps == runs[0].steps
        report = compact(d, prefix)
        assert report is not None
        after = ArchiveReader(d, prefix=prefix).runs()
        assert len(after) == len(runs)


def test_non_rotating_sink_fed_parent_side(tmp_path):
    from repro.engine import MemorySink
    sink = MemorySink()
    with SimulationService(default_mechanism="hanoi", procs=2,
                           archive=sink) as svc:
        svc.run(_reqs(4), timeout=120)
    assert len(sink.runs) == 4


# ---------------------------------------------------------------------------
# shutdown semantics
# ---------------------------------------------------------------------------

def test_stop_terminates_wedged_shard_and_resolves_tickets():
    _parent_stub("proc_sleeper")
    try:
        svc = SimulationService(default_mechanism="hanoi", procs=1,
                                shard_init=_register_shard_probes)
        svc.start()
        assert svc._pool.wait_ready(timeout=60.0)
        ticket = svc.submit(diamond_program(), CFG,
                            mechanism="proc_sleeper")
        svc.flush()
        time.sleep(0.5)                    # let the shard start sleeping
        t0 = time.monotonic()
        stragglers = svc.stop(timeout=1.0)
        elapsed = time.monotonic() - t0
        assert elapsed < 15.0
        assert "sim-shard-0" in stragglers
        with pytest.raises(ServiceStopped):
            ticket.result(timeout=5.0)
    finally:
        unregister_mechanism("proc_sleeper")


def test_clean_stop_reports_no_stragglers():
    svc = SimulationService(default_mechanism="hanoi", procs=2)
    svc.start()
    svc.run(_reqs(4), timeout=120)
    assert svc.stop(timeout=30.0) == []
    st = svc.stats()
    assert st.completed == 4 and st.inflight == 0


# ---------------------------------------------------------------------------
# warm start + compile-cache counters
# ---------------------------------------------------------------------------

def test_warm_start_restarted_service_retraces_zero(tmp_path):
    cache_dir = str(tmp_path / "ccache")
    svc1 = SimulationService(default_mechanism="hanoi_jax", procs=1,
                             warm_start=cache_dir)
    with svc1:
        svc1.run(_reqs(6), timeout=300)
        svc1.run(_reqs(3), timeout=300)    # second batch-class signature
    st1 = svc1.stats()
    assert st1.cache_misses >= 2           # cold compiles happened
    assert CompileCache(cache_dir).entries()   # manifest persisted

    svc2 = SimulationService(default_mechanism="hanoi_jax", procs=1,
                             warm_start=cache_dir)
    with svc2:
        svc2.run(_reqs(6), timeout=300)
        svc2.run(_reqs(3), timeout=300)
        st2 = svc2.stats()
    assert st2.warm_signatures >= 2
    # the warm-start contract: hot signatures never re-trace at serve time
    assert st2.cache_misses == st2.warm_retraced
    assert st2.cache_hits >= 2
    # AOT executables deserialize: zero re-trace anywhere
    assert st2.warm_retraced == 0
    assert st2.warm_loaded >= 2
    assert st2.cache_misses == 0


def test_thread_tier_warm_start(tmp_path):
    from repro.engine.compile_cache import uninstall_compile_cache
    cache_dir = str(tmp_path / "ccache")
    try:
        with SimulationService(default_mechanism="hanoi_jax",
                               warm_start=cache_dir) as svc:
            svc.run(_reqs(5), timeout=300)
        adapters.reset_batch_caches()      # simulate a process restart
        with SimulationService(default_mechanism="hanoi_jax",
                               warm_start=cache_dir) as svc2:
            before = svc2.stats()
            assert before.warm_signatures >= 1
            svc2.run(_reqs(5), timeout=300)
            after = svc2.stats()
        assert after.cache_misses == before.cache_misses   # zero re-trace
    finally:
        uninstall_compile_cache()
        adapters.reset_batch_caches()


def test_executable_from_another_backend_is_a_miss(tmp_path, monkeypatch):
    """An entry serialized on another platform is a clean disk miss on
    this one — never handed to the loader to fail as a load error."""
    cache = CompileCache(str(tmp_path))
    compiled, _ = adapters._compiled_batch_exec(CFG, True, 1, 32)
    monkeypatch.setattr(compile_cache, "_backend_key",
                        lambda: "tpu|TPU v5 lite|jax0.9.0")
    assert cache.store_executable("hanoi_jax", CFG, True, 1, 32, compiled)
    monkeypatch.undo()
    assert cache.has("hanoi_jax", CFG, True, 1, 32)     # still hot
    assert cache.load_executable("hanoi_jax", CFG, True, 1, 32) is None
    assert cache.stats["disk_misses"] == 1
    assert cache.stats["load_errors"] == 0
    assert cache.store_executable("hanoi_jax", CFG, True, 1, 32, compiled)
    assert cache.load_executable("hanoi_jax", CFG, True, 1, 32) is not None
    assert cache.stats["disk_hits"] == 1


@pytest.mark.parametrize("env", [True, False])
def test_install_jax_cache_places_the_cache(env, tmp_path, monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set;
    otherwise the cache is the fixed, git-ignored ``<checkout>/.jax_cache``."""
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prev = jax.config.jax_compilation_cache_dir
    if env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = install_jax_cache()
        if env:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            assert got == os.path.join(checkout, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            with open(os.path.join(checkout, ".gitignore")) as f:
                assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# ---------------------------------------------------------------------------
# bounded in-memory caches (satellite: no more unbounded lru_cache)
# ---------------------------------------------------------------------------

def test_batch_caches_bounded_with_eviction_counters():
    adapters.reset_batch_caches()
    adapters.set_batch_cache_capacity(executables=2)
    try:
        sim = Simulator("hanoi_jax")
        for n in (1, 2, 3):
            sim.run_batch(_reqs(n))
        s = adapters.batch_cache_stats()
        assert s["entries"] <= 2
        assert s["evictions"] >= 1
        assert s["misses"] >= 3
        assert s["capacity"] == 2
        sim.run_batch(_reqs(3))            # most recent entry: a hit
        assert adapters.batch_cache_stats()["hits"] > s["hits"]
    finally:
        adapters.set_batch_cache_capacity(executables=256)
        adapters.reset_batch_caches()


def test_thread_tier_stats_surface_cache_counters():
    adapters.reset_batch_caches()
    with SimulationService(default_mechanism="hanoi_jax") as svc:
        svc.run(_reqs(4), timeout=300)
        st = svc.stats()
    assert st.procs == 0 and st.shards == ()
    assert st.cache_misses >= 1 or st.cache_hits >= 1
    assert st.cache_entries >= 1


# ---------------------------------------------------------------------------
# affinity hashing + envelope pickling
# ---------------------------------------------------------------------------

def test_affinity_token_stable_and_partitioning():
    tok = affinity_token("hanoi_jax", CFG, True, 32)
    assert tok == affinity_token("hanoi_jax", CFG, True, 32)
    assert tok != affinity_token("hanoi_jax", CFG, False, 32)
    assert tok != affinity_token("hanoi_jax", CFG, True, 64)
    for n in (1, 2, 3, 7):
        assert 0 <= shard_of_token(tok, n) < n
    assert shard_of_token(tok, 1) == 0


def test_request_result_pickle_roundtrip():
    import pickle
    import types as pytypes
    req = _reqs(1, meta={"k": 1})[0]
    r2 = pickle.loads(pickle.dumps(req))
    assert isinstance(r2.meta, pytypes.MappingProxyType)
    assert dict(r2.meta) == {"k": 1}
    np.testing.assert_array_equal(r2.program, req.program)
    res = SIM.run(req)
    res2 = pickle.loads(pickle.dumps(res))
    _same_outcome(res, res2)
    assert isinstance(res2.meta, pytypes.MappingProxyType)
    sm = SIM.run_sm([b.program for b in SUITE[:2]], CFG, n_warps=2,
                    inner="hanoi")
    sm2 = pickle.loads(pickle.dumps(sm))
    assert sm2.sm_trace == sm.sm_trace and sm2.cycles == sm.cycles
    for a, b in zip(sm.warps, sm2.warps):
        _same_outcome(a, b)
