"""Kernel benchmarks: divergence-aware tile census per assigned-arch
attention pattern (the Hanoi EMPTY/PARTIAL/FULL saving at MXU granularity),
warp-level SIMD utilization per control-flow mechanism (via the unified
``repro.engine`` API — the same EMPTY/PARTIAL/FULL economics one level
down), and interpret-mode wall times vs the jnp reference (correct-path
costs; TPU wall times are a dry-run quantity here, see EXPERIMENTS.md
SS Roofline)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref, tile_stats


def tile_census_rows() -> list[dict]:
    cases = [
        ("llama/minitron/internlm causal 4k", 4096, 4096, True, 0),
        ("gemma3 local (w=1024) 4k", 4096, 4096, True, 1024),
        ("gemma3 local (w=1024) 32k", 32768, 32768, True, 1024),
        ("mixtral SWA (w=4096) 32k", 32768, 32768, True, 4096),
        ("recurrentgemma local (w=2048) 32k", 32768, 32768, True, 2048),
        ("hubert bidirectional 32k", 32768, 32768, False, 0),
    ]
    rows = []
    for name, sq, sk, causal, w in cases:
        st = tile_stats(sq, sk, causal=causal, window=w, bq=128, bk=128)
        rows.append({"case": name, **st})
    return rows


def mechanism_utilization_rows() -> list[dict]:
    """Warp-level SIMD utilization of each control-flow mechanism on the
    divergence-heavy BFS benchmark — the lane-granularity analogue of the
    tile census above, computed through the unified engine API."""
    from repro.core import MachineConfig
    from repro.core.programs import make_suite
    from repro.engine import Simulator, available_mechanisms

    cfg = MachineConfig(n_threads=8, mem_size=64, max_steps=8192)
    bench = next(b for b in make_suite(cfg, datasets=1) if b.name == "BFSD")
    sim = Simulator()
    rows = []
    for mech in available_mechanisms():
        res = sim.run(bench, cfg, mechanism=mech)
        rows.append({"mechanism": mech, "utilization": res.utilization,
                     "steps": res.steps, "status": res.status.value})
    return rows


def _time(fn, *args, reps=3, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6      # us


def kernel_timing_rows() -> list[dict]:
    key = jax.random.PRNGKey(0)
    rows = []
    B, S, H, hd = 1, 256, 4, 64
    q = jax.random.normal(key, (B, S, H, hd), jnp.float32)
    k = jax.random.normal(key, (B, S, H, hd), jnp.float32)
    v = jax.random.normal(key, (B, S, H, hd), jnp.float32)
    rows.append({"kernel": "flash_attention(interp)",
                 "us": _time(ops.flash_attention, q, k, v, causal=True,
                             bq=64, bk=64, interpret=True, reps=1)})
    rows.append({"kernel": "attention_ref",
                 "us": _time(ref.attention_ref, q, k, v, causal=True)})
    a = jax.random.uniform(key, (2, 256, 128), jnp.float32, 0.5, 0.99)
    b = jax.random.normal(key, (2, 256, 128), jnp.float32)
    rows.append({"kernel": "rglru_scan(interp)",
                 "us": _time(ops.rglru_scan, a, b, bs=64, bw=64,
                             interpret=True, reps=1)})
    rows.append({"kernel": "rglru_ref",
                 "us": _time(ref.rglru_scan_ref, a, b)})
    r = jax.random.normal(key, (1, 128, 2, 16), jnp.float32)
    w = jax.random.uniform(key, (1, 128, 2, 16), jnp.float32, 0.8, 0.99)
    u = jax.random.normal(key, (2, 16), jnp.float32) * 0.1
    rows.append({"kernel": "rwkv6_scan(interp)",
                 "us": _time(ops.rwkv6_scan, r, r, r, w, u, bs=32,
                             interpret=True, reps=1)})
    rows.append({"kernel": "rwkv6_ref",
                 "us": _time(ref.rwkv6_scan_ref, r, r, r, w, u)})
    return rows


def main() -> None:
    from repro.engine import install_jax_cache
    install_jax_cache()
    print("== divergence-aware tile census (Hanoi EMPTY-tile skipping) ==")
    for r in tile_census_rows():
        print(f"  {r['case']:38s} kept={r['flops_kept_frac']:6.1%} "
              f"(empty={r['empty']}, partial={r['partial']}, "
              f"full={r['full']})")
    print("== SIMD utilization per mechanism (BFSD, repro.engine) ==")
    for r in mechanism_utilization_rows():
        print(f"  {r['mechanism']:14s} util={r['utilization']:6.1%} "
              f"steps={r['steps']:5d} status={r['status']}")
    print("== kernel wall times (CPU; interpret mode for Pallas) ==")
    for r in kernel_timing_rows():
        print(f"  {r['kernel']:28s} {r['us']:12.0f} us")


if __name__ == "__main__":
    main()
