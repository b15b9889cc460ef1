"""Engine backend benchmark — the perf trajectory for the mining hot loop.

Times the jnp reference backend against the fused pallas backend (and the
sharded backend when run under a mesh-capable subprocess is not needed —
single-process here) on the synthetic T10-style dataset, then writes
``BENCH_engine.json`` so future PRs have per-backend wall time,
intersections/sec, and padding efficiency to compare against.

Two measurements per backend:
  mine   end-to-end ``mine()`` wall time (jit warmed by a first run)
  micro  steady-state ``engine.expand`` throughput on a fixed (Q, W) batch
"""
from __future__ import annotations

import json
import os
import time
from typing import List

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import EclatConfig, mine
from repro.core import engine as eng
from repro.data import generate

BENCH_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_engine.json")
BACKENDS = ("jnp", "pallas")


def _row(name: str, seconds: float, derived: str) -> str:
    return f"{name},{seconds * 1e6:.0f},{derived}"


def _micro_pairs_per_s(backend: str, q: int = 4096, w: int = 128,
                       reps: int = 5, **engine_kw) -> float:
    """Steady-state expand() throughput.  Timing hygiene: the first call
    (trace+compile) runs outside the timed region, and every timed rep is
    blocked to completion — engine.expand already syncs on the host mask
    read, but the survivor block is the last async value, so block on it
    per rep rather than once at the end."""
    rng = np.random.default_rng(0)
    bitmaps = jnp.asarray(rng.integers(0, 2**32, (512, w), dtype=np.uint32))
    left = rng.integers(0, 512, q).astype(np.int32)
    right = rng.integers(0, 512, q).astype(np.int32)
    supl = np.zeros(q, np.int32)
    e = eng.make_engine(backend, **engine_kw)

    def call():
        res = e.expand(bitmaps, left, right, supl, mode=eng.MODE_TIDSET,
                       min_sup=w * 8)
        jax.block_until_ready(res.bitmaps)

    call()  # trace + compile, not timed
    call()  # steady-state warm-up
    t0 = time.perf_counter()
    for _ in range(reps):
        call()
    return q * reps / (time.perf_counter() - t0)


def engine_bench(out: List[str], smoke: bool = False) -> dict:
    scale = 0.02 if smoke else float(os.environ.get("BENCH_SCALE", "0.08"))
    txns, spec = generate("T10I4D100K", scale=scale, seed=1)
    ms = spec.min_sups[len(spec.min_sups) // 2]
    report: dict = {
        "dataset": "T10I4D100K", "scale": scale, "n_txn": len(txns),
        "n_items": spec.n_items, "min_sup": float(ms), "smoke": bool(smoke),
        "jax_backend": jax.default_backend(), "backends": {},
    }
    for backend in BACKENDS:
        cfg = EclatConfig(min_sup=ms, variant="v4", p=10, backend=backend)
        t0 = time.perf_counter()
        mine(txns, spec.n_items, cfg)  # warm the jit/bucket caches
        cold_wall = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = mine(txns, spec.n_items, cfg)
        wall = time.perf_counter() - t0
        n_int = res.stats["n_intersections"]
        n_pad = res.stats["n_padded"]
        micro = _micro_pairs_per_s(backend)
        # off-TPU the pallas backend dispatches to the fused jnp ref, so the
        # jnp-vs-pallas delta there measures the fused call pattern (fewer
        # host transfers), not the Mosaic kernel — record which path ran
        entry = {
            "executed_path": res.stats["kernel_path"],
            "mine_wall_s": wall,
            "mine_cold_wall_s": cold_wall,   # trace+compile-inclusive first run
            "itemsets": res.total,
            "n_intersections": n_int,
            "intersections_per_s": n_int / wall if wall > 0 else 0.0,
            "padding_efficiency": n_int / (n_int + n_pad) if n_int + n_pad else 1.0,
            "pair_padding": res.stats.get("pair_padding"),
            "micro_pairs_per_s": micro,
        }
        report["backends"][backend] = entry
        out.append(_row(f"engine/{backend}/mine", wall,
                        f"itemsets={res.total};ips={entry['intersections_per_s']:.0f};"
                        f"pad_eff={entry['padding_efficiency']:.3f}"))
        out.append(_row(f"engine/{backend}/micro", 1.0 / micro,
                        f"pairs_per_s={micro:.0f}"))
    jw = report["backends"]["jnp"]["mine_wall_s"]
    pw = report["backends"]["pallas"]["mine_wall_s"]
    report["fused_speedup_vs_jnp"] = jw / pw if pw > 0 else 0.0
    with open(BENCH_PATH, "w") as f:
        json.dump(report, f, indent=2)
    out.append(_row("engine/fused_speedup", 0.0,
                    f"x{report['fused_speedup_vs_jnp']:.2f};json={os.path.basename(BENCH_PATH)}"))
    return report
