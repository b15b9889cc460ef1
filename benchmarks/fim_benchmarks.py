"""FIM benchmarks reproducing the paper's tables/figures.

Figs 8-14 : execution time of EclatV1..V5 (+V6) vs Spark-Apriori across
            min_sup sweeps on the seven Table-2 datasets -> fim_minsup.
Fig 15    : execution time vs executor cores               -> fim_cores
            (subprocess per core count; --xla_force_host_platform_device_count).
Fig 16    : execution time vs dataset size (T10I4 doubling) -> fim_scale.
(ext.)    : partitioner balance (padding efficiency)        -> partitioner_balance.

Datasets are generated at a CPU-budget scale by default (same statistical
shape as Table 2, see repro.data.synthetic); BENCH_SCALE / BENCH_FULL env
vars raise it.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import List

import numpy as np

from repro.core import EclatConfig, apriori_mine, mine
from repro.data import PAPER_DATASETS, generate

SCALE = float(os.environ.get("BENCH_SCALE", "0.08"))
FULL = os.environ.get("BENCH_FULL", "") == "1"

# paper-benchmarked variants; v6 is the beyond-paper greedy/LPT variant
VARIANTS = ["v1", "v2", "v3", "v4", "v5", "v6"]
DEFAULT_DATASETS = list(PAPER_DATASETS) if FULL else [
    "chess", "mushroom", "T10I4D100K", "BMS_WebView_1"]


def _row(name: str, seconds: float, derived: str) -> str:
    return f"{name},{seconds * 1e6:.0f},{derived}"


def fim_minsup(out: List[str], datasets=None, n_minsups=None) -> None:
    datasets = datasets or DEFAULT_DATASETS
    for ds in datasets:
        txns, spec = generate(ds, scale=SCALE if spec_scale(ds) else 1.0, seed=1)
        sups = spec.min_sups if FULL else spec.min_sups[:: 2]
        if n_minsups:
            sups = sups[:n_minsups]
        # warm jit paths once (compile time is not part of the paper's claim)
        mine(txns, spec.n_items,
             EclatConfig(min_sup=sups[0], variant="v3", p=10,
                         tri_matrix=spec.tri_matrix or None))
        apriori_mine(txns, spec.n_items, sups[0])
        for ms in sups:
            for variant in (VARIANTS if FULL else ["v1", "v3", "v5", "v6"]):
                cfg = EclatConfig(min_sup=ms, variant=variant, p=10,
                                  tri_matrix=spec.tri_matrix or None)
                t0 = time.perf_counter()
                res = mine(txns, spec.n_items, cfg)
                dt = time.perf_counter() - t0
                out.append(_row(f"fim_minsup/{ds}/ms{ms}/{variant}", dt,
                                f"itemsets={res.total}"))
            t0 = time.perf_counter()
            ap = apriori_mine(txns, spec.n_items, ms)
            dt = time.perf_counter() - t0
            out.append(_row(f"fim_minsup/{ds}/ms{ms}/apriori", dt,
                            f"itemsets={ap.total}"))


def spec_scale(ds: str) -> bool:
    return PAPER_DATASETS[ds].n_txn > 4000


def fim_scale(out: List[str]) -> None:
    """Fig 16: dataset doubling at fixed min_sup (paper: T10I4, 0.05)."""
    scales = [SCALE, 2 * SCALE, 4 * SCALE, 8 * SCALE]
    for sc in scales:
        txns, spec = generate("T10I4D100K", scale=sc, seed=1)
        cfg = EclatConfig(min_sup=0.05, variant="v4", p=10)
        t0 = time.perf_counter()
        res = mine(txns, spec.n_items, cfg)
        dt = time.perf_counter() - t0
        out.append(_row(f"fim_scale/T10I4D100K/x{sc/SCALE:.0f}", dt,
                        f"n_txn={len(txns)};itemsets={res.total}"))


_CORES_SNIPPET = r"""
import os, sys, time, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
from repro.core import EclatConfig, mine
from repro.data import generate
from repro.dist.compat import make_mesh
txns, spec = generate("T10I4D100K", scale=%f, seed=1)
mesh = make_mesh((%d,), ("data",))
cfg = EclatConfig(min_sup=0.02, variant="%s", p=10, backend="sharded")
t0 = time.perf_counter()
res = mine(txns, spec.n_items, cfg, mesh=mesh)
print(json.dumps({"s": time.perf_counter() - t0, "total": res.total,
                  "eff": res.stats.get("device_balance", {}).get("padding_efficiency")}))
"""


def fim_cores(out: List[str]) -> None:
    """Fig 15: scaling with executor cores (device count via subprocess)."""
    # forced host devices: a CPU rehearsal, pinned off any accelerator
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    for cores in ([2, 4, 6, 8, 10] if FULL else [2, 4, 8]):
        for variant in ["v4", "v5"]:
            proc = subprocess.run(
                [sys.executable, "-c", _CORES_SNIPPET % (cores, SCALE, cores, variant)],
                capture_output=True, text=True, env=env, cwd=os.getcwd())
            if proc.returncode != 0:
                out.append(_row(f"fim_cores/{cores}/{variant}", 0.0,
                                f"ERROR={proc.stderr.strip()[-80:]}"))
                continue
            data = json.loads(proc.stdout.strip().splitlines()[-1])
            out.append(_row(f"fim_cores/{cores}/{variant}", data["s"],
                            f"itemsets={data['total']};pad_eff={data['eff']:.3f}"))


def partitioner_balance(out: List[str]) -> None:
    """Extension table: per-partitioner padding efficiency per dataset."""
    from repro.core import assign_partitions, build_vertical, partition_stats
    from repro.core.equivalence import pair_work
    for ds in DEFAULT_DATASETS:
        txns, spec = generate(ds, scale=SCALE if spec_scale(ds) else 1.0, seed=1)
        ms = spec.min_sups[len(spec.min_sups) // 2]
        db = build_vertical(txns, spec.n_items, max(2, int(ms * len(txns))))
        n = db.n_items
        if n < 3:
            continue
        sizes = (n - 1 - np.arange(n - 1)).clip(min=0)
        work = pair_work(sizes + 1, db.n_words)
        t0 = time.perf_counter()
        for name in ("default", "hash", "reverse_hash", "greedy"):
            a = assign_partitions(n - 1, name, 10, work=work)
            eff = partition_stats(a, work, 10)["padding_efficiency"]
            out.append(_row(f"partitioner_balance/{ds}/{name}",
                            time.perf_counter() - t0, f"pad_eff={eff:.3f}"))
