"""The paper's headline: RDD-Eclat vs Spark-Apriori, across scale and mesh.

    python benchmarks/headline_bench.py [--smoke]    # or benchmarks/run.py

Reproduces the comparison protocol of the source paper (arXiv:1912.06415)
and its companion Apriori study (arXiv:1908.01338): the same datasets, the
same min_sup, Apriori vs every Eclat variant v1–v6, varied over dataset
scale (>= 2 sizes) and over mesh size (1 device vs a forced 4-device host
mesh — the executor-core axis of Fig 15).  Every cell's full
(itemset, support) map is checksummed; ``apriori_mine`` is the
differential oracle, so ANY divergence between it and any engine backend
fails the bench (and CI), not just a wall-clock regression.

Runs in a subprocess because the forced XLA host-device count is
process-global.  Writes ``BENCH_headline.json``; ``analysis/report.py``
renders it as the EXPERIMENTS.md "Headline" table.
"""
from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
from typing import List

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
BENCH_PATH = os.path.join(ROOT, "BENCH_headline.json")
DATASET = "T10I4D100K"
VARIANTS = ["v1", "v2", "v3", "v4", "v5", "v6"]
MESH_SIZES = (1, 4)          # 1 device vs the forced 4-device host mesh


def _row(name: str, seconds: float, derived: str) -> str:
    return f"{name},{seconds * 1e6:.0f},{derived}"


def support_checksum(support_map: dict) -> str:
    """Stable digest of a full (itemset, support) map — identical mining
    output <=> identical checksum, independent of dict/iteration order."""
    lines = sorted(f"{','.join(map(str, k))}:{int(v)}"
                   for k, v in support_map.items())
    return hashlib.sha1("\n".join(lines).encode()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# child: runs under --xla_force_host_platform_device_count=4
# ---------------------------------------------------------------------------

def _child(smoke: bool) -> None:
    import time

    import jax

    from repro.core import EclatConfig, apriori_mine, mine
    from repro.data import generate
    from repro.dist.compat import make_mesh

    if len(jax.devices()) < max(MESH_SIZES):
        raise SystemExit("child needs 4 forced host devices (XLA_FLAGS)")

    scales = ((0.01, 0.02) if smoke
              else tuple(float(s) for s in os.environ.get(
                  "BENCH_HEADLINE_SCALES", "0.04,0.08").split(",")))
    spec0 = None
    report: dict = {
        "dataset": DATASET, "smoke": bool(smoke),
        "jax_backend": jax.default_backend(),
        "variants": VARIANTS, "mesh_sizes": list(MESH_SIZES),
        "scales": [], "checksums_identical": True,
    }

    def timed(fn):
        fn()                                   # warm jit/bucket caches
        t0 = time.perf_counter()
        out = fn()
        return out, time.perf_counter() - t0

    speedups: List[float] = []
    for scale in scales:
        txns, spec = generate(DATASET, scale=scale, seed=1)
        spec0 = spec
        ms = spec.min_sups[len(spec.min_sups) // 2]

        ap, ap_wall = timed(lambda: apriori_mine(txns, spec.n_items, ms))
        ap_sum = support_checksum(ap.support_map)
        entry = {
            "scale": scale, "n_txn": len(txns), "min_sup": float(ms),
            "apriori": {"wall_s": round(ap_wall, 4),
                        "itemsets": ap.total, "levels": ap.counts,
                        "checksum": ap_sum},
            "eclat": {},
        }

        best = None
        for n_dev in MESH_SIZES:
            if n_dev == 1:
                mesh, kw = None, dict(backend="pallas")
            else:
                mesh = make_mesh((n_dev,), ("data",),
                                 devices=jax.devices()[:n_dev])
                kw = dict(backend="tidsharded", shard="words")
            cell: dict = {}
            for variant in VARIANTS:
                cfg = EclatConfig(min_sup=ms, variant=variant, p=10,
                                  use_diffsets=(variant == "v6"), **kw)
                res, wall = timed(lambda: mine(txns, spec.n_items, cfg,
                                               mesh=mesh))
                ck = support_checksum(res.support_map())
                identical = ck == ap_sum
                report["checksums_identical"] &= identical
                sp = ap_wall / wall if wall > 0 else 0.0
                cell[variant] = {"wall_s": round(wall, 4), "checksum": ck,
                                 "identical": identical,
                                 "itemsets": res.total,
                                 "speedup_vs_apriori": round(sp, 3)}
                speedups.append(sp)
                if best is None or sp > best["speedup"]:
                    best = {"variant": variant, "mesh": n_dev,
                            "speedup": round(sp, 3)}
            entry["eclat"][str(n_dev)] = cell
        entry["best"] = best
        report["scales"].append(entry)

    report["min_sup"] = report["scales"][0]["min_sup"]
    report["n_items"] = spec0.n_items
    report["speedup_min"] = round(min(speedups), 3)
    report["speedup_max"] = round(max(speedups), 3)
    print(json.dumps(report))


# ---------------------------------------------------------------------------
# parent harness entry
# ---------------------------------------------------------------------------

def headline_bench(out: List[str], smoke: bool = False) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    # forced host devices are a CPU rehearsal: pin the child to the CPU so
    # it never contends for an accelerator the parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    cmd = [sys.executable, os.path.abspath(__file__), "--child"]
    if smoke:
        cmd.append("--smoke")
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=ROOT)
    if proc.returncode != 0:
        raise RuntimeError(f"headline child failed:\n{proc.stderr[-2000:]}")
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    with open(BENCH_PATH, "w") as f:
        json.dump(report, f, indent=2)
    # the differential-oracle contract is the acceptance-critical claim: a
    # checksum divergence between Apriori and ANY engine cell fails the
    # harness (and CI), not just a flag inside the JSON artifact
    if not report["checksums_identical"]:
        bad = [f"x{s['scale']}/{mesh}dev/{v}"
               for s in report["scales"]
               for mesh, cell in s["eclat"].items()
               for v, c in cell.items() if not c["identical"]]
        raise RuntimeError(f"headline checksum divergence vs Apriori: {bad} "
                           f"(see {BENCH_PATH})")
    for s in report["scales"]:
        out.append(_row(f"headline/x{s['scale']}/apriori",
                        s["apriori"]["wall_s"],
                        f"itemsets={s['apriori']['itemsets']};"
                        f"checksum={s['apriori']['checksum']}"))
        for mesh, cell in sorted(s["eclat"].items()):
            for v, c in cell.items():
                out.append(_row(f"headline/x{s['scale']}/{mesh}dev/{v}",
                                c["wall_s"],
                                f"speedup={c['speedup_vs_apriori']};"
                                f"identical={c['identical']}"))
    out.append(_row("headline/summary", 0.0,
                    f"speedup_min=x{report['speedup_min']};"
                    f"speedup_max=x{report['speedup_max']};"
                    f"json={os.path.basename(BENCH_PATH)}"))
    return report


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (still writes BENCH_headline.json)")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        sys.path.insert(0, os.path.join(ROOT, "src"))
        _child(smoke=args.smoke)
    else:
        rows: List[str] = ["name,us_per_call,derived"]
        headline_bench(rows, smoke=args.smoke)
        print("\n".join(rows))
