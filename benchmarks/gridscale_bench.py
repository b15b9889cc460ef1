"""Grid-sharded (pairs x words) scaling: parity + per-axis work/memory.

    python benchmarks/gridscale_bench.py [--smoke]   # or benchmarks/run.py

The grid engine (DESIGN.md §8) runs on a 2D ("class", "data") mesh:
candidate pairs split over the class axis, the frontier's packed word axis
over the data axis, frontier carried ``P(None, "data")``.  The 1D modes
each scale one axis and replicate the other — ``shard="pairs"`` replicates
the frontier on every device, ``shard="words"`` replicates the pair work on
every shard.  This bench demonstrates, on the forced 4-device CPU host (a
subprocess, because the XLA device count is process-global):

  parity     batch ``mine()`` v1–v6 and >= 9 streaming window slides are
             bit-identical between the 2x2 grid engine and the jnp backend;
  placement  the same level expansion through the pairs / words / grid
             engines keeps the supports identical while the grid cuts
             per-device frontier bytes ~1/n_data vs "pairs" AND per-device
             pair work ~1/n_class vs "words".

Writes ``BENCH_gridscale.json`` for the cross-PR trajectory.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import List

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
BENCH_PATH = os.path.join(ROOT, "BENCH_gridscale.json")
DATASET = "T10I4D100K"
VARIANTS = ["v1", "v2", "v3", "v4", "v5", "v6"]
N_STREAM_SLIDES = 9           # acceptance: >= 9 bit-identical window slides


def _row(name: str, seconds: float, derived: str) -> str:
    return f"{name},{seconds * 1e6:.0f},{derived}"


# ---------------------------------------------------------------------------
# child: runs under --xla_force_host_platform_device_count=4
# ---------------------------------------------------------------------------

def _child(smoke: bool) -> None:
    import time

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import EclatConfig, mine
    from repro.core import engine as eng
    from repro.core.eclat import resolve_min_sup
    from repro.core.vertical import build_vertical
    from repro.data import generate, stream_spec, transaction_stream
    from repro.dist.compat import make_mesh

    if len(jax.devices()) < 4:
        raise SystemExit("child needs 4 forced host devices (XLA_FLAGS)")

    scale = 0.02 if smoke else float(os.environ.get("BENCH_SCALE", "0.08"))
    txns, spec = generate(DATASET, scale=scale, seed=1)
    ms = spec.min_sups[len(spec.min_sups) // 2]
    n_class, n_data = 2, 2
    grid_mesh = make_mesh((n_class, n_data), ("class", "data"),
                          devices=jax.devices()[:4])
    mesh4 = make_mesh((4,), ("data",))
    report: dict = {
        "dataset": DATASET, "scale": scale, "min_sup": float(ms),
        "n_txn": len(txns), "smoke": bool(smoke),
        "jax_backend": jax.default_backend(),
        "grid": [n_class, n_data],
        "parity": {}, "placement": {}, "parity_ok": True,
    }

    # ---- (a) batch parity: v1-v6, 2x2 grid vs jnp -------------------------
    for variant in VARIANTS:
        maps = {}
        walls = {}
        for label, kw, mesh in (
            ("jnp", dict(backend="jnp"), None),
            ("grid", dict(backend="pallas", shard="grid"), grid_mesh),
        ):
            cfg = EclatConfig(min_sup=ms, variant=variant, p=10,
                              use_diffsets=(variant == "v6"), **kw)
            t0 = time.perf_counter()
            res = mine(txns, spec.n_items, cfg, mesh=mesh)
            walls[label] = time.perf_counter() - t0
            maps[label] = res.support_map()
        identical = maps["jnp"] == maps["grid"]
        report["parity"][variant] = {
            "itemsets": len(maps["jnp"]),
            "identical": bool(identical),
            "wall_s": {k: round(v, 4) for k, v in walls.items()},
        }
        report["parity_ok"] &= bool(identical)

    # ---- (a') streaming parity: grid-placed ring, >= 9 slides -------------
    from repro.streaming import StreamConfig, StreamingMiner

    sspec = stream_spec(DATASET)
    block_txns, n_blocks = (128, 2) if smoke else (512, 4)
    miner = StreamingMiner(sspec.n_items,
                           StreamConfig(min_sup=0.01, n_blocks=n_blocks,
                                        block_txns=block_txns,
                                        backend="pallas", shard="grid"),
                           mesh=grid_mesh)
    stream_ok = True
    slides = 0
    for batch in transaction_stream(DATASET, block_txns,
                                    N_STREAM_SLIDES, seed=1):
        res = miner.advance(batch)
        full = mine(miner.window_transactions(), sspec.n_items,
                    EclatConfig(min_sup=0.01, variant="v4", backend="jnp"))
        stream_ok &= res.support_map() == full.support_map()
        slides += 1
    report["parity"]["streaming"] = {
        "engine": miner.engine.name,
        "slides": slides,
        "ring_spec": str(miner.ring.device.sharding.spec),
        "ring_bytes_per_device":
            int(miner.ring.device.addressable_shards[0].data.nbytes),
        "ring_bytes_total": int(miner.ring.device.nbytes),
        "identical": bool(stream_ok),
    }
    report["parity_ok"] &= bool(stream_ok)

    # ---- (b) per-device frontier bytes + pair work: pairs vs words vs grid
    # The same level-2 expansion, three mesh mappings.  Frontier bytes are
    # measured on the placement each backend's shard_map in_spec commits
    # (replicated for pairs; P(None, "data") for words/grid); pair work is
    # the per-device pair count the engine actually grouped/replicated.
    abs_ms = resolve_min_sup(ms, len(txns))
    db = build_vertical(txns, spec.n_items, abs_ms, order="support_asc")
    n1 = db.n_items
    iu, ju = np.triu_indices(n1, k=1)
    q = min(int(iu.shape[0]), 4096)
    iu, ju = iu[:q].astype(np.int32), ju[:q].astype(np.int32)
    sup1 = db.supports.astype(np.int32)
    bitmaps = jnp.asarray(db.bitmaps)
    checksums = set()

    def _entry(label, engine, frontier_per_dev, pairs_per_dev, res):
        checksums.add(int(np.asarray(res.supports).sum()))
        return {
            "engine": engine.name,
            "db_rows": int(n1),
            "n_pairs": int(q),
            "frontier_bytes_total": int(bitmaps.nbytes),
            "frontier_bytes_per_device": int(frontier_per_dev),
            "pairs_per_device": int(pairs_per_dev),
            "survivors": int(res.supports.shape[0]),
            "supports_checksum": int(np.asarray(res.supports).sum()),
        }

    # pairs: 4-way pair split, frontier replicated on every device
    ep = eng.make_engine("sharded", mesh=mesh4, inner="jnp")
    resp = ep.expand(bitmaps, iu, ju, sup1[iu], mode=eng.MODE_TIDSET,
                     min_sup=abs_ms, device_of_pair=iu.astype(np.int64) % 4)
    repl = jax.device_put(bitmaps, NamedSharding(mesh4, P()))
    report["placement"]["pairs"] = _entry(
        "pairs", ep, repl.addressable_shards[0].data.nbytes,
        int(np.max(ep.device_pair_counts[-1])), resp)

    # words: 4-way word split, every shard executes all pairs
    ew = eng.make_engine("tidsharded", mesh=mesh4, inner="jnp")
    fw = ew.prepare_frontier(bitmaps)
    resw = ew.expand(bitmaps, iu, ju, sup1[iu], mode=eng.MODE_TIDSET,
                     min_sup=abs_ms)
    report["placement"]["words"] = _entry(
        "words", ew, fw.addressable_shards[0].data.nbytes, q, resw)

    # grid 2x2: pairs split n_class ways AND words split n_data ways
    eg = eng.make_engine("grid", mesh=grid_mesh, inner="jnp")
    fg = eg.prepare_frontier(bitmaps)
    resg = eg.expand(bitmaps, iu, ju, sup1[iu], mode=eng.MODE_TIDSET,
                     min_sup=abs_ms,
                     device_of_pair=iu.astype(np.int64) % n_class)
    report["placement"]["grid"] = _entry(
        "grid", eg, fg.addressable_shards[0].data.nbytes,
        int(np.max(eg.device_pair_counts[-1])), resg)

    report["placement_supports_identical"] = len(checksums) == 1
    p_ = report["placement"]
    report["frontier_reduction_vs_pairs"] = (
        p_["pairs"]["frontier_bytes_per_device"]
        / p_["grid"]["frontier_bytes_per_device"])
    report["pairwork_reduction_vs_words"] = (
        p_["words"]["pairs_per_device"] / p_["grid"]["pairs_per_device"])
    print(json.dumps(report))


# ---------------------------------------------------------------------------
# parent harness entry
# ---------------------------------------------------------------------------

def gridscale_bench(out: List[str], smoke: bool = False) -> dict:
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
        raise RuntimeError(f"gridscale child failed:\n{proc.stderr[-2000:]}")
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    with open(BENCH_PATH, "w") as f:
        json.dump(report, f, indent=2)
    # parity is the acceptance-critical claim — a regression must fail the
    # harness (and CI), not just flip a flag inside the JSON artifact
    if not report["parity_ok"]:
        bad = [k for k, v in report["parity"].items() if not v["identical"]]
        raise RuntimeError(f"gridscale parity regression: {bad} not "
                           f"bit-identical (see {BENCH_PATH})")
    if not report["placement_supports_identical"]:
        raise RuntimeError("gridscale placement supports diverged across "
                           f"pairs/words/grid (see {BENCH_PATH})")
    for variant in VARIANTS:
        p = report["parity"][variant]
        out.append(_row(f"gridscale/parity/{variant}",
                        p["wall_s"]["grid"],
                        f"itemsets={p['itemsets']};identical={p['identical']}"))
    s = report["parity"]["streaming"]
    out.append(_row("gridscale/parity/streaming", 0.0,
                    f"slides={s['slides']};identical={s['identical']};"
                    f"ring_per_dev={s['ring_bytes_per_device']}"))
    for mode in ("pairs", "words", "grid"):
        m = report["placement"][mode]
        out.append(_row(f"gridscale/placement/{mode}", 0.0,
                        f"frontier_per_dev={m['frontier_bytes_per_device']};"
                        f"pairs_per_dev={m['pairs_per_device']};"
                        f"checksum={m['supports_checksum']}"))
    out.append(_row("gridscale/reduction", 0.0,
                    f"frontier_vs_pairs=x"
                    f"{report['frontier_reduction_vs_pairs']:.2f};"
                    f"pairwork_vs_words=x"
                    f"{report['pairwork_reduction_vs_words']:.2f};"
                    f"json={os.path.basename(BENCH_PATH)}"))
    return report


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (still writes BENCH_gridscale.json)")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        sys.path.insert(0, os.path.join(ROOT, "src"))
        _child(smoke=args.smoke)
    else:
        rows: List[str] = ["name,us_per_call,derived"]
        gridscale_bench(rows, smoke=args.smoke)
        print("\n".join(rows))
