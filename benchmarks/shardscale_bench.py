"""Word-sharded frontier scaling: parity + per-device memory vs mesh size.

    python benchmarks/shardscale_bench.py [--smoke]   # or benchmarks/run.py

The tid-sharded engine (DESIGN.md §7) carries the frontier bitmap as
``P(None, "data")`` so per-device bitmap memory is total/n_shards — the mode
that lets a database bigger than one device's memory stay minable.  This
bench demonstrates the two halves of that claim on the forced 4-device CPU
host (a subprocess, because the XLA device count is process-global):

  parity   batch ``mine()`` v1–v6 and the streaming sliding-window miner are
           bit-exact across jnp / pallas / tidsharded;
  memory   the same expansion on 1-, 2- and 4-device meshes keeps the mined
           supports identical while per-device frontier bytes drop ~1/n.

Writes ``BENCH_shardscale.json`` for the cross-PR trajectory.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import List

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
BENCH_PATH = os.path.join(ROOT, "BENCH_shardscale.json")
DATASET = "T10I4D100K"
VARIANTS = ["v1", "v2", "v3", "v4", "v5", "v6"]


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
    mesh4 = make_mesh((4,), ("data",))
    report: dict = {
        "dataset": DATASET, "scale": scale, "min_sup": float(ms),
        "n_txn": len(txns), "smoke": bool(smoke),
        "jax_backend": jax.default_backend(),
        "parity": {}, "memory": [], "parity_ok": True,
    }

    # ---- (a) batch parity: v1-v6, tidsharded vs jnp vs pallas -------------
    for variant in VARIANTS:
        maps = {}
        walls = {}
        for label, kw in (
            ("jnp", dict(backend="jnp")),
            ("pallas", dict(backend="pallas")),
            ("tidsharded", dict(backend="pallas", shard="words")),
        ):
            cfg = EclatConfig(min_sup=ms, variant=variant, p=10,
                              use_diffsets=(variant == "v6"), **kw)
            mesh = mesh4 if label == "tidsharded" else None
            t0 = time.perf_counter()
            res = mine(txns, spec.n_items, cfg, mesh=mesh)
            walls[label] = time.perf_counter() - t0
            maps[label] = res.support_map()
        identical = maps["jnp"] == maps["pallas"] == maps["tidsharded"]
        report["parity"][variant] = {
            "itemsets": len(maps["jnp"]),
            "identical": bool(identical),
            "wall_s": {k: round(v, 4) for k, v in walls.items()},
        }
        report["parity_ok"] &= bool(identical)

    # ---- (a') streaming parity: word-sharded ring vs batch re-mine --------
    from repro.streaming import StreamConfig, StreamingMiner

    sspec = stream_spec(DATASET)
    block_txns, n_blocks = (128, 2) if smoke else (512, 4)
    n_slides = 3 if smoke else 5
    miner = StreamingMiner(sspec.n_items,
                           StreamConfig(min_sup=0.01, n_blocks=n_blocks,
                                        block_txns=block_txns,
                                        backend="pallas", shard="words"),
                           mesh=mesh4)
    stream_ok = True
    slides = 0
    for batch in transaction_stream(DATASET, block_txns,
                                    n_blocks + n_slides, seed=1):
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

    # ---- (b) per-device frontier bytes vs mesh size -----------------------
    abs_ms = resolve_min_sup(ms, len(txns))
    db = build_vertical(txns, spec.n_items, abs_ms, order="support_asc")
    n1 = db.n_items
    iu, ju = np.triu_indices(n1, k=1)
    q = min(int(iu.shape[0]), 4096)
    iu, ju = iu[:q].astype(np.int32), ju[:q].astype(np.int32)
    sup1 = db.supports.astype(np.int32)
    checksums = set()
    for n in (1, 2, 4):
        mesh = make_mesh((n,), ("data",), devices=jax.devices()[:n])
        e = eng.make_engine("tidsharded", mesh=mesh, inner="jnp")
        frontier = e._ensure_sharded(jnp.asarray(db.bitmaps))
        res = e.expand(jnp.asarray(db.bitmaps), iu, ju, sup1[iu],
                       mode=eng.MODE_TIDSET, min_sup=abs_ms)
        entry = {
            "n_devices": n,
            "db_rows": int(n1),
            "db_bitmap_bytes_total": int(frontier.nbytes),
            "db_bitmap_bytes_per_device":
                int(frontier.addressable_shards[0].data.nbytes),
            "level_bitmap_bytes_total": int(res.bitmaps.nbytes),
            "level_bitmap_bytes_per_device":
                int(res.bitmaps.addressable_shards[0].data.nbytes),
            "survivors": int(res.supports.shape[0]),
            "supports_checksum": int(np.asarray(res.supports).sum()),
        }
        report["memory"].append(entry)
        checksums.add(entry["supports_checksum"])
    report["memory_supports_identical"] = len(checksums) == 1
    m1 = report["memory"][0]["level_bitmap_bytes_per_device"]
    m4 = report["memory"][-1]["level_bitmap_bytes_per_device"]
    report["per_device_reduction_4dev"] = m1 / m4 if m4 else 0.0
    print(json.dumps(report))


# ---------------------------------------------------------------------------
# parent harness entry
# ---------------------------------------------------------------------------

def shardscale_bench(out: List[str], smoke: bool = False) -> dict:
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
        raise RuntimeError(f"shardscale child failed:\n{proc.stderr[-2000:]}")
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    with open(BENCH_PATH, "w") as f:
        json.dump(report, f, indent=2)
    # parity is the acceptance-critical claim — a regression must fail the
    # harness (and CI), not just flip a flag inside the JSON artifact
    if not report["parity_ok"]:
        bad = [k for k, v in report["parity"].items() if not v["identical"]]
        raise RuntimeError(f"shardscale parity regression: {bad} not "
                           f"bit-identical (see {BENCH_PATH})")
    for variant in VARIANTS:
        p = report["parity"][variant]
        out.append(_row(f"shardscale/parity/{variant}",
                        p["wall_s"]["tidsharded"],
                        f"itemsets={p['itemsets']};identical={p['identical']}"))
    s = report["parity"]["streaming"]
    out.append(_row("shardscale/parity/streaming", 0.0,
                    f"slides={s['slides']};identical={s['identical']};"
                    f"ring_per_dev={s['ring_bytes_per_device']}"))
    for m in report["memory"]:
        out.append(_row(f"shardscale/mem/n{m['n_devices']}", 0.0,
                        f"level_per_dev={m['level_bitmap_bytes_per_device']};"
                        f"db_per_dev={m['db_bitmap_bytes_per_device']};"
                        f"checksum={m['supports_checksum']}"))
    out.append(_row("shardscale/reduction", 0.0,
                    f"x{report['per_device_reduction_4dev']:.2f};"
                    f"json={os.path.basename(BENCH_PATH)}"))
    return report


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (still writes BENCH_shardscale.json)")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        sys.path.insert(0, os.path.join(ROOT, "src"))
        _child(smoke=args.smoke)
    else:
        rows: List[str] = ["name,us_per_call,derived"]
        shardscale_bench(rows, smoke=args.smoke)
        print("\n".join(rows))
