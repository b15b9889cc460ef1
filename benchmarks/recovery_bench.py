"""Recovery economics: restore-and-resume vs re-mine-from-scratch.

    python benchmarks/recovery_bench.py [--smoke]   # or benchmarks/run.py

The resilience contract (DESIGN.md §10) is only worth its checkpoint bytes
if recovering a crashed stream is cheaper than replaying it from the start.
This bench runs in a forced-4-device subprocess (the XLA device count is
process-global) and measures, on the paper's T10I4D100K stream:

  resume    crash the miner at a late slide, restore the newest durable
            checkpoint, replay the remaining slides — wall-clock vs a fresh
            miner replaying the whole stream, with *identical* final
            support checksums (divergence raises, it is not a data point);
  remesh    the same restore landed on a different mesh factorization
            (4 -> 2 devices, 2x2 grid -> 4x1, sharded -> single device),
            checksum-gated against the same reference;
  torn      a kill *inside* the checkpoint write itself: the torn step is
            invisible, restore falls back one step and still converges.

Writes ``BENCH_recovery.json`` for the cross-PR trajectory.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import List

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
BENCH_PATH = os.path.join(ROOT, "BENCH_recovery.json")
DATASET = "T10I4D100K"


def _row(name: str, seconds: float, derived: str) -> str:
    return f"{name},{seconds * 1e6:.0f},{derived}"


# ---------------------------------------------------------------------------
# child: runs under --xla_force_host_platform_device_count=4
# ---------------------------------------------------------------------------

def _child(smoke: bool) -> None:
    import tempfile
    import time

    import jax

    from repro.data import stream_spec, transaction_stream
    from repro.dist.compat import make_mesh
    from repro.faults import (InjectedFault, clear_kill_hook, set_kill_hook)
    from repro.streaming import (StreamCheckpointer, StreamConfig,
                                 StreamingMiner, restore_miner)
    from repro.training import valid_steps

    if len(jax.devices()) < 4:
        raise SystemExit("child needs 4 forced host devices (XLA_FLAGS)")

    spec = stream_spec(DATASET)
    block_txns, n_blocks, slides = (128, 2, 5) if smoke else (512, 4, 8)
    min_sup = 0.02 if smoke else 0.01
    kill_slide = slides - 1
    batches = list(transaction_stream(DATASET, block_txns, slides, seed=1))
    mesh4 = make_mesh((4,), ("data",))

    def checksum(res):
        sm = res.support_map()
        return {"itemsets": len(sm), "support_sum": int(sum(sm.values()))}

    def fresh(cfg, mesh):
        return StreamingMiner(spec.n_items, cfg, mesh=mesh,
                              keep_transactions=False)

    def crashed_stream(cfg, mesh, directory, point="miner:mid_append"):
        """Checkpoint-per-slide run killed at `point` during the last
        slide; returns the newest durable step."""
        miner = fresh(cfg, mesh)
        ck = StreamCheckpointer(directory, every=1, keep=3)
        hits = {"n": 0}

        def die(name):
            if name == point:
                hits["n"] += 1
                raise InjectedFault(name)
        try:
            for i, b in enumerate(batches):
                if i == kill_slide:
                    set_kill_hook(die)
                miner.advance(b)
                ck.save(miner, i + 1)
                try:
                    ck.wait()
                except InjectedFault:
                    break
        except InjectedFault:
            pass
        finally:
            clear_kill_hook()
        if not hits["n"] > 0:
            raise RuntimeError(f"kill point {point} never fired")
        steps = valid_steps(directory)
        if not steps:
            raise RuntimeError("no durable checkpoint survived")
        return steps[-1]

    def resume(directory, mesh, backend=None, shard=None):
        t0 = time.perf_counter()
        miner, start = restore_miner(directory, mesh=mesh, backend=backend,
                                     shard=shard, keep_transactions=False)
        res = None
        for b in batches[start:]:
            res = miner.advance(b)
        if res is None:
            res = miner.mine_window()
        return res, time.perf_counter() - t0, start

    report: dict = {
        "dataset": DATASET, "smoke": bool(smoke),
        "block_txns": block_txns, "n_blocks": n_blocks, "slides": slides,
        "kill_slide": kill_slide, "min_sup": min_sup,
        "jax_backend": jax.default_backend(),
        "checksums_identical": True,
    }
    cfg = StreamConfig(min_sup=min_sup, n_blocks=n_blocks,
                       block_txns=block_txns, backend="tidsharded")

    # ---- (a) resume vs scratch, same 4-device mesh ------------------------
    with tempfile.TemporaryDirectory() as d:
        step = crashed_stream(cfg, mesh4, d)     # also warms the jit caches
        t0 = time.perf_counter()
        scratch_miner = fresh(cfg, mesh4)
        for b in batches:
            ref = scratch_miner.advance(b)
        t_scratch = time.perf_counter() - t0
        ref_map = ref.support_map()
        res, t_restore, start = resume(d, mesh4)
        ok = res.support_map() == ref_map
        report["resume"] = {
            "durable_step": int(step), "resumed_from_slide": int(start),
            "replayed_slides": slides - int(start),
            "t_scratch_s": round(t_scratch, 4),
            "t_restore_s": round(t_restore, 4),
            "speedup": round(t_scratch / t_restore, 2) if t_restore else 0.0,
            "checksum": checksum(res), "identical": bool(ok),
        }
        report["checksums_identical"] &= ok

        # ---- (b) the same checkpoint landed on different meshes -----------
        report["remesh"] = []
        for label, mesh, backend, shard in (
            ("4dev->2dev", make_mesh((2,), ("data",),
                                     devices=jax.devices()[:2]), None, None),
            ("4dev->grid2x2", make_mesh((2, 2), ("class", "data"),
                                        devices=jax.devices()[:4]),
             "grid", "grid"),
            ("4dev->single", None, "pallas", "pairs"),
        ):
            res, t_r, _ = resume(d, mesh, backend=backend, shard=shard)
            ok = res.support_map() == ref_map
            report["remesh"].append({
                "move": label, "t_restore_s": round(t_r, 4),
                "checksum": checksum(res), "identical": bool(ok)})
            report["checksums_identical"] &= ok

    # ---- (b') a grid-mesh checkpoint refactored 2x2 -> 4x1 ----------------
    gcfg = StreamConfig(min_sup=min_sup, n_blocks=n_blocks,
                        block_txns=block_txns, backend="grid", shard="grid")
    mesh22 = make_mesh((2, 2), ("class", "data"), devices=jax.devices()[:4])
    with tempfile.TemporaryDirectory() as d:
        crashed_stream(gcfg, mesh22, d, point="miner:pre_deep_expand")
        mesh41 = make_mesh((4, 1), ("class", "data"),
                           devices=jax.devices()[:4])
        res, t_r, _ = resume(d, mesh41)
        ok = res.support_map() == ref_map
        report["remesh"].append({
            "move": "grid2x2->grid4x1", "t_restore_s": round(t_r, 4),
            "checksum": checksum(res), "identical": bool(ok)})
        report["checksums_identical"] &= ok

    # ---- (c) a kill inside the checkpoint write: fall back one step -------
    with tempfile.TemporaryDirectory() as d:
        step = crashed_stream(cfg, mesh4, d, point="checkpoint:mid_write")
        res, t_r, start = resume(d, mesh4)
        ok = res.support_map() == ref_map
        report["torn_write"] = {
            "durable_step": int(step), "resumed_from_slide": int(start),
            "t_restore_s": round(t_r, 4),
            "checksum": checksum(res), "identical": bool(ok)}
        report["checksums_identical"] &= ok

    print(json.dumps(report))


# ---------------------------------------------------------------------------
# parent harness entry
# ---------------------------------------------------------------------------

def recovery_bench(out: List[str], smoke: bool = False) -> dict:
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
        raise RuntimeError(f"recovery child failed:\n{proc.stderr[-2000:]}")
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    with open(BENCH_PATH, "w") as f:
        json.dump(report, f, indent=2)
    # bit-exact recovery is the acceptance-critical claim: a restore that
    # "works" but mines different itemsets must fail the harness, not ship
    # as a timing row
    if not report["checksums_identical"]:
        bad = ([m["move"] for m in report["remesh"] if not m["identical"]]
               + [k for k in ("resume", "torn_write")
                  if not report[k]["identical"]])
        raise RuntimeError(f"recovery checksum divergence: {bad} "
                           f"(see {BENCH_PATH})")
    r = report["resume"]
    out.append(_row("recovery/resume", r["t_restore_s"],
                    f"scratch={r['t_scratch_s']}s;speedup=x{r['speedup']};"
                    f"replayed={r['replayed_slides']}/{report['slides']};"
                    f"identical={r['identical']}"))
    for m in report["remesh"]:
        out.append(_row(f"recovery/remesh/{m['move']}", m["t_restore_s"],
                        f"itemsets={m['checksum']['itemsets']};"
                        f"identical={m['identical']}"))
    t = report["torn_write"]
    out.append(_row("recovery/torn_write", t["t_restore_s"],
                    f"fellback_to={t['durable_step']};"
                    f"identical={t['identical']};"
                    f"json={os.path.basename(BENCH_PATH)}"))
    return report


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (still writes BENCH_recovery.json)")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        sys.path.insert(0, os.path.join(ROOT, "src"))
        _child(smoke=args.smoke)
    else:
        rows: List[str] = ["name,us_per_call,derived"]
        recovery_bench(rows, smoke=args.smoke)
        print("\n".join(rows))
