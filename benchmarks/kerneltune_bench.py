"""Kernel-tune benchmark: autotune sweep + measured backend-crossover table.

Three artifacts in one ``BENCH_kerneltune.json``:

``shapes``
    The autotune sweep — per (Q, W, mode) shape class, every candidate tile
    width's steady-state seconds (compile excluded), the tuned winner, and
    whether the roofline cost model's prediction agreed.  Off-TPU the fused
    path is the XLA ref with no tile knob, so the sweep collapses to one
    honest candidate per shape (see ``kernels.autotune``); winners persist
    in the autotune cache so subsequent runs start tuned.

``tuned_vs_default``
    mine() end-to-end with the tuned configuration vs the hard-coded
    ``block_w=512`` default on the largest bench shape — the accept gate
    for this PR's raw-speed pass.  The itemset checksum of the two runs
    MUST be bit-identical; a divergence raises (and fails CI): a tuner
    that changes answers is a bug, not a speedup.

``crossover``
    The measured dispatch table behind ``resolve_engine("auto")`` /
    DESIGN.md §6: steady-state expand() throughput of the jnp and pallas
    backends per (Q, W) cell — plus the mesh backends (sharded /
    tidsharded / grid) measured in a 4-device subprocess — and the winner
    of each cell.  ``core.engine.DispatchPolicy`` loads exactly this list.
"""
from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import time
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import EclatConfig, mine
from repro.core import engine as eng
from repro.data import generate
from repro.kernels import autotune

BENCH_PATH = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_kerneltune.json")

# log-spaced (Q, W) grid: small/medium/large pair batches x narrow/wide rows
SWEEP_SHAPES = [(1024, 32), (1024, 512), (8192, 128), (8192, 2048),
                (32768, 512)]
SWEEP_SHAPES_SMOKE = [(512, 32), (2048, 128)]
CROSSOVER_CELLS = [(256, 32), (1024, 128), (4096, 512), (16384, 128),
                   (16384, 1024)]
CROSSOVER_CELLS_SMOKE = [(256, 32), (2048, 128)]


def _row(name: str, seconds: float, derived: str) -> str:
    return f"{name},{seconds * 1e6:.0f},{derived}"


def itemset_checksum(res) -> str:
    """Order-independent digest of (itemset, support) pairs — the
    bit-identical-answers gate for tuned-vs-default runs."""
    h = hashlib.sha256()
    for items, sup in sorted(res.store.support_map().items()):
        h.update(repr((items, int(sup))).encode())
    return h.hexdigest()[:16]


def _steady_expand_s(e, q: int, w: int, reps: int = 3) -> float:
    """Steady-state seconds per expand() on a synthetic (q, w) batch:
    compile excluded, every rep blocked to completion."""
    rng = np.random.default_rng(0)
    p = min(max(q, 2), 1024)
    bitmaps = e.prepare_frontier(
        jnp.asarray(rng.integers(0, 2 ** 32, (p, w), dtype=np.uint32)))
    left = rng.integers(0, p, q).astype(np.int32)
    right = rng.integers(0, p, q).astype(np.int32)
    supl = np.full(q, w * 32, np.int32)
    dev = (np.arange(q) % e.n_devices) if e.n_devices > 1 else None

    def call():
        res = e.expand(bitmaps, left, right, supl, mode=eng.MODE_TIDSET,
                       min_sup=w * 16, device_of_pair=dev)
        jax.block_until_ready(res.bitmaps)

    call()  # trace + compile, not timed
    call()  # steady-state warm-up
    t0 = time.perf_counter()
    for _ in range(reps):
        call()
    return (time.perf_counter() - t0) / reps


_MESH_PROBE = r"""
import json, sys
import numpy as np, jax
from jax.sharding import Mesh
sys.path.insert(0, {src!r})
from repro.core import engine as eng
from benchmarks.kerneltune_bench import _steady_expand_s
cells = json.loads(sys.argv[1])
devs = np.array(jax.devices())
mesh = Mesh(devs.reshape(-1), ("data",))
grid = Mesh(devs.reshape(2, -1), ("class", "data"))
out = []
for q, w in cells:
    row = {{"q": q, "w": w}}
    for name, e in (
        ("sharded", eng.make_engine("sharded", mesh=mesh, inner="jnp")),
        ("tidsharded", eng.make_engine("tidsharded", mesh=mesh, inner="jnp")),
        ("grid", eng.make_engine("grid", mesh=grid, inner="jnp")),
    ):
        row[name] = _steady_expand_s(e, q, w)
    out.append(row)
print(json.dumps(out))
"""


def _mesh_crossover(cells, n_devices: int = 4) -> Optional[dict]:
    """Measure the mesh backends per cell in a forced-multi-device
    subprocess (the parent process has already initialized jax with one
    device).  Returns {(q, w): {backend: steady_s}} or None if the probe
    fails — the crossover table then records single-device winners only."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={n_devices}")
    # forced host devices are a CPU rehearsal: pin the child to the CPU so
    # it never contends for an accelerator the parent process holds
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [root, os.path.join(root, "src"), env.get("PYTHONPATH", "")])
    script = _MESH_PROBE.format(src=os.path.join(root, "src"))
    try:
        proc = subprocess.run(
            [sys.executable, "-c", script, json.dumps(list(cells))],
            capture_output=True, text=True, timeout=1800, env=env, cwd=root)
        if proc.returncode != 0:
            return None
        rows = json.loads(proc.stdout.strip().splitlines()[-1])
    except (OSError, ValueError, subprocess.SubprocessError):
        return None
    return {(r["q"], r["w"]): {k: v for k, v in r.items()
                               if k not in ("q", "w")} for r in rows}


def kerneltune_bench(out: List[str], smoke: bool = False) -> dict:
    report: dict = {
        "smoke": bool(smoke),
        "jax_backend": jax.default_backend(),
        "autotune_cache": autotune.table_path(),
        "shapes": [], "crossover": [],
    }

    # ---- 1. autotune sweep ------------------------------------------------
    shapes = SWEEP_SHAPES_SMOKE if smoke else SWEEP_SHAPES
    reps = 2 if smoke else 5
    for q, w in shapes:
        rec = autotune.tune_shape(q, w, mode=eng.MODE_TIDSET, reps=reps)
        report["shapes"].append(rec)
        out.append(_row(f"kerneltune/sweep/q{q}_w{w}", rec["steady_s"],
                        f"block_w={rec['tuned_block_w']};"
                        f"model_agrees={rec['model_agrees']};"
                        f"candidates={len(rec['candidates'])}"))

    # ---- 2. tuned vs default on the largest bench shape -------------------
    scale = 0.02 if smoke else float(os.environ.get("BENCH_SCALE", "0.08"))
    txns, spec = generate("T10I4D100K", scale=scale, seed=1)
    ms = spec.min_sups[len(spec.min_sups) // 2]
    # "default" reproduces the pre-tuning configuration exactly: hard-coded
    # block_w=512 and the legacy two-dispatch compaction; "tuned" is the
    # autotuned tile width with the fused survivor-compaction epilogue
    arms = {
        "default": EclatConfig(min_sup=ms, variant="v4", backend="pallas",
                               block_w=autotune.DEFAULT_BLOCK_W,
                               autotune=False, compact=False),
        "tuned": EclatConfig(min_sup=ms, variant="v4", backend="pallas",
                             block_w=None, autotune=True, compact=True),
    }
    walls, sums = {}, {}
    for label, cfg in arms.items():   # warm trace/compile caches (and, for
        # the tuned arm, run any tune-on-miss sweeps outside the clock)
        sums[label] = itemset_checksum(mine(txns, spec.n_items, cfg))
        walls[label] = float("inf")
    for _ in range(1 if smoke else 5):
        # interleave the arms so load drift on a shared host hits both;
        # min-of-N per arm is then robust to both drift and timer noise
        for label, cfg in arms.items():
            t0 = time.perf_counter()
            mine(txns, spec.n_items, cfg)
            walls[label] = min(walls[label], time.perf_counter() - t0)
    if sums["default"] != sums["tuned"]:
        raise RuntimeError(
            f"tuned-vs-default itemset checksum divergence: "
            f"default={sums['default']} tuned={sums['tuned']} — the tuner "
            f"changed the mined answer, refusing to publish a dispatch table")
    report["tuned_vs_default"] = {
        "dataset": "T10I4D100K", "scale": scale, "n_txn": len(txns),
        "default_wall_s": walls["default"], "tuned_wall_s": walls["tuned"],
        "speedup": (walls["default"] / walls["tuned"]
                    if walls["tuned"] > 0 else 0.0),
        "itemset_checksum": sums["tuned"], "checksums_match": True,
    }
    out.append(_row("kerneltune/tuned_vs_default", walls["tuned"],
                    f"x{report['tuned_vs_default']['speedup']:.2f};"
                    f"checksum={sums['tuned']}"))

    # ---- 3. backend crossover sweep ---------------------------------------
    cells = CROSSOVER_CELLS_SMOKE if smoke else CROSSOVER_CELLS
    mesh_rows = None if smoke else _mesh_crossover(cells)
    if not smoke and mesh_rows is None:
        out.append(_row("kerneltune/mesh_probe_failed", 0.0,
                        "crossover=single-device-only"))
    mesh_backend_of = {"sharded": "sharded", "tidsharded": "tidsharded",
                       "grid": "grid"}
    for q, w in cells:
        cell = {"q": q, "w": w, "steady_s": {}}
        for backend in ("jnp", "pallas"):
            e = eng.make_engine(backend)
            cell["steady_s"][backend] = _steady_expand_s(e, q, w)
        if mesh_rows and (q, w) in mesh_rows:
            cell["steady_s"].update(mesh_rows[(q, w)])
        singles = {b: s for b, s in cell["steady_s"].items()
                   if b in ("jnp", "pallas")}
        meshes = {b: s for b, s in cell["steady_s"].items()
                  if b in mesh_backend_of}
        cell["best_single"] = min(singles, key=singles.get)
        cell["best_mesh"] = (min(meshes, key=meshes.get) if meshes else None)
        cell["speedup_fused_vs_jnp"] = (
            cell["steady_s"]["jnp"] / cell["steady_s"]["pallas"]
            if cell["steady_s"]["pallas"] > 0 else 0.0)
        report["crossover"].append(cell)
        out.append(_row(f"kerneltune/crossover/q{q}_w{w}",
                        cell["steady_s"][cell["best_single"]],
                        f"best={cell['best_single']};"
                        f"best_mesh={cell['best_mesh']};"
                        f"fused_vs_jnp=x{cell['speedup_fused_vs_jnp']:.2f}"))

    with open(BENCH_PATH, "w") as f:
        json.dump(report, f, indent=2)
    out.append(_row("kerneltune/json", 0.0,
                    f"json={os.path.basename(BENCH_PATH)};"
                    f"cells={len(report['crossover'])}"))
    return report
