#!/usr/bin/env python3
"""Smoke test of the mining engine on a TPU, through the user entry points.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the mesh backends on a four-chip host

One chip runs, in this one process:

1. batch mine, sparse: T10I4D100K at its published 100,000 transactions,
   ``min_sup=0.01``, variant v4, backend ``pallas`` (``repro.launch.mine``);
2. batch mine, dense: chess at 3,196 transactions, ``min_sup=0.7``, v6 with
   diffsets (kernel modes 1 and 2);
3. streaming + serving: ``repro.launch.stream --serve`` on T10I4D100K with a
   32,768-transaction window (8 blocks x 4,096), 12 slides, 200 queries;
   every slide must publish.

Each mine is checked against backend ``jnp`` on the same chip (sha1 of the
sorted (itemset, support) map) and its kernel executables must contain the
Mosaic kernel (``tpu_custom_call``), so neither the interpreter nor the jnp
twin can stand in.  Every served answer must pass ``verify_storm``.

``--chips 4`` runs only the mesh backends — ``tidsharded`` on a 4-device
``("data",)`` mesh and ``grid`` on a 2x2 ``("class", "data")`` mesh — mining
T10I4D100K as in phase 1, each compared with ``pallas`` on device 0.

Earlier lines report each phase (wall and compile seconds, itemsets,
checksum, executed path) as observations.  The last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
With no TPU, or outside a checkout of the repo, it exits non-zero and prints
no result; it never falls back to another platform.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

SPARSE = ["--dataset", "T10I4D100K", "--scale", "1.0", "--min-sup", "0.01",
          "--variant", "v4"]
DENSE = ["--dataset", "chess", "--scale", "1.0", "--min-sup", "0.7",
         "--variant", "v6", "--diffsets"]
# the writer's first slides compile on its own thread: a heartbeat deadline
# shorter than a compile would report a healthy writer as stalled
STREAM = ["--dataset", "T10I4D100K", "--n-blocks", "8", "--block-txns",
          "4096", "--batches", "12", "--serve", "--serve-queries", "200",
          "--stall-timeout", "300"]

_COMPILE_S = [0.0]


def _fail(msg: str) -> None:
    print(f"[smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def checksum(support_map) -> str:
    """sha1 of the sorted (itemset, support) map."""
    h = hashlib.sha1()
    for itemset, sup in sorted((tuple(sorted(k)), int(v))
                               for k, v in support_map.items()):
        h.update(f"{itemset}:{sup};".encode())
    return h.hexdigest()


def _count_compiles() -> None:
    import jax.monitoring

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE_S[0] += duration

    jax.monitoring.register_event_duration_secs_listener(listener)


def assert_mosaic(stats: dict) -> int:
    """The engine ran the Mosaic path, and the executable of every kernel
    shape it dispatched holds the Pallas kernel.  Returns the shape count."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.fused_intersect import (fused_intersect_compact_pairs,
                                               resolve_block_w)
    if stats.get("kernel_path") != "mosaic":
        raise AssertionError(f"kernel path {stats.get('kernel_path')!r}, "
                             f"not the Mosaic kernel")
    shapes = stats.get("call_shapes") or []
    if not shapes:
        raise AssertionError("the engine dispatched no kernel call")
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    for rows, words, pairs, mode in shapes:
        vec = jax.ShapeDtypeStruct((pairs,), jnp.int32)
        text = fused_intersect_compact_pairs.lower(
            jax.ShapeDtypeStruct((rows, words), jnp.uint32), vec, vec, vec,
            i32, i32, mode=mode,
            block_w=resolve_block_w(None, pairs, words, mode),
        ).compile().as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError(f"no tpu_custom_call in the executable for "
                                 f"shape {(rows, words, pairs, mode)}")
    return len(shapes)


def run_mine(name: str, argv) -> str:
    """Mine with ``pallas`` and with ``jnp`` through ``launch.mine``; the
    checksums must agree.  Returns the checksum."""
    from repro.launch import mine as mine_cli
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    res = mine_cli.main(argv + ["--backend", "pallas"])
    wall, comp = time.perf_counter() - t0, _COMPILE_S[0] - c0
    got = checksum(res.support_map())
    n_shapes = assert_mosaic(res.stats)
    ref = mine_cli.main(argv + ["--backend", "jnp"])
    want = checksum(ref.support_map())
    if got != want:
        raise AssertionError(f"{name}: pallas sha1 {got} != jnp sha1 {want}")
    _log(f"{name}: wall_s={wall} compile_s={comp} itemsets={res.total} "
         f"levels={res.counts} sha1={got} jnp_sha1_equal=True "
         f"path={res.stats['kernel_path']} kernel_shapes={n_shapes}")
    return got


def run_stream(argv) -> None:
    from repro.launch import stream as stream_cli
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    out = stream_cli.main(argv)
    wall, comp = time.perf_counter() - t0, _COMPILE_S[0] - c0
    ver = out["verify"]
    if out["kernel_path"] != "mosaic":
        raise AssertionError(f"stream ran {out['kernel_path']!r}")
    slides = int(argv[argv.index("--batches") + 1])
    if (out["n_errors"] or out["n_shed"] or ver["unverifiable"]
            or ver["verified"] != out["n_queries"]
            or out["window_version"] != slides):
        raise AssertionError(f"served storm not fully verified: {out}")
    _log(f"stream+serve: wall_s={wall} compile_s={comp} "
         f"queries={out['n_queries']} verified={ver['verified']} "
         f"checksum={ver['checksum']} window_version={out['window_version']} "
         f"path={out['kernel_path']}")


def run_mesh(argv) -> None:
    """tidsharded (4 x data) and grid (2 x 2) against pallas on device 0."""
    import jax
    from repro.core import engine as eng
    from repro.launch import mine as mine_cli
    from repro.launch.mesh import mesh_for_mining

    devices = jax.devices()
    if len(devices) != 4 or len({d.id for d in devices}) != 4:
        raise AssertionError(f"need 4 distinct devices, got {devices}")
    t0 = time.perf_counter()
    single = mine_cli.main(argv + ["--backend", "pallas"])
    want = checksum(single.support_map())
    _log(f"pallas on {devices[0]}: wall_s={time.perf_counter() - t0} "
         f"itemsets={single.total} sha1={want}")
    for backend, extra in (("tidsharded", []), ("grid", ["--grid", "2x2"])):
        c0, t0 = _COMPILE_S[0], time.perf_counter()
        res = mine_cli.main(argv + ["--backend", backend] + extra)
        wall, comp = time.perf_counter() - t0, _COMPILE_S[0] - c0
        got = checksum(res.support_map())
        if got != want:
            raise AssertionError(f"{backend} sha1 {got} != pallas {want}")
        mesh = mesh_for_mining(backend, "pairs", extra[-1] if extra else None)
        mesh_devs = set(mesh.devices.flat)
        if len(mesh_devs) != 4 or not mesh_devs <= set(devices):
            raise AssertionError(f"{backend} mesh does not span the 4 "
                                 f"devices: {mesh.devices}")
        frontier = eng.resolve_engine(backend, mesh).prepare_frontier(
            jax.device_put(res.db.bitmaps))
        on = {s.device for s in frontier.addressable_shards}
        if on != mesh_devs:
            raise AssertionError(f"{backend} frontier shards sit on {on}, "
                                 f"not on all of {mesh_devs}")
        _log(f"{backend} mesh={dict(mesh.shape)}: wall_s={wall} "
             f"compile_s={comp} itemsets={res.total} sha1={got} "
             f"pallas_sha1_equal=True frontier_shards={len(on)} "
             f"path={res.stats['kernel_path']}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4: run only the mesh backends on a four-chip host")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        _fail("run from a checkout of the repo: no src/repro next to "
              "chip_smoke.py")
    sys.path.insert(0, os.path.join(HERE, "src"))

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"no TPU: JAX found {devices[0].platform} devices")
    from repro.launch.compile_cache import enable_compile_cache
    _log(f"compile cache: {enable_compile_cache()}")
    _count_compiles()
    _log(f"device: {devices[0].device_kind} x{len(devices)}")

    try:
        if args.chips == 4:
            run_mesh(SPARSE)
        else:
            run_mine("mine-sparse T10I4D100K", SPARSE)
            run_mine("mine-dense chess", DENSE)
            run_stream(STREAM)
    except Exception:  # noqa: BLE001 — any phase failure fails the smoke
        traceback.print_exc()
        _fail("a phase failed")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
