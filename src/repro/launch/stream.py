"""Streaming driver: sliding-window mining over a live micro-batch stream.

    PYTHONPATH=src python -m repro.launch.stream --dataset T10I4D100K \
        --min-sup 0.01 --block-txns 512 --n-blocks 8 --batches 12 \
        --top-k 5 --min-conf 0.8 [--drift-every 6] [--backend pallas]

Each slide prints the re-mine latency, window occupancy, class churn
(equivalence classes entering/leaving the active set), and the live top-k;
``--min-conf`` adds the rule count of the current window.

Recovery (DESIGN.md §10): ``--checkpoint-dir`` writes an async miner
snapshot every ``--checkpoint-every`` slides; after a crash, rerun with
``--restore`` (same ``--dataset``/``--seed``/``--drift-every`` — the stream
is deterministic, so completed slides are skipped and the rest replayed).
``--remesh`` restores under *this* invocation's ``--backend``/``--shard``/
``--grid`` and visible devices instead of the checkpoint's recorded config —
live re-meshing, bit-exact either way.

Serving (DESIGN.md §11): ``--serve`` puts the stream behind the async
admission front end — the slides above run on a writer thread (checkpointer
and fault injection included) while the main thread fires a
``--serve-queries`` storm at the bounded queue and verifies every answer
against the synchronous path at its stamped ``window_version``.  A writer
that stops beating its heartbeat for ``--stall-timeout`` seconds is
*reported* (exit code 4) instead of hanging the readers.
"""
from __future__ import annotations

import argparse
import threading

from ..data import PAPER_DATASETS, stream_spec, transaction_stream
from ..faults import InjectedFault, clear_kill_hook, set_kill_hook
from ..serving import StreamQueryService
from ..streaming import (StreamCheckpointer, StreamConfig, StreamingMiner,
                         peek_config, restore_miner)
from .compile_cache import enable_compile_cache


def _serve_mode(args, miner, cfg, ck, start):
    """--serve: slides on a writer thread, query storm on the main thread.

    The writer is the exact synchronous slide loop (checkpointer, kill-hook
    fault injection and all) moved behind :class:`ServingFrontend`; readers
    never touch the miner, only published snapshots, so a crashed or stalled
    writer degrades to answering from the last complete window — detected
    and reported, never a hang.
    """
    from ..serving import (AdmissionConfig, ServingFrontend, query_mix,
                           run_storm, verify_storm)
    from ..training import HeartbeatMonitor, WriterStalledError

    acfg = AdmissionConfig(max_queue=args.queue_cap, policy=args.serve_policy,
                           stall_timeout_s=args.stall_timeout or None,
                           keep_versions=max(args.batches + 2, 8))
    frontend = ServingFrontend(miner, acfg)
    writer_fault = []

    def writer():
        try:
            for i, batch in enumerate(transaction_stream(
                    args.dataset, cfg.block_txns, args.batches,
                    seed=args.seed, drift_every=args.drift_every)):
                if i < start:
                    continue
                if args.kill_after is not None and i == args.kill_after:
                    def _die(name):
                        if name == "miner:mid_append":
                            raise InjectedFault(name)
                    set_kill_hook(_die)
                res = frontend.ingest(batch)
                print(f"[stream] slide {i:3d}: window={res.n_txn} txns "
                      f"itemsets={res.total} version={res.version} "
                      f"latency={res.stats['slide_s']*1e3:.1f}ms")
                if ck is not None:
                    ck.maybe_save(miner, i + 1)
        except InjectedFault as e:
            writer_fault.append(e)
        finally:
            clear_kill_hook()
            if ck is not None:
                ck.wait()

    wt = threading.Thread(target=writer, name="miner-writer", daemon=True)
    wt.start()
    monitor = (HeartbeatMonitor(frontend.heartbeat, args.stall_timeout,
                                name="miner writer")
               if args.stall_timeout else None)
    queries = query_mix(args.serve_queries, seed=args.seed)
    outcome = run_storm(frontend, queries, n_clients=args.serve_clients)
    stalled = None
    while wt.is_alive():
        if monitor is not None:
            try:
                monitor.assert_alive()
            except WriterStalledError as e:
                stalled = e
                break
        wt.join(timeout=0.1)

    ver = verify_storm(frontend, queries, outcome)
    m = frontend.metrics.summary()
    c = frontend.cache.stats()
    print(f"[stream] storm: answered {m['n_answered']}/{len(queries)} "
          f"(shed {m['n_shed']}, errors {m['n_errors']}); latency "
          f"p50 {m['latency_ms']['p50']:.2f}ms p99 "
          f"{m['latency_ms']['p99']:.2f}ms; {m['qps']:.0f} qps; cache hit "
          f"rate {c['hit_rate']:.1%} ({c['stale_evicted']} invalidated)")
    print(f"[stream] verified {ver['verified']} answers bit-identical at "
          f"their window versions (checksum {ver['checksum']}); final "
          f"window_version={frontend.window_version}")
    frontend.stop()
    if stalled is not None:
        print(f"[stream] STALL DETECTED: {stalled} — readers kept answering "
              f"from window_version={frontend.window_version}")
        raise SystemExit(4)
    if writer_fault:
        print(f"[stream] injected crash mid-append at slide "
              f"{args.kill_after}; storm kept answering from the last "
              f"published window — recover with --restore")
        raise SystemExit(3)
    if outcome["errors"]:
        raise SystemExit(f"[stream] query errors: {outcome['errors']}")
    if ck is not None:
        print(f"[stream] checkpoints durable in {args.checkpoint_dir}")
    return {"n_queries": len(queries), "n_answered": m["n_answered"],
            "n_shed": m["n_shed"], "n_errors": m["n_errors"],
            "verify": ver, "window_version": frontend.window_version,
            "kernel_path": miner.engine.kernel_path()}


def main(argv=None):
    """Run the stream; with ``--serve`` returns the storm summary (counts,
    the ``verify_storm`` record, final window version, kernel path)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="T10I4D100K",
                    choices=list(PAPER_DATASETS))
    ap.add_argument("--min-sup", type=float, default=0.01)
    ap.add_argument("--block-txns", type=int, default=512,
                    help="transactions per micro-batch block (multiple of 32)")
    ap.add_argument("--n-blocks", type=int, default=8,
                    help="window capacity in blocks")
    ap.add_argument("--batches", type=int, default=12,
                    help="how many micro-batches to stream")
    ap.add_argument("--drift-every", type=int, default=None,
                    help="re-seed the pattern pool every N batches")
    ap.add_argument("--backend", default="pallas",
                    choices=["jnp", "pallas", "sharded", "tidsharded", "grid"],
                    help="engine backend (DESIGN.md §6)")
    ap.add_argument("--shard", default="pairs",
                    choices=["pairs", "words", "grid"],
                    help="mesh split under a device mesh: candidate pairs "
                         "(frontier replicated), the frontier's word axis, "
                         "or both on a 2D class x data grid (DESIGN.md §8)")
    ap.add_argument("--grid", default=None, metavar="RxC",
                    help="class x data mesh shape for --shard grid, e.g. 2x2 "
                         "(default: auto-factorize the visible devices)")
    ap.add_argument("--top-k", type=int, default=5)
    ap.add_argument("--min-conf", type=float, default=0.0,
                    help="if >0, also report association rules per slide")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="write an async miner snapshot (MinerState, "
                         "DESIGN.md §10) every --checkpoint-every slides")
    ap.add_argument("--checkpoint-every", type=int, default=1, metavar="N",
                    help="checkpoint cadence in slides (with --checkpoint-dir)")
    ap.add_argument("--keep", type=int, default=3,
                    help="checkpoints retained by GC (with --checkpoint-dir)")
    ap.add_argument("--restore", action="store_true",
                    help="resume from the newest checkpoint in "
                         "--checkpoint-dir: completed slides are skipped and "
                         "the deterministic stream replayed from there; the "
                         "checkpoint's recorded backend/shard/window config "
                         "is reused unless --remesh is given")
    ap.add_argument("--remesh", action="store_true",
                    help="with --restore: re-place the checkpointed state "
                         "under THIS invocation's --backend/--shard/--grid "
                         "and visible devices (live re-meshing) instead of "
                         "the recorded config")
    ap.add_argument("--kill-after", type=int, default=None, metavar="N",
                    help="fault injection (CI recovery smoke): crash "
                         "mid-append during slide N and exit with code 3; "
                         "recover with --restore")
    ap.add_argument("--serve", action="store_true",
                    help="run the slides on a writer thread behind the async "
                         "admission front end and storm it with queries "
                         "(DESIGN.md §11)")
    ap.add_argument("--serve-queries", type=int, default=120, metavar="N",
                    help="with --serve: query storm size")
    ap.add_argument("--serve-clients", type=int, default=4, metavar="N",
                    help="with --serve: concurrent client threads")
    ap.add_argument("--serve-policy", default="block",
                    choices=["block", "shed"],
                    help="with --serve: full-queue backpressure policy")
    ap.add_argument("--queue-cap", type=int, default=256, metavar="N",
                    help="with --serve: bounded admission queue capacity")
    ap.add_argument("--stall-timeout", type=float, default=5.0, metavar="S",
                    help="with --serve: writer heartbeat deadline (0 "
                         "disables); a stalled writer is reported, readers "
                         "keep answering from the last published window")
    args = ap.parse_args(argv)
    enable_compile_cache()

    from .mesh import mesh_for_mining
    spec = stream_spec(args.dataset)
    start = 0
    if args.restore:
        if not args.checkpoint_dir:
            ap.error("--restore requires --checkpoint-dir")
        ck_cfg, done = peek_config(args.checkpoint_dir)
        if args.remesh:
            backend, shard, grid = args.backend, args.shard, args.grid
        else:
            backend, shard, grid = ck_cfg.backend, ck_cfg.shard, None
        mesh = mesh_for_mining(backend, shard, grid)
        miner, start = restore_miner(args.checkpoint_dir, mesh=mesh,
                                     backend=backend, shard=shard,
                                     keep_transactions=False)
        cfg = miner.config
        print(f"[stream] restored {args.checkpoint_dir} at slide {start} "
              f"({'re-meshed to ' if args.remesh else ''}backend={backend}, "
              f"shard={shard})")
    else:
        cfg = StreamConfig(min_sup=args.min_sup, n_blocks=args.n_blocks,
                           block_txns=args.block_txns, backend=args.backend,
                           shard=args.shard)
        backend, shard = args.backend, args.shard
        mesh = mesh_for_mining(backend, shard, args.grid)
        miner = StreamingMiner(spec.n_items, cfg, mesh=mesh,
                               keep_transactions=False)
    service = StreamQueryService(miner)
    ck = (StreamCheckpointer(args.checkpoint_dir,
                             every=args.checkpoint_every, keep=args.keep)
          if args.checkpoint_dir else None)
    eff_shard = {"tidsharded": "words", "grid": "grid"}.get(backend, shard)
    if mesh is None:
        mesh_note = ""
    elif "class" in mesh.axis_names:
        mesh_note = (f", shard=grid over a {mesh.shape['class']}x"
                     f"{mesh.shape['data']} class x data mesh")
    else:
        mesh_note = f", shard={eff_shard} over {mesh.shape['data']} device(s)"
    print(f"[stream] {spec.name}: window={cfg.n_blocks}x{cfg.block_txns} "
          f"txns, min_sup={cfg.min_sup}, backend={backend}{mesh_note}")

    if args.serve:
        return _serve_mode(args, miner, cfg, ck, start)

    try:
        for i, batch in enumerate(transaction_stream(
                args.dataset, cfg.block_txns, args.batches,
                seed=args.seed, drift_every=args.drift_every)):
            if i < start:
                continue    # replayed deterministically; already in the state
            if args.kill_after is not None and i == args.kill_after:
                def _die(name):
                    if name == "miner:mid_append":
                        raise InjectedFault(name)
                set_kill_hook(_die)
            res = service.ingest(batch)
            cls = res.stats["classes"]
            print(f"[stream] slide {i:3d}: window={res.n_txn} txns "
                  f"({res.stats['window']['filled_blocks']}/{cfg.n_blocks} blocks) "
                  f"itemsets={res.total} "
                  f"classes={cls['n_active']} (+{cls['n_entered']}/-{cls['n_exited']}) "
                  f"latency={res.stats['slide_s']*1e3:.1f}ms")
            for iset, sup in service.top_k_itemsets(args.top_k, min_len=2):
                print(f"[stream]   top {iset} support={sup} ({sup/res.n_txn:.1%})")
            if args.min_conf > 0:
                rules = service.rules(args.min_conf, k=3)
                print(f"[stream]   {len(service.rules(args.min_conf))} rules at "
                      f"conf>={args.min_conf}; best: "
                      + "; ".join(f"{a}=>{c} conf={cf:.2f}" for a, c, cf, _ in rules))
            if ck is not None:
                ck.maybe_save(miner, i + 1)
    except InjectedFault:
        if ck is not None:
            ck.wait()
        print(f"[stream] injected crash mid-append at slide "
              f"{args.kill_after}; last durable checkpoint survives — "
              f"recover with --restore")
        raise SystemExit(3)
    finally:
        clear_kill_hook()
    if ck is not None:
        ck.wait()
        print(f"[stream] checkpoints durable in {args.checkpoint_dir}")


if __name__ == "__main__":
    main()
