"""Mining driver: the paper's job as a launchable (the Spark-submit analogue).

    PYTHONPATH=src python -m repro.launch.mine --dataset chess --min-sup 0.8 \
        --variant v5 --checkpoint-dir /tmp/mine_ckpt

Workload modes (DESIGN.md §9): ``--mode closed|maximal`` post-filters the
mined lattice, ``--top-k K`` replaces the threshold with the adaptive
min_sup ladder, ``--fimi FILE.dat`` mines a FIMI-format file (retail.dat
et al.) instead of a synthetic paper dataset.
"""
from __future__ import annotations

import argparse
import contextlib
import os

import jax

from ..core import EclatConfig, generate_rules, mine, resume_mine, top_k_mine
from ..data import PAPER_DATASETS, generate, load_fimi
from .compile_cache import enable_compile_cache


def main(argv=None):
    """Run one mining job; returns its result (an ``EclatResult``, or the
    ``top_k_mine`` result with ``--top-k``) for callers that check it."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="chess", choices=list(PAPER_DATASETS))
    ap.add_argument("--fimi", default=None, metavar="FILE.dat",
                    help="mine a FIMI-format transaction file instead of "
                         "--dataset (one txn per line, whitespace-separated "
                         "integer item ids)")
    ap.add_argument("--scale", type=float, default=0.25)
    ap.add_argument("--min-sup", type=float, default=0.8)
    ap.add_argument("--mode", default="all",
                    choices=["all", "closed", "maximal"],
                    help="workload mode: all frequent itemsets, or the "
                         "closed/maximal subset (lineage post-filter)")
    ap.add_argument("--top-k", type=int, default=None, metavar="K",
                    help="mine the K highest-support itemsets via the "
                         "adaptive min_sup ladder (--min-sup is ignored)")
    ap.add_argument("--variant", default="v4",
                    choices=["v1", "v2", "v3", "v4", "v5", "v6"])
    ap.add_argument("--p", type=int, default=10)
    ap.add_argument("--backend", default="pallas",
                    choices=["jnp", "pallas", "sharded", "tidsharded", "grid"],
                    help="engine backend (DESIGN.md §6)")
    ap.add_argument("--shard", default="pairs",
                    choices=["pairs", "words", "grid"],
                    help="mesh split under a device mesh: candidate pairs, "
                         "the frontier's word axis, or both on a 2D grid "
                         "(DESIGN.md §7-8)")
    ap.add_argument("--grid", default=None, metavar="RxC",
                    help="class x data mesh shape for --shard grid, e.g. 2x2 "
                         "(default: auto-factorize the visible devices)")
    ap.add_argument("--diffsets", action="store_true",
                    help="dEclat diffsets (variant v6 only)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--restore", action="store_true",
                    help="resume the deepest mining checkpoint in "
                         "--checkpoint-dir instead of mining from scratch; "
                         "--backend/--shard/--grid select the *restoring* "
                         "mesh, which may differ from the original run's "
                         "(live re-meshing, DESIGN.md §10)")
    ap.add_argument("--min-conf", type=float, default=0.0,
                    help="if >0, also generate association rules")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="write a profiler trace of the mine to DIR: the "
                         "mine.* phase spans beside the device ops, for "
                         "TensorBoard or Perfetto")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.fimi:
        txns, n_items = load_fimi(args.fimi)
        name = os.path.basename(args.fimi)
        tri_matrix = None                     # auto (item-id range heuristic)
        scale_note = ""
    else:
        txns, spec = generate(args.dataset, scale=args.scale, seed=1)
        name, n_items = spec.name, spec.n_items
        tri_matrix = spec.tri_matrix or None
        scale_note = f" x{args.scale}"
    cfg = EclatConfig(min_sup=args.min_sup, variant=args.variant, p=args.p,
                      tri_matrix=tri_matrix,
                      use_diffsets=args.diffsets,
                      backend=args.backend, shard=args.shard,
                      mode=args.mode,
                      checkpoint_dir=args.checkpoint_dir,
                      checkpoint_every_level=args.checkpoint_dir is not None)
    from .mesh import mesh_for_mining
    mesh = mesh_for_mining(args.backend, args.shard, args.grid)

    if args.restore and not args.checkpoint_dir:
        ap.error("--restore requires --checkpoint-dir")
    if args.profile:
        profile = jax.profiler.trace(args.profile)
    else:
        profile = contextlib.nullcontext()
    with profile:
        return _run(args, cfg, mesh, txns, n_items, name + scale_note)


def _run(args, cfg, mesh, txns, n_items, name):
    if args.restore:
        res = resume_mine(cfg, mesh=mesh)
        print(f"[mine] resumed {res.stats['resumed_from']} at level "
              f"{res.stats['resume_k']} ({res.stats['backend']}): "
              f"{res.total} itemsets in {res.stats['total_s']:.2f}s "
              f"levels={res.counts}")
        if args.min_conf > 0:
            rules = generate_rules(res.support_map(), args.min_conf)
            print(f"[mine] {len(rules)} rules at conf>={args.min_conf}")
        _print_phases(res.stats)
        return res

    if args.top_k is not None:
        tk = top_k_mine(txns, n_items, args.top_k, config=cfg, mesh=mesh)
        mined_s = sum(r["total_s"] for r in tk.ladder)
        print(f"[mine] {name} top-{args.top_k} "
              f"({len(tk.itemsets)} returned) in {mined_s:.2f}s: ladder "
              f"{[r['abs_min_sup'] for r in tk.ladder]} -> "
              f"abs_min_sup={tk.abs_min_sup}")
        for itemset, sup in tk.itemsets[: min(args.top_k, 10)]:
            print(f"[mine]   {itemset} sup={sup}")
        return tk

    res = mine(txns, n_items, cfg, mesh=mesh)
    grid_note = (f" grid={mesh.shape['class']}x{mesh.shape['data']}"
                 if mesh is not None and "class" in mesh.axis_names else "")
    mode_note = (f" {args.mode}={res.stats['mode_itemsets']}"
                 if args.mode != "all" else "")
    print(f"[mine] {name} min_sup={args.min_sup} "
          f"{args.variant}: {res.total} itemsets in "
          f"{res.stats['total_s']:.2f}s "
          f"levels={res.counts}{grid_note}{mode_note}")
    if args.min_conf > 0:
        rules = generate_rules(res.support_map(), args.min_conf)
        print(f"[mine] {len(rules)} rules at conf>={args.min_conf}")
    _print_phases(res.stats)
    return res


def _print_phases(stats: dict) -> None:
    """One line: seconds per phase (``phase_s``) and the ``counts``."""
    phases = " ".join(f"{k}={v:.4f}s" for k, v in stats["phase_s"].items())
    counts = " ".join(f"{k}={v}" for k, v in stats.get("counts", {}).items())
    print(f"[mine] phases: {phases} | counts: {counts}")


if __name__ == "__main__":
    main()
