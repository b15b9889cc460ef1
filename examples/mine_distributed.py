"""Distributed mining with fault tolerance: shard the equivalence classes
over a device mesh, kill a partition, recover it from lineage.

    PYTHONPATH=src python examples/mine_distributed.py [--devices 4]

(The script re-execs itself on the CPU with XLA_FLAGS so --devices takes
effect.)
"""
import argparse
import os
import sys

sys.path.insert(0, "src")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--dataset", default="mushroom")
    ap.add_argument("--min-sup", type=float, default=0.3)
    args = ap.parse_args()

    if os.environ.get("_MINE_CHILD") != "1":
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")
        # forced host devices: the re-exec runs on the CPU, never a chip
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["_MINE_CHILD"] = "1"
        os.execv(sys.executable, [sys.executable] + sys.argv)

    import numpy as np
    from repro.core import (EclatConfig, assign_partitions, build_vertical,
                            mine, recover_partition)
    from repro.data import generate
    from repro.dist.compat import make_mesh

    mesh = make_mesh((args.devices,), ("data",))
    txns, spec = generate(args.dataset, scale=0.2, seed=1)
    cfg = EclatConfig(min_sup=args.min_sup, variant="v5",
                      p=2 * args.devices, backend="sharded")
    res = mine(txns, spec.n_items, cfg, mesh=mesh)
    print(f"mined {res.total} itemsets on {args.devices} devices; "
          f"device balance: {res.stats['device_balance']}")

    # --- simulate losing a partition and recover it from lineage ----------
    abs_ms = cfg.resolve_min_sup(len(txns))
    db = build_vertical(txns, spec.n_items, abs_ms)
    table = assign_partitions(db.n_items - 1, "reverse_hash", 2 * args.devices)
    lost = 3
    recovered = recover_partition(db, table, pid=lost, abs_min_sup=abs_ms)
    # verify against the full result
    rank_of = {int(it): r for r, it in enumerate(db.items)}
    expect = {k: v for k, v in res.support_map().items()
              if len(k) >= 2 and table[min(rank_of[i] for i in k)] == lost}
    assert recovered == expect
    print(f"partition {lost} lost -> {len(recovered)} itemsets recovered "
          f"bit-exactly from lineage (vertical DB + partition table)")


if __name__ == "__main__":
    main()
