"""repro.core — RDD-Eclat (the paper's contribution) on JAX.

Public surface:
  mine / EclatConfig / EclatResult     level-wise RDD-Eclat, variants v1..v6
  CSR                                   array input: (offsets, items)
  make_engine / available_backends      pluggable device-executor backends
  apriori_mine                          YAFIM-style Spark-Apriori baseline
  bruteforce_fim                        exact oracle for tests
  closed/maximal_itemsets, top_k_mine   workload modes (lineage post-filters)
  build_vertical / filter_transactions  vertical DB construction
  assign_partitions / partition_stats   equivalence-class partitioners
  recover_partition                     lineage-based partition recovery
  generate_rules                        ARM step 2
"""
from .apriori import AprioriResult, apriori_mine
from .bitmap import CSR
from .eclat import VARIANTS, EclatConfig, EclatResult, mine, resume_mine
from .engine import (Engine, EngineState, LevelResult, available_backends,
                     engine_from_state, make_engine, register_backend)
from .itemsets import ItemsetStore, LevelRecord, generate_rules
from .lineage import (latest_mining_checkpoint, load_mining_checkpoint,
                      recover_partition, save_mining_checkpoint)
from .oracle import bruteforce_fim
from .postfilter import (WORKLOAD_MODES, TopKResult, closed_itemsets,
                         filter_mode, frequent_from_closed, maximal_itemsets,
                         top_k_mine)
from .partitioners import (
    PARTITIONERS,
    assign_partitions,
    default_partitioner,
    greedy_partitioner,
    hash_partitioner,
    pack_items,
    partition_stats,
    reverse_hash_partitioner,
)
from .vertical import VerticalDB, build_vertical, filter_transactions

__all__ = [
    "AprioriResult", "apriori_mine", "CSR",
    "VARIANTS", "EclatConfig", "EclatResult", "mine", "resume_mine",
    "Engine", "EngineState", "LevelResult", "available_backends",
    "engine_from_state", "make_engine", "register_backend",
    "ItemsetStore", "LevelRecord", "generate_rules",
    "latest_mining_checkpoint", "load_mining_checkpoint",
    "recover_partition", "save_mining_checkpoint",
    "bruteforce_fim",
    "WORKLOAD_MODES", "TopKResult", "closed_itemsets", "filter_mode",
    "frequent_from_closed", "maximal_itemsets", "top_k_mine",
    "PARTITIONERS", "assign_partitions", "default_partitioner",
    "greedy_partitioner", "hash_partitioner", "pack_items", "partition_stats",
    "reverse_hash_partitioner",
    "VerticalDB", "build_vertical", "filter_transactions",
]
