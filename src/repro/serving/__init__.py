"""repro.serving — the unified serving path (DESIGN.md §11).

One request lifecycle for both workloads: admission -> greedy-LPT pack ->
answer -> version-stamped result, instrumented end to end.

* ``ServingFrontend`` (``admission``) — async batched admission over the
  streaming miner: bounded queue with shed-or-block backpressure,
  deadline/size drain triggers, continuous greedy-LPT packing, answers
  bit-identical to the synchronous path at the same ``window_version``.
* ``StreamQueryService`` (``stream_query``) — the thin synchronous adapter
  over the same snapshot/cache/answer kernels.
* ``ServingEngine`` (``engine``) — KV-cached batched LM inference on the
  shared pack + metrics scaffolding.
* ``VersionedCache`` / ``WindowSnapshot`` / ``ServingMetrics`` — the shared
  version-keyed caching, immutable snapshot handoff, and p50/p99/QPS
  instrumentation layers.
* ``loadgen`` — deterministic query storms + the answer-checksum
  verification oracle (the ``--serve`` drivers;
  ``tests/test_serving_frontend.py`` checks it).
"""
from .admission import AdmissionConfig, QueryShed, ServingFrontend, Ticket
from .cache import VersionedCache
from .engine import Request, ServingEngine, pack_requests
from .loadgen import answer_checksum, query_mix, run_storm, verify_storm
from .metrics import ServingMetrics
from .snapshot import (WindowSnapshot, answer_query, answer_rules,
                       answer_support, answer_topk)
from .stream_query import (ItemsetQuery, StreamQueryService, pack_queries,
                           query_work)

__all__ = ["Request", "ServingEngine", "pack_requests",
           "ItemsetQuery", "StreamQueryService", "pack_queries", "query_work",
           "AdmissionConfig", "QueryShed", "ServingFrontend", "Ticket",
           "VersionedCache", "WindowSnapshot", "ServingMetrics",
           "answer_query", "answer_rules", "answer_support", "answer_topk",
           "answer_checksum", "query_mix", "run_storm", "verify_storm"]
