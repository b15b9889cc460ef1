"""The mine path's phase spans: ``phase_s`` and the profiler trace agree.

Every timed phase of ``mine()`` and of a ``StreamingMiner`` slide goes
through ``repro.spans.span``, which both adds to ``phase_s`` and opens a
``jax.profiler.TraceAnnotation``.  These tests read a real ``.xplane.pb``
back (``jax.profiler.ProfileData``) and hold the two to each other, and
check the ``counts`` a mine reports against independent counts.
"""
import glob
import os

import jax
import pytest

from repro.core import EclatConfig, mine
from repro.core.triangular import cooc_blocks
from repro.data import generate
from repro.spans import span
from repro.streaming import StreamConfig, StreamingMiner

TOP_LEVEL = ("vertical", "plan", "tri_matrix", "bottom_up")
# nested phase -> the top-level phases it may lie in
NESTED = {"cooc": ("tri_matrix",), "level": ("bottom_up",),
          "expand_wait": ("tri_matrix", "bottom_up")}


@pytest.fixture(scope="module")
def quest():
    """A small Quest market-basket database: several levels, a mine of a
    few hundred milliseconds on the CPU."""
    txns, spec = generate("T10I4D100K", scale=0.05, seed=1)
    return txns, spec.n_items


def _config(backend="pallas", **kw):
    return EclatConfig(min_sup=0.005, backend=backend, **kw)


def _traced(log_dir, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        out = fn()
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "mine" or e.name.startswith(("mine.", "slide.")):
                    spans.append((e.name, int(e.start_ns),
                                  int(e.start_ns + e.duration_ns),
                                  dict(e.stats)))
    return out, spans


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def traced_mine(quest, tmp_path_factory):
    txns, n_items = quest
    mine(txns, n_items, _config())            # compiles outside the trace

    def run():
        res = mine(txns, n_items, _config())
        res.support_map()
        return res
    return _traced(tmp_path_factory.mktemp("trace"), run)


def test_every_phase_has_its_span_and_the_same_duration(traced_mine):
    res, spans = traced_mine
    phase_s = res.stats["phase_s"]
    assert set(TOP_LEVEL) | {"cooc", "expand_wait",
                             "support_map"} <= set(phase_s)
    for key, seconds in phase_s.items():
        got = _named(spans, f"mine.{key}")
        assert got, key
        traced = sum(e - s for _, s, e, _ in got) / 1e9
        assert abs(traced - seconds) <= max(2e-3, 0.05 * seconds), key
    outer, = _named(spans, "mine")
    assert abs((outer[2] - outer[1]) / 1e9 - res.stats["total_s"]) <= max(
        2e-3, 0.05 * res.stats["total_s"])


def test_spans_nest_inside_mine_and_top_level_ones_do_not_overlap(
        quest, traced_mine):
    txns, n_items = quest
    res, spans = traced_mine
    outer, = _named(spans, "mine")
    assert outer[3] == {"n_txn": len(txns), "n_items": n_items}
    inner = [s for s in spans if s[0].startswith("mine.")
             and s[0] != "mine.support_map"]
    assert inner and all(_inside(s, outer) for s in inner)
    top = sorted((s for s in inner if s[0][5:] in TOP_LEVEL),
                 key=lambda s: s[1])
    assert [s[0][5:] for s in top] == list(TOP_LEVEL)
    for a, b in zip(top, top[1:]):
        assert a[2] <= b[1], (a[0], b[0])
    for s in inner:
        parents = NESTED.get(s[0][5:])
        if parents:
            assert any(_inside(s, t) for t in top
                       if t[0][5:] in parents), s[0]
    answer, = _named(spans, "mine.support_map")
    assert answer[1] >= outer[2]


def test_level_spans_carry_k_and_pairs(traced_mine):
    res, spans = traced_mine
    levels = sorted(_named(spans, "mine.level"), key=lambda s: s[1])
    assert [s[3]["k"] for s in levels] == list(range(3, 3 + len(levels)))
    per_level = res.stats["pair_padding"]["per_level"]
    # the first expansion is level 2's, inside tri_matrix
    assert ([s[3]["pairs"] for s in levels]
            == [p["pairs"] for p in per_level[1:]])
    assert "level" not in res.stats["phase_s"]


def test_phases_cover_the_mine_without_a_session(quest):
    txns, n_items = quest
    mine(txns, n_items, _config())
    res = mine(txns, n_items, _config())
    total = res.stats["total_s"]
    covered = sum(res.stats["phase_s"][k] for k in TOP_LEVEL)
    assert covered <= total
    if total >= 0.05:
        assert covered >= 0.8 * total
    assert "support_map" not in res.stats["phase_s"]
    res.support_map()
    first = res.stats["phase_s"]["support_map"]
    res.support_map()
    assert res.stats["phase_s"]["support_map"] > first > 0


@pytest.mark.parametrize("variant", ["v1", "v4"])
def test_incidences_count_distinct_items_per_transaction(quest, variant):
    txns, n_items = quest
    res = mine(txns, n_items, _config(variant=variant))
    assert res.stats["counts"]["incidences"] == sum(len(set(t)) for t in txns)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_host_reads_are_cooc_blocks_plus_expand_reads(quest, monkeypatch,
                                                      backend):
    txns, n_items = quest
    reads = []
    device_get = jax.device_get

    def counting(x):
        reads.append(1)
        return device_get(x)
    monkeypatch.setattr(jax, "device_get", counting)
    res = mine(txns, n_items, _config(backend))
    monkeypatch.undo()
    # single-device engines make one blocking read per kernel-sized call,
    # and record one padding entry per call
    expand_reads = len(res.stats["pair_padding"]["per_level"])
    cooc_reads = len(reads) - expand_reads
    n1 = res.stats["n_freq_items"]
    # the co-occurrence pass reads once, whatever n1
    assert n1 > 64 and cooc_reads == cooc_blocks(n1) == 1
    assert res.stats["counts"]["host_reads"] == cooc_reads + expand_reads
    ph = res.stats["phase_s"]
    assert 0 < ph["expand_wait"] <= ph["tri_matrix"] + ph["bottom_up"]
    assert ph["cooc"] <= ph["tri_matrix"]


def test_a_single_frequent_item_mine_reports_no_expand_wait():
    txns = [[0, 1]] + [[0]] * 9
    res = mine(txns, 2, EclatConfig(min_sup=5))
    assert res.total == 1
    assert set(res.stats["phase_s"]) == {"vertical", "plan"}
    assert "host_reads" not in res.stats["counts"]


def test_streaming_slides_report_push_level2_bottom_up_and_emit_spans(
        quest, tmp_path):
    txns, n_items = quest
    miner = StreamingMiner(n_items, StreamConfig(min_sup=0.01, n_blocks=2,
                                                 block_txns=512))
    miner.advance(txns[:512])                     # compiles outside the trace

    def run():
        return [miner.advance(txns[i:i + 512]) for i in (512, 1024)]
    results, spans = _traced(tmp_path, run)
    for res in results:
        st = res.stats
        assert st["push_s"] > 0
        assert {"level2", "bottom_up", "expand_wait"} <= set(st["phase_s"])
        assert st["phase_s"]["expand_wait"] <= (st["phase_s"]["level2"]
                                                + st["phase_s"]["bottom_up"])
        assert st["counts"]["host_reads"] >= 1
    names = {s[0] for s in spans}
    assert {"slide.push", "slide.mine_window", "slide.level2",
            "slide.bottom_up", "slide.expand_wait"} <= names
    assert not any(n.startswith("mine") for n in names)
    pushes = _named(spans, "slide.push")
    assert len(pushes) == 2 and all(s[3] == {"n_txn": 512} for s in pushes)
    # per-slide deltas: the two slides' waits add up to the traced waits
    waits = sum(e - s for _, s, e, _ in _named(spans, "slide.expand_wait"))
    want = sum(r.stats["phase_s"]["expand_wait"] for r in results)
    assert abs(waits / 1e9 - want) <= max(2e-3, 0.05 * want)


SLIDE_PHASES = {"push", "ring", "cooc_delta", "level2", "bottom_up",
                "support_map"}


def test_a_slide_times_its_push_parts_and_its_answer(quest, tmp_path):
    txns, n_items = quest
    miner = StreamingMiner(n_items, StreamConfig(min_sup=0.01, n_blocks=2,
                                                 block_txns=512),
                           keep_transactions=False)
    miner.advance(txns[:512]).support_map()       # compiles outside the trace

    def run():
        out = []
        for i in (512, 1024, 1536):
            res = miner.advance(txns[i:i + 512])
            res.support_map()
            out.append(res)
        return out
    results, spans = _traced(tmp_path, run)
    for res in results:
        ph = res.stats["phase_s"]
        assert SLIDE_PHASES <= set(ph)
        assert 0 < ph["ring"] and 0 < ph["cooc_delta"]
        assert ph["ring"] + ph["cooc_delta"] <= ph["push"]
        assert ph["push"] == res.stats["push_s"]
    pushes = _named(spans, "slide.push")
    rings = _named(spans, "slide.ring")
    deltas = _named(spans, "slide.cooc_delta")
    assert len(pushes) == len(rings) == len(deltas) == 3
    for push, ring, delta in zip(pushes, rings, deltas):
        assert _inside(ring, push) and _inside(delta, push)
        assert ring[2] <= delta[1]
    maps = _named(spans, "slide.support_map")
    windows = _named(spans, "slide.mine_window")
    assert len(maps) == 3
    # the map is built after its window is mined, outside the re-mine
    assert all(w[2] <= m[1] for w, m in zip(windows, maps))
    for res, m in zip(results, maps):
        want = res.stats["phase_s"]["support_map"]
        assert abs((m[2] - m[1]) / 1e9 - want) <= max(2e-3, 0.05 * want)


def test_a_slide_counts_both_count_passes_among_its_host_reads(quest,
                                                                monkeypatch):
    txns, n_items = quest
    miner = StreamingMiner(n_items, StreamConfig(min_sup=0.01, n_blocks=1,
                                                 block_txns=512))
    first = miner.advance(txns[:512])             # nothing evicted yet
    reads = []
    device_get = jax.device_get

    def counting(x):
        reads.append(1)
        return device_get(x)
    monkeypatch.setattr(jax, "device_get", counting)
    res = miner.advance(txns[512:1024])           # evicts the first block
    monkeypatch.undo()
    assert res.stats["n_evicted"] == 512
    engine_reads = len(res.stats["pair_padding"]["per_level"])
    assert engine_reads >= 1
    assert len(reads) == 2 * cooc_blocks(n_items) + engine_reads
    assert res.stats["counts"]["host_reads"] == len(reads)
    first_engine = len(first.stats["pair_padding"]["per_level"])
    assert (first.stats["counts"]["host_reads"]
            == cooc_blocks(n_items) + first_engine)


def test_span_accumulates_repeats_and_needs_no_session():
    into = {}
    for _ in range(3):
        with span("x", into, prefix="mine.", k=1):
            pass
    assert set(into) == {"x"} and into["x"] > 0
    with span("y", prefix="mine."):
        pass


def test_launch_mine_prints_phases_and_writes_a_profile(tmp_path, capsys):
    from repro.launch import mine as mine_cli
    mine_cli.main(["--dataset", "chess", "--scale", "0.05", "--min-sup",
                   "0.85", "--min-conf", "0.9", "--profile", str(tmp_path)])
    out = capsys.readouterr().out.splitlines()
    assert out[-1].startswith("[mine] phases: vertical=")
    assert "support_map=" in out[-1] and "incidences=" in out[-1]
    assert glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)
