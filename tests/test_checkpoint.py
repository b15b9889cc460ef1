"""Distributed checkpoint: atomic write, async, elastic mesh reshard."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.training import (AsyncCheckpointer, latest_step,
                            restore_checkpoint, save_checkpoint)


def tree():
    return {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.ones((5,), jnp.int32), "d": jnp.zeros(())}}


def test_roundtrip(tmp_path):
    t = tree()
    save_checkpoint(str(tmp_path), 7, t, extra={"note": "x"})
    assert latest_step(str(tmp_path)) == 7
    restored, manifest = restore_checkpoint(str(tmp_path), 7, t)
    assert manifest["step"] == 7 and manifest["extra"]["note"] == "x"
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_and_gc(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    t = tree()
    for s in (1, 2, 3, 4):
        ck.save(s, t)
    ck.wait()
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path)
                   if d.startswith("step_"))
    assert steps == [3, 4]


def test_structure_mismatch_raises(tmp_path):
    save_checkpoint(str(tmp_path), 1, tree())
    bad = {"a": jnp.zeros((2, 2)), "b": {"c": jnp.ones((5,), jnp.int32),
                                         "d": jnp.zeros(())}}
    with pytest.raises(ValueError):
        restore_checkpoint(str(tmp_path), 1, bad)


_ELASTIC = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.training import restore_checkpoint, save_checkpoint
from repro.dist.compat import make_mesh
d = sys.argv[1]
mesh = make_mesh((2, 2), ("data", "model"))
t = {"w": jnp.arange(64.0).reshape(8, 8)}
sh = {"w": NamedSharding(mesh, P("data", "model"))}
if sys.argv[2] == "save":
    tw = jax.device_put(t["w"], sh["w"])
    save_checkpoint(d, 3, {"w": tw})
else:
    restored, _ = restore_checkpoint(d, 3, t, shardings=sh)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(t["w"]))
    assert restored["w"].sharding == sh["w"]
    print("ELASTIC_OK")
"""


def test_elastic_reshard_across_processes(tmp_path):
    """Save on a 4-device (2,2) mesh; restore in a fresh process on the same
    mesh AND on 1 device — content identical (mesh-agnostic checkpoints)."""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", _ELASTIC, str(tmp_path), "save"],
                       capture_output=True, text=True, env=env, cwd=os.getcwd())
    assert r.returncode == 0, r.stderr
    r = subprocess.run([sys.executable, "-c", _ELASTIC, str(tmp_path), "load"],
                       capture_output=True, text=True, env=env, cwd=os.getcwd())
    assert r.returncode == 0 and "ELASTIC_OK" in r.stdout, r.stderr
    # 1-device restore in this process
    restored, _ = restore_checkpoint(
        str(tmp_path), 3, {"w": jnp.zeros((8, 8))})
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(64.0).reshape(8, 8))


# ---------------------------------------------------------------------------
# crash-consistency (DESIGN.md §10): torn writes, corrupt steps, GC races
# ---------------------------------------------------------------------------

from faultinject import crash_at  # noqa: E402
from repro.faults import InjectedFault  # noqa: E402
from repro.training import (load_checkpoint, restore_latest,  # noqa: E402
                            valid_steps)
from repro.training.checkpoint import AsyncCheckpointer as _AC  # noqa: E402


def test_mid_write_crash_preserves_previous_step(tmp_path):
    """A writer killed after the leaves but before the manifest leaves no
    visible step — restore falls back to the previous one."""
    save_checkpoint(str(tmp_path), 1, tree(), extra={"v": 1})
    with crash_at("checkpoint:mid_write"), pytest.raises(InjectedFault):
        save_checkpoint(str(tmp_path), 2, tree(), extra={"v": 2})
    assert valid_steps(str(tmp_path)) == [1]
    _, manifest, step = restore_latest(str(tmp_path))
    assert step == 1 and manifest["extra"]["v"] == 1


def test_overwrite_same_step_is_crash_safe(tmp_path):
    """Re-saving an existing step must never destroy the only copy: a kill
    just before the rename leaves the old content fully restorable
    (regression: the old rmtree-then-replace deleted it first)."""
    save_checkpoint(str(tmp_path), 5, {"a": jnp.arange(4.0)}, extra={"v": "old"})
    with crash_at("checkpoint:pre_replace"), pytest.raises(InjectedFault):
        save_checkpoint(str(tmp_path), 5, {"a": jnp.zeros(4)}, extra={"v": "new"})
    flat, manifest = load_checkpoint(str(tmp_path), 5)
    assert manifest["extra"]["v"] == "old"
    np.testing.assert_array_equal(flat["a"], np.arange(4.0))
    # a successful re-save lands the new content and leaves no .old debris
    save_checkpoint(str(tmp_path), 5, {"a": jnp.zeros(4)}, extra={"v": "new"})
    flat, manifest = load_checkpoint(str(tmp_path), 5)
    assert manifest["extra"]["v"] == "new"
    np.testing.assert_array_equal(flat["a"], np.zeros(4))
    assert not [d for d in os.listdir(tmp_path) if d.endswith(".old")]


def test_restore_latest_falls_back_past_corrupt_steps(tmp_path):
    """A truncated leaf or a missing manifest in the newest step must not
    stop restore (regression: it crashed instead of falling back)."""
    save_checkpoint(str(tmp_path), 1, tree(), extra={"v": 1})
    save_checkpoint(str(tmp_path), 2, tree(), extra={"v": 2})
    save_checkpoint(str(tmp_path), 3, tree(), extra={"v": 3})
    # step 3: manifest intact but a leaf truncated mid-write
    leaf = os.path.join(tmp_path, "step_00000003", "leaf_00000.npy")
    with open(leaf, "r+b") as f:
        f.truncate(8)
    # step 2: manifest gone entirely
    os.remove(os.path.join(tmp_path, "step_00000002", "manifest.json"))
    assert valid_steps(str(tmp_path)) == [1, 3]    # 3 still *looks* valid
    _, manifest, step = restore_latest(str(tmp_path))
    assert step == 1 and manifest["extra"]["v"] == 1
    # with like= the same fallback applies
    restored, manifest, step = restore_latest(str(tmp_path), like=tree())
    assert step == 1
    # nothing restorable at all -> FileNotFoundError, not a crash
    with open(os.path.join(tmp_path, "step_00000001", "leaf_00000.npy"),
              "r+b") as f:
        f.truncate(8)
    with pytest.raises(FileNotFoundError):
        restore_latest(str(tmp_path))


def test_gc_spares_newest_and_just_written(tmp_path):
    """GC keeps the newest ``keep`` steps and never collects a step at or
    above the save that triggered it, even if an older save's GC runs late
    (regression: a racing collector could eat the step just written)."""
    for s in (1, 2, 3):
        save_checkpoint(str(tmp_path), s, tree())
    ck = _AC(str(tmp_path), keep=1)
    ck._gc(just_wrote=2)             # a stale collector for the step-2 save
    assert valid_steps(str(tmp_path)) == [2, 3]
    ck._gc(just_wrote=3)
    assert valid_steps(str(tmp_path)) == [3]
    # no half-deleted ".gc" victims left in the step namespace
    assert not [d for d in os.listdir(tmp_path) if d.endswith(".gc")]


def test_async_checkpointer_surfaces_writer_error_on_wait(tmp_path):
    """A fault on the background writer thread is re-raised by wait(), once
    — deterministic surfacing, no silent checkpoint loss."""
    ck = AsyncCheckpointer(str(tmp_path), keep=3)
    ck.save(1, tree())
    ck.wait()
    with crash_at("checkpoint:mid_write"):
        ck.save(2, tree())
        with pytest.raises(InjectedFault):
            ck.wait()
    ck.wait()                        # error was consumed; wait is reusable
    assert valid_steps(str(tmp_path)) == [1]
    ck.save(3, tree())               # the checkpointer survives the fault
    ck.wait()
    assert valid_steps(str(tmp_path)) == [1, 3]


# an engine snapshot as the tree before the block_w / compact / autotune
# knobs were retired wrote it: those keys are present and must be ignored
PARENT_ENGINE_EXTRA = {
    "pallas": {"mesh": None, "block_w": None, "compact": True,
               "autotune": False},
    "tidsharded": {"mesh": {"axes": ["data"], "shape": [4]},
                   "block_w": 256, "compact": False, "autotune": False},
    "grid": {"mesh": {"axes": ["class", "data"], "shape": [2, 2]},
             "block_w": None, "compact": True, "autotune": True},
}


@pytest.mark.parametrize("backend", sorted(PARENT_ENGINE_EXTRA))
def test_engine_state_restores_parent_tree(backend, host_devices):
    from repro.core import engine as eng
    from repro.dist.compat import make_mesh
    extra = {"backend": backend, "inner": "pallas", "bucket_min": 8,
             "compact_min": 8, "interpret": None,
             "n_intersections": 300, "n_padded": 84,
             **PARENT_ENGINE_EXTRA[backend]}
    tree = {"level_padding": np.asarray([[100, 128], [200, 256]], np.int64)}
    mesh = None
    if backend == "tidsharded":
        mesh = make_mesh((4,), ("data",))
    elif backend == "grid":
        mesh = make_mesh((2, 2), ("class", "data"),
                         devices=jax.devices()[:4])
        tree["device_pair_counts"] = np.asarray([[60, 40], [120, 80]],
                                                np.int64)
    e = eng.engine_from_state(eng.EngineState.from_tree(tree, extra), mesh)
    assert e.name == backend and e.buffers.floor == 8 and e.compact_min == 8
    st = e.stats()
    assert (st["n_intersections"], st["n_padded"]) == (300, 84)
    assert [(lv["pairs"], lv["padded_to"])
            for lv in st["pair_padding"]["per_level"]] == [(100, 128),
                                                            (200, 256)]
    if backend == "grid":
        assert st["device_balance"]["pairs_per_device"] == [180, 120]
    # the rebuilt engine expands, and its own snapshot drops the old keys
    rng = np.random.default_rng(0)
    bm = rng.integers(0, 2 ** 32, (6, 8), dtype=np.uint32)
    left, right = np.asarray([0, 1, 2], np.int32), np.asarray([3, 4, 5],
                                                               np.int32)
    res = e.expand(jnp.asarray(bm), left, right,
                   np.full(3, 256, np.int32), mode=eng.MODE_TIDSET,
                   min_sup=0)
    want = [int(np.unpackbits((bm[a] & bm[b]).view(np.uint8)).sum())
            for a, b in zip(left, right)]
    assert res.supports.tolist() == want
    _, new_extra = e.snapshot_state().to_tree()
    assert not {"block_w", "compact", "autotune"} & set(new_extra)
