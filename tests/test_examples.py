"""The runnable examples must stay runnable (fast reduced invocations)."""
import os
import subprocess
import sys

import pytest

ENV = dict(os.environ, PYTHONPATH="src")


def run(args, timeout=900):
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, env=ENV, cwd=os.getcwd(), timeout=timeout)


def test_quickstart():
    r = run(["examples/quickstart.py", "--dataset", "mushroom",
             "--min-sup", "0.4", "--scale", "0.1"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "frequent itemsets" in r.stdout


def test_quickstart_rules_output():
    """ARM step 2 through the quickstart surface: rules printed, conf bound."""
    r = run(["examples/quickstart.py", "--dataset", "chess",
             "--min-sup", "0.85", "--scale", "0.1", "--rules"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "association rules at conf>=0.9" in r.stdout
    n_rules = int(r.stdout.split(" association rules")[0].rsplit("\n", 1)[-1])
    assert n_rules > 0
    # every printed rule line carries a confidence within [0.9, 1]
    printed = [l for l in r.stdout.splitlines() if "conf=" in l]
    assert printed, r.stdout
    for line in printed:
        conf = float(line.split("conf=")[1].split()[0])
        assert 0.9 <= conf <= 1.0


def test_mine_driver():
    r = run(["-m", "repro.launch.mine", "--dataset", "chess",
             "--min-sup", "0.85", "--scale", "0.1", "--variant", "v6"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "[mine]" in r.stdout


def test_mine_driver_min_conf_rules():
    """generate_rules through the launch.mine --min-conf CLI path."""
    r = run(["-m", "repro.launch.mine", "--dataset", "chess",
             "--min-sup", "0.85", "--scale", "0.1", "--min-conf", "0.8"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "rules at conf>=0.8" in r.stdout
    n_rules = int(r.stdout.split("[mine] ")[2].split(" rules")[0])
    assert n_rules > 0


def test_stream_driver():
    r = run(["-m", "repro.launch.stream", "--batches", "4", "--n-blocks", "2",
             "--block-txns", "128", "--min-sup", "0.02", "--min-conf", "0.8",
             "--backend", "jnp"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "[stream] slide   3" in r.stdout
    assert "rules at conf>=0.8" in r.stdout


def test_stream_example_parity():
    r = run(["examples/stream_topk.py", "--batches", "4", "--n-blocks", "2",
             "--block-txns", "128"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "parity: windowed == batch mine()" in r.stdout


def test_mine_distributed():
    r = run(["examples/mine_distributed.py", "--devices", "2",
             "--min-sup", "0.35"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "recovered" in r.stdout


@pytest.mark.parametrize("env_set", [False, True])
def test_drivers_compile_cache_location(env_set, tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is the only compile cache (the
    helper sets nothing); unset, the drivers use <repo>/.jax_cache."""
    import jax
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        if env_set:
            monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
            assert compile_cache.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv(compile_cache.ENV, raising=False)
            want = os.path.join(repo, ".jax_cache")
            assert compile_cache.enable_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
