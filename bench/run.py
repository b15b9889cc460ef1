#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run.py --workload mine.T10I4D100K --seed 7 --seconds 30 --trace 0

The cell, its configuration (``bench/configs/<config>.json``) and its
traffic mix (``bench/traffic/<traffic>.json``) are found by the names in
``BENCHMARK.json``; the traffic file names the driver
(``bench/drivers/<driver>.py``) that sets the cell up and runs one unit of
work, and each per-layer metric is read by ``bench/metrics/<metric>.py``.
A driver also states the chip counts it runs on (``CHIPS``), the program
entry its window calls (``ENTRY``), and the control and faults that can
take that entry's place (``CONTROL``, ``FAULTS``; ``bench/control.py``).

A run builds its inputs from ``--seed``, warms every program the window
runs (set-up, reported as ``setup_s``), then runs units back to back for
``--seconds`` and finishes the unit in progress.  After the window it reads
the device's peak memory, drops the program's state and compares the
answers kept from the window with the plain reference.  With ``--trace 0``
the result holds the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.

Standard error ends with each compared number beside its limit; the last
line of standard output is one JSON object.  With no TPU, fewer chips
than the cell asks for, an unknown device kind, or no program beside the
benchmark, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the answers compared, each with the limit it must not pass
LIMITS = {"wrong_itemsets": 0, "raised": 0}


class SetupError(RuntimeError):
    """The run cannot start here: no chip, too few, or no program."""


def load_json(*parts) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(configs[cell["config"]]["file"])
    traffic = load_json("bench", "traffic", f"{cell['traffic']}.json")
    return cell, config, traffic


def peaks_for(kind: str) -> dict:
    """The published peaks of ``kind``; a device not in the table is an
    error, never a default."""
    table = load_json("bench", "peaks.json")["devices"]
    if kind not in table:
        raise SetupError(f"device kind {kind!r} is not in bench/peaks.json "
                         f"(known: {sorted(table)})")
    return table[kind]


def check_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SetupError(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """The program's persistent-cache rule (``JAX_COMPILATION_CACHE_DIR``
    if set, else ``.jax_cache`` inside the checkout), with small programs
    cached too, so that only the first run of a cell in a checkout
    compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def load_metric(name: str):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    mod_name = "bench.metrics." + name.replace(".", "_")
    importlib.import_module("bench.metrics")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def per_layer_metrics(bench: dict, cell: dict) -> list:
    """The per-layer metrics reported in ``cell``: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    reported = {m["name"] for m in bench["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])}
    out = []
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if (cell["name"] in cells) if cells is not None else m["moves"] in reported:
            out.append(m)
    return out


class Compiles:
    """Counts XLA compilations, so that one inside the window shows."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1


def run_window(cell, seconds: float, annotate=None):
    """Units back to back until ``seconds`` have passed; the unit in
    progress is finished.  Returns the records, the raised count and the
    window's length in seconds."""
    records, raised, i = [], 0, 0
    t0 = time.perf_counter()
    while True:
        try:
            if annotate is None:
                records.append(cell.step(i))
            else:
                with annotate():
                    records.append(cell.step(i))
        except Exception as e:  # noqa: BLE001 -- a unit that raises fails
            raised += 1
            print(f"[bench] unit {i} raised {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
        i += 1
        if time.perf_counter() - t0 >= seconds:
            break
    return records, raised, time.perf_counter() - t0


def run_cell(bench: dict, name: str, seed: int, seconds: float, trace: bool,
             *, devices=None, peaks=None, overrides=None) -> dict:
    """Set up, warm, run the window, check; returns the result object.

    ``devices`` and ``peaks`` are what :func:`main` found on the chip;
    ``overrides`` (a configuration's ``cpu_test``: ``{"dataset": {...},
    "min_sup": ...}``, or ``{"traffic": {...}}``) shrink a cell for a test
    on a host without one."""
    import jax

    cell, config, traffic = find_cell(bench, name)
    for key, upd in (overrides or {}).items():
        if key == "traffic":
            traffic.update(upd)
        elif isinstance(config.get(key), dict):
            config[key].update(upd)
        else:
            config[key] = upd
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    devices = devices or jax.devices()[:1]
    compiles = Compiles()

    unit = driver.Cell(config, traffic, seed)
    unit.warm()
    setup_s = time.perf_counter() - T_START
    compiles_before = compiles.n

    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        if trace:
            span = f"bench.{driver.UNIT}"
            with jax.profiler.TraceAnnotation("bench.window"):
                records, raised, window_s = run_window(
                    unit, seconds, lambda: jax.profiler.TraceAnnotation(span))
        else:
            records, raised, window_s = run_window(unit, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    in_window = compiles.n - compiles_before

    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                      for d in devices)
    unit.release()
    t_check = time.perf_counter()
    checks = unit.check()
    check_s = time.perf_counter() - t_check
    checks["raised"] = raised

    trace_red = None
    if trace:
        from bench import trace_reduce

        try:
            tr = trace_reduce.load(log_dir)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        win = [s for s in tr.host if s[2] == "bench.window"]
        spans = [s for s in tr.host if s[2] == f"bench.{driver.UNIT}"]
        if len(win) != 1 or len(spans) != len(records) + raised:
            raise RuntimeError(f"trace holds {len(win)} window and "
                               f"{len(spans)} unit spans for "
                               f"{len(records) + raised} units")
        labels = []
        if not raised:
            for rec, (s, e, _) in zip(records, spans):
                labels.extend(driver.labels(rec, s, e))
        trace_red = trace_reduce.reduce(tr, (win[0][0], win[0][1]), labels)
        print(f"[bench] trace: window {win[0][:2]} ns, {len(spans)} unit "
              f"spans, busy_s={trace_red['busy_s']}", file=sys.stderr,
              flush=True)

    metrics = {}
    if trace:
        run = types.SimpleNamespace(records=records, trace=trace_red,
                                    cell=unit, peaks=peaks or {})
        for m in per_layer_metrics(bench, cell):
            value = load_metric(m["name"]).read(run) if records else None
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    elif records:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        for k, v in driver.end_to_end(records, window_s).items():
            metrics[k] = {"value": v, "unit": units[k]}
        metrics["setup_s"] = {"value": setup_s, "unit": units["setup_s"]}

    attempted = len(records) + raised
    compared = checks["answers_compared"]
    correct = (compared >= 1 and all(checks[k] <= lim
                                     for k, lim in LIMITS.items()))
    failed = raised + checks["wrong_answers"]
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": min(failed, attempted), "metrics": metrics,
              "device": device}
    if trace_red is not None:
        device["busy_s"] = trace_red["busy_s"]
        device["window_s"] = trace_red["window_s"]
        result["breakdown"] = {"device_ops": trace_red["device_ops"],
                               "idle_gaps": trace_red["idle_gaps"]}
    kernel_paths = sorted({str(r.get("kernel_path")) for r in records})
    times = sorted(r["t_s"] for r in records) or [0.0]
    print(f"[bench] {name} seed={seed} units={attempted} window_s={window_s} "
          f"setup_s={setup_s} check_s={check_s} "
          f"compiles_in_window={in_window} "
          f"kernel_path={','.join(kernel_paths)} unit_s: "
          f"min={times[0]} p50={times[len(times) // 2]} "
          f"p95={times[int(0.95 * (len(times) - 1))]} max={times[-1]} "
          f"first={[round(r['t_s'], 4) for r in records[:3]]}",
          file=sys.stderr, flush=True)
    result["checks"] = {
        "wrong_itemsets": {"value": checks["wrong_itemsets"],
                           "limit": LIMITS["wrong_itemsets"]},
        "raised": {"value": raised, "limit": LIMITS["raised"]},
        "answers_compared": {"value": compared, "at_least": 1},
    }
    for k, v in result["checks"].items():
        bound = (f"limit={v['limit']}" if "limit" in v
                 else f"at_least={v['at_least']}")
        print(f"[bench] check {k}={v['value']} {bound}", file=sys.stderr,
              flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    try:
        if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
            raise SetupError("no program beside the benchmark: src/repro is "
                             "missing from this checkout")
        sys.path.insert(0, os.path.join(ROOT, "src"))
        bench = load_json("BENCHMARK.json")
        cell, _, traffic = find_cell(bench, args.workload)
        chips = int(cell["chips"])
        driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
        if chips not in driver.CHIPS:
            raise SetupError(f"driver {traffic['driver']!r} runs on "
                             f"{driver.CHIPS} chips, the cell asks for {chips}")
        devices = check_devices(chips)
        peaks = peaks_for(devices[0].device_kind)
        print(f"[bench] compile cache: {enable_compile_cache()}",
              file=sys.stderr, flush=True)
    except SetupError as e:
        print(f"[bench] cannot run: {e}", file=sys.stderr, flush=True)
        return 2
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), devices=devices, peaks=peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
