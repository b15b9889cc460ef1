"""Put the repository root (for ``bench``) and ``src`` on the path, and
give the tests the cells of ``BENCHMARK.json`` at their CPU test sizes."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

WORKLOADS = [w["name"] for w in BENCH["workloads"]]


def small(name: str) -> dict:
    """Overrides that shrink cell ``name`` to its configuration's
    ``cpu_test`` size, a size a test run on the CPU holds."""
    cell = {w["name"]: w for w in BENCH["workloads"]}[name]
    conf = {c["name"]: c for c in BENCH["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        return json.load(f)["cpu_test"]
