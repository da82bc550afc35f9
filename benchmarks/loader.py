"""How the harness finds a file by a name from ``BENCHMARK.json``: JSON
data files, and Python files loaded by path (a per-layer reader under
``layer_metrics/``, a model family under ``models/``; their names hold
dots, so they are not importable modules)."""

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(*parts):
    """The Python file ``benchmarks/<parts...>.py`` as a module."""
    path = os.path.join(BENCH, *parts) + ".py"
    name = "bench_" + "_".join(parts).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
