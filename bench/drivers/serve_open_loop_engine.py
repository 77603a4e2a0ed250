"""The open loop of ``serve_open_loop`` with the engine's settings taken
from the mix's ``engine`` block, for a configuration file that has none
(one written for training)."""

import importlib.util
from pathlib import Path

from harness import merged

_spec = importlib.util.spec_from_file_location(
    "bench_drivers_serve_open_loop",
    Path(__file__).with_name("serve_open_loop.py"))
open_loop = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(open_loop)


class Driver(open_loop.Driver):
    def __init__(self, run):
        run.model = merged(run.model, {"program": {"engine": run.mix["engine"]}})
        super().__init__(run)
