"""Data of the `conviva_daily_20m` configuration.

The session table of `conviva_sessions_20m`, drawn by that
configuration's generator from the same columns, distributions and
`layout_seed`: the two configurations differ in the sample families the
engine keeps (K1 and the day-paired templates), not in the data.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

_SPEC = importlib.util.spec_from_file_location(
    "blinkbench_conviva_sessions_data",
    Path(__file__).with_name("conviva_sessions_20m.py"))
_SESSIONS = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(_SESSIONS)


def generate(cfg: dict, seed: int, n_rows: int):
    return _SESSIONS.generate(cfg, seed, n_rows)
