"""Data of the `conviva_sessions_20m` configuration.

The Conviva-like session table of BlinkDB sec. 6.1 with the shapes of
`repro.data.synth.sessions_table` (Zipf City and URL, skewed OS, uniform
Genre and day, gamma SessionTime that depends on OS and City, normal
Bitrate), drawn directly as dictionary codes: no per-row strings.

The categorical columns come from the configuration's `layout_seed`, the
same in every run, so every seed sees the same strata and sample families
of the same sizes; the run's seed draws the measures.
"""
from __future__ import annotations

import numpy as np

from blinkbench import datagen


def generate(cfg: dict, seed: int, n_rows: int) -> datagen.Data:
    cols = cfg["columns"]
    layout = np.random.default_rng(
        np.random.SeedSequence([cfg["layout_seed"], n_rows, 0xC0F1]))
    codes = {name: datagen.draw_codes(layout, n_rows, cols[name])
             for name in ("City", "URL", "OS", "Genre", "dt")}
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC0F2]))
    city, os_ = codes["City"], codes["OS"]
    base = (20.0 + 3.0 * (os_ % 3) + 0.05 * (city % 17)).astype(np.float32)
    shape = np.float32(cols["SessionTime"]["shape"])
    session_time = (rng.standard_gamma(shape, n_rows, dtype=np.float32)
                    * (base / np.float32(2.0)))
    bitrate = (np.float32(800.0) + np.float32(100.0) * (os_ % 4)
               + np.float32(cols["Bitrate"]["sd"])
               * rng.standard_normal(n_rows, dtype=np.float32))
    numeric = {"SessionTime": session_time.astype(np.float32),
               "Bitrate": bitrate.astype(np.float32)}
    return datagen.Data.from_parts(cfg, codes, numeric)
