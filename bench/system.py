"""Stand up the system under test for one configuration file
(``bench/configs/<config>.json``): rows from the seed, the learned
curve, the index or the on-disk segment, and the attached engine.

Both deployment kinds learn the curve by SMBO (`Database.fit`) on a
fixed sample of the configuration's distribution (`_learn_curve`); the
file's ``system`` key then picks:

* ``memory`` -- `Database.fit` builds the in-memory index over the rows
  with that curve; the engine named in the file serves it.
* ``store`` -- the rows go through the out-of-core `build_segment` with
  that curve, and the ``store`` engine serves the segment through a
  page-group cache of ``cache_fraction`` of its device footprint.
"""
from __future__ import annotations

import dataclasses
import os
import shutil
import time

import numpy as np

from datagen import make_data, make_workload


@dataclasses.dataclass
class System:
    """What one run serves, and what set-up cost."""

    db: object
    engine: str
    rows: np.ndarray              # every row served (reference input)
    K: int
    page_mbrs: np.ndarray         # (P, d, 2) page bounding boxes
    page_rows: np.ndarray         # (P,) rows per page
    timings: dict                 # data_s, fit_s, build_s (host clock)
    workdir: str = None
    stateless: bool = True        # no state carries from query to query

    def close(self) -> None:
        self.db = None
        if self.workdir:
            shutil.rmtree(self.workdir, ignore_errors=True)


def _learn_curve(cfg: dict, K: int):
    """The curve, learned by `Database.fit` (SMBO) on a sample of
    ``fit.rows`` rows drawn from ``structure_seed``: the same sample, so
    the same curve, in every run.  The compiled serving programs close
    over the curve, so a curve that moved with ``--seed`` would make every
    run compile them all again."""
    from repro.api import Database
    fit = cfg["fit"]
    seed = int(cfg["structure_seed"])
    sample = make_data({**cfg, "rows": int(fit["rows"])}, seed)
    Ls, Us = make_workload(sample, int(fit["train_queries"]), seed,
                           float(fit["train_width"]),
                           float(fit["data_frac"]), K)
    return Database.fit(sample, workload=(Ls, Us), seed=seed,
                        sample=int(fit["sample"]), smbo=fit.get("smbo")).curve


def build(cfg: dict, seed: int, workdir: str, engine_overrides: dict = None,
          log=None) -> System:
    """The configured deployment, its rows made from `seed`.
    `engine_overrides` replaces `EngineConfig` fields (the control)."""
    from repro.api import Database, EngineConfig
    K = int(cfg["K"])
    ecfg = dict(cfg.get("engine_config", {}))
    ecfg.update(engine_overrides or {})
    timings = {}
    t0 = time.perf_counter()
    rows = make_data(cfg, seed)
    timings["data_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    curve = _learn_curve(cfg, K)
    timings["fit_s"] = time.perf_counter() - t0
    if cfg["system"] == "memory":
        t0 = time.perf_counter()
        db = Database.fit(rows, curve=curve, learn=False)
        timings["build_s"] = time.perf_counter() - t0
        db.engine(cfg["engine"], EngineConfig(**ecfg))
        idx = db.index
        return System(db, cfg["engine"], rows, K, np.asarray(idx.mbrs),
                      np.diff(np.asarray(idx.starts)), timings)
    if cfg["system"] != "store":
        raise ValueError(f"unknown system kind {cfg['system']!r}")
    from repro.store import build_segment
    st = cfg["store"]
    path = os.path.join(workdir, "segment")
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(workdir, exist_ok=True)
    chunk = int(st["chunk_rows"])
    t0 = time.perf_counter()
    build_segment((rows[i:i + chunk] for i in range(0, len(rows), chunk)),
                  path, curve=curve, page_rows=int(st["page_rows"]))
    timings["build_s"] = time.perf_counter() - t0
    db = Database.from_segment(path, verify="meta")
    seg = db.segment
    g = int(st["group_pages"])
    footprint = seg.num_groups(g) * seg.group_nbytes(g)
    ecfg.update(group_pages=g,
                cache_bytes=int(footprint * float(st["cache_fraction"])))
    db.engine("store", EngineConfig(**ecfg))
    return System(db, "store", rows, K, np.asarray(seg.mbrs),
                  np.diff(np.asarray(seg.starts)), timings, workdir=workdir,
                  stateless=False)
