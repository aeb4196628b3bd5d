"""What every traffic kind shares: the program's configuration built from
a configuration file, request counting, and the driver interface.

A traffic file names its ``kind``; ``hbench/kinds/<kind>.py`` holds the
driver (a class named ``Kind``) that builds the system under test, makes
the traffic from the file's parameters, warms every shape the window
uses, drives the measured window, and replays what the window produced
through the reference.
"""
from __future__ import annotations

import jax
import numpy as np

from hbench import compare


def span(name):
    return jax.profiler.TraceAnnotation(name)


def emulator_config(conf: dict):
    """The program's ``EmulatorConfig`` for a configuration file, with
    the file's technology table."""
    from repro.core.config import EmulatorConfig, TechnologyParams

    p = dict(conf["platform"])
    techs = conf["technologies"]
    for tier in ("fast", "slow"):
        t = techs[p[tier]]
        p[tier] = TechnologyParams(p[tier], t["read_lat"], t["write_lat"],
                                   t["bytes_per_cycle"])
    return EmulatorConfig(**p)


def trace(arrays, i=None):
    from repro.core import Trace

    return Trace(*(a if i is None else a[i] for a in arrays))


def requests_counted(counters) -> np.ndarray:
    return sum(np.asarray(getattr(counters, k), np.int64)
               for k in ("reads_fast", "writes_fast", "reads_slow",
                         "writes_slow"))


class Driver:
    """What every traffic kind provides to the harness."""
    span = "engine.run"

    def __init__(self, conf: dict, traffic: dict, seed: int):
        self.conf, self.traffic, self.seed = conf, traffic, seed
        self.cfg = emulator_config(conf)
        self.chunk = self.cfg.chunk

    def setup(self) -> None:
        raise NotImplementedError

    def window(self, seconds: float) -> dict:
        """Drive the window; returns the work done in it: ``work`` (point
        x requests, or requests), ``attempted``, ``counted`` (requests the
        counters show), ``chunks`` (scan iterations per design point) and
        ``points``."""
        raise NotImplementedError

    def check(self, tally: compare.Tally) -> None:
        raise NotImplementedError
