"""Traffic kind ``sweep``: a design-point grid through
``Engine.continue_sweep`` on one chip."""
from __future__ import annotations

import itertools
import time

import jax
import numpy as np

from hbench import compare, gen
from hbench import reference as ref
from hbench.drivers import Driver, requests_counted, span, trace


def grid_points(grid: dict, n_pages: int):
    """The grid's design points as reference overrides, in the order of
    the cartesian product technology x fast fraction x policy x link
    latency (the sweep engine's order)."""
    axes = []
    if grid.get("technologies"):
        axes.append([{"slow": t} for t in grid["technologies"]])
    if grid.get("fast_fractions"):
        fr = []
        for f in grid["fast_fractions"]:
            nf = min(max(int(round(n_pages * f)), 1), n_pages - 1)
            fr.append({"n_fast_pages": nf, "n_slow_pages": n_pages - nf})
        axes.append(fr)
    if grid.get("policies"):
        axes.append([{"policy": p} for p in grid["policies"]])
    if grid.get("link_lats"):
        axes.append([{"link_lat": v} for v in grid["link_lats"]])
    out = []
    for combo in itertools.product(*axes):
        o = {}
        for d in combo:
            o.update(d)
        out.append(o)
    return out


class Kind(Driver):
    """Cell kind ``sweep``: a design grid through ``continue_sweep``."""
    span = "engine.continue_sweep"

    def setup(self):
        from repro import Engine
        from repro.sweep import SweepSpec, build_points

        tr = self.traffic
        g = tr["grid"]
        self.n = tr["stream"]["requests"]
        spec = SweepSpec(
            base=self.cfg, technologies=tuple(g.get("technologies", ())),
            fast_fractions=tuple(g.get("fast_fractions", ())),
            policies=tuple(g.get("policies", ())),
            link_lats=tuple(g.get("link_lats", ())))
        self.points = build_points(spec)
        self.overrides = grid_points(g, self.cfg.n_pages)
        if len(self.points) != len(self.overrides):
            raise ValueError("the grid's point count differs from the "
                             "sweep engine's")
        pool = gen.segments(self.seed, tr["stream"], tr["pool_segments"])
        self.segs = [trace(pool, k) for k in range(tr["pool_segments"])]
        self.engine = Engine(self.points[0].cfg)
        self.used, self.outs = [0], []
        with span("engine.sweep"):
            res = self.engine.sweep(self.points, self.segs[0])
        self.outs.append({o: res.outs[o] for o in compare.OUT_KEYS})
        res = self._call(res)
        jax.block_until_ready(res.states)
        self.res = res
        self.counted0 = requests_counted(jax.device_get(
            res.states.counters))

    def _call(self, res):
        k = len(self.used) % self.traffic["pool_segments"]
        with span(self.span):
            res = self.engine.continue_sweep(res, self.segs[k])
        self.used.append(k)
        self.outs.append({o: res.outs[o] for o in compare.OUT_KEYS})
        return res

    def window(self, seconds):
        res, calls = self.res, 0
        t0 = time.perf_counter()
        while True:
            res = self._call(res)
            calls += 1
            if calls > 1:
                jax.block_until_ready(self.outs[-2]["returns"])
            if time.perf_counter() - t0 >= seconds:
                break
        with span("window.block"):
            jax.block_until_ready(res.states)
        wall = time.perf_counter() - t0
        self.res = res
        counted = requests_counted(jax.device_get(res.states.counters))
        p = len(self.points)
        return {"wall": wall, "work": calls * self.n * p, "calls": calls,
                "attempted": calls * self.n * p,
                "counted": int(np.sum(counted - self.counted0)),
                "chunks": calls * self.n // self.chunk,
                "points": p}

    def check(self, tally, control=False):
        """Replays a sample of the design points, drawn from the seed."""
        pool = [[np.asarray(a) for a in seg]
                for seg in jax.device_get(self.segs)]
        p = len(self.points)
        m = min(self.traffic.get("checked_points", p), p)
        pick = np.sort(np.random.default_rng(self.seed).choice(
            p, size=m, replace=False))
        states = None if control else jax.device_get(self.res.states)
        outs = [None if control else jax.device_get(x) for x in self.outs]
        for i in pick:
            pf = ref.Platform(self.conf["platform"],
                              self.conf["technologies"], self.overrides[i])
            st, ctl = ref.State(pf), ref.State(pf)
            for c, (k, got) in enumerate(zip(self.used, outs)):
                seg = pool[k]
                want = ref.run(pf, st, *seg)
                got = (ref.run(pf, ctl, *seg, redirect=False) if control
                       else {o: got[o][i] for o in compare.OUT_KEYS})
                tally.outs(got, want, f"point {i} call {c}")
            final = (compare.reference_state(ctl) if control else
                     compare.program_state(states, i))
            tally.state(final, compare.reference_state(st),
                        f"point {i} state")
