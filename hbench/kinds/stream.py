"""Traffic kind ``stream``: one design point, request segments through the
donated ``Engine.run`` continuation (the path ``Engine.run_stream``
takes)."""
from __future__ import annotations

import time

import jax
import numpy as np

from hbench import compare, gen
from hbench import reference as ref
from hbench.drivers import Driver, requests_counted, span, trace


class Kind(Driver):
    """Cell kind ``stream``: one design point, segments through the
    donated continuation."""

    def setup(self):
        from repro import Engine

        tr = self.traffic
        self.n = tr["stream"]["requests"]
        pool = gen.segments(self.seed, tr["stream"], tr["pool_segments"])
        self.segs = [trace(pool, k) for k in range(tr["pool_segments"])]
        self.engine = Engine(self.cfg)
        self.used, self.outs = [], []
        # The first call (fresh state) and the donated continuation: the
        # two programs of the path.
        state = None
        for _ in range(2):
            state = self._call(state)
        jax.block_until_ready(state)
        self.state = state
        self.counted0 = int(requests_counted(jax.device_get(
            state.counters)))

    def _call(self, state):
        k = len(self.used) % self.traffic["pool_segments"]
        with span(self.span):
            r = self.engine.run(self.segs[k], state=state)
        self.used.append(k)
        self.outs.append({o: r.outs[o] for o in compare.OUT_KEYS})
        return r.state

    def window(self, seconds):
        state, calls = self.state, 0
        t0 = time.perf_counter()
        while True:
            state = self._call(state)
            calls += 1
            # Keep the host at most one call ahead of the device.
            if calls > 1:
                jax.block_until_ready(self.outs[-2]["returns"])
            if time.perf_counter() - t0 >= seconds:
                break
        with span("window.block"):
            jax.block_until_ready(state)
        wall = time.perf_counter() - t0
        self.state = state
        counted = int(requests_counted(jax.device_get(state.counters)))
        return {"wall": wall, "work": calls * self.n, "calls": calls,
                "attempted": calls * self.n,
                "counted": counted - self.counted0,
                "chunks": calls * self.n // self.chunk, "points": 1}

    def check(self, tally, control=False):
        pool = [[np.asarray(a) for a in seg]
                for seg in jax.device_get(self.segs)]
        pf = ref.Platform(self.conf["platform"], self.conf["technologies"])
        st, ctl = ref.State(pf), ref.State(pf)
        for i, (k, outs) in enumerate(zip(self.used, self.outs)):
            seg = pool[k]
            want = ref.run(pf, st, *seg)
            got = (ref.run(pf, ctl, *seg, redirect=False) if control else
                   jax.device_get(outs))
            tally.outs(got, want, f"call {i}")
        final = (compare.reference_state(ctl) if control else
                 compare.program_state(jax.device_get(self.state)))
        tally.state(final, compare.reference_state(st), "final state")
