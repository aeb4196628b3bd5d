"""Traffic kind ``serve_closed``: a closed population of live sequences
through ``repro.serve.ContinuousBatchingScheduler.step``."""
from __future__ import annotations

import time

import jax
import numpy as np

from hbench import compare, gen
from hbench import reference as ref
from hbench.drivers import Driver, requests_counted, span


class Kind(Driver):
    """Cell kind ``serve_closed``: a closed population of live sequences
    through the continuous-batching scheduler. The window counts the
    memory requests the scheduler dispatched in it.

    For the check, the harness records what the scheduler sends to the
    platform: each ``Engine.run`` dispatch (through an ``Engine`` whose
    ``run`` notes its arguments) and each pin-contract stamp and release
    (through the names ``repro.serve.scheduler`` calls)."""
    span = "sched.step"

    def setup(self):
        import repro.serve.scheduler as sched_mod
        from repro import Engine
        from repro.serve import ContinuousBatchingScheduler, ServeConfig

        log = self.log = []
        recording = self.recording = [False]

        class RecordingEngine(Engine):
            def run(self, trace, **kw):
                r = super().run(trace, **kw)
                if recording[0]:
                    log.append(("run", trace, kw.get("valid"), r.outs))
                return r

        def hook(name, fn):
            def wrapped(state, pages, **kw):
                if recording[0]:
                    log.append((name, np.array(pages, np.int64)))
                return fn(state, pages, **kw)
            return wrapped

        for name in ("stamp_pin_pages", "release_pin_pages"):
            if not hasattr(sched_mod, name):
                raise RuntimeError(f"repro.serve.scheduler no longer calls "
                                   f"{name}; the harness cannot record pins")
        self.hooks = [(sched_mod, n, getattr(sched_mod, n))
                      for n in ("stamp_pin_pages", "release_pin_pages")]
        for mod, n, fn in self.hooks:
            setattr(mod, n, hook(n, fn))

        serve = dict(self.conf["serve"])
        serve["sorted_batch_sizes"] = tuple(serve["sorted_batch_sizes"])
        self.engine = RecordingEngine(self.cfg)
        self.sched = ContinuousBatchingScheduler(self.engine,
                                                 ServeConfig(**serve))
        self.sched.warmup()
        tr = self.traffic
        self.prompt, self.decode = gen.population(
            self.seed, tr["mix"], tr["pool_sequences"])
        self.next = 0
        recording[0] = True
        # Fill the population and admit it: the window starts in the
        # steady state.
        self._top_up()
        while self.sched.queued:
            self.sched.step()
        jax.block_until_ready(self.sched.carry)
        self.dispatched0 = self.sched.requests_dispatched
        self.counted0 = int(requests_counted(jax.device_get(
            self.sched.carry.counters)))
        self.log_mark = len(self.log)

    def _top_up(self):
        s = self.sched
        k = self.traffic["population"] - s.live_seqs - s.queued
        k = min(k, len(self.prompt) - self.next)
        if k > 0:
            with span("sched.submit"):
                s.submit(self.prompt[self.next:self.next + k],
                         self.decode[self.next:self.next + k])
            self.next += k

    def window(self, seconds):
        s, steps = self.sched, 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with span(self.span):
                s.step()
            self._top_up()
            steps += 1
        with span("window.block"):
            jax.block_until_ready(s.carry)
        wall = time.perf_counter() - t0
        self.recording[0] = False
        for mod, n, fn in self.hooks:
            setattr(mod, n, fn)
        done = s.requests_dispatched - self.dispatched0
        counted = int(requests_counted(jax.device_get(s.carry.counters)))
        chunks = sum(len(e[1].page) // self.chunk
                     for e in self.log[self.log_mark:] if e[0] == "run")
        return {"wall": wall, "work": done, "calls": steps,
                "attempted": done, "counted": counted - self.counted0,
                "chunks": chunks, "points": 1}

    def check(self, tally, control=False):
        pf = ref.Platform(self.conf["platform"], self.conf["technologies"])
        st, ctl = ref.State(pf), ref.State(pf)
        for i, ev in enumerate(self.log):
            if ev[0] == "stamp_pin_pages":
                ref.stamp_pins(pf, st, ev[1])
                ref.stamp_pins(pf, ctl, ev[1])
            elif ev[0] == "release_pin_pages":
                ref.release_pins(pf, st, ev[1])
                ref.release_pins(pf, ctl, ev[1])
            else:
                _, trace, valid, outs = ev
                t = [np.asarray(a) for a in jax.device_get(tuple(trace))]
                v = None if valid is None else np.asarray(valid)
                want = ref.run(pf, st, *t, valid=v)
                got = (ref.run(pf, ctl, *t, valid=v, redirect=False)
                       if control else jax.device_get(
                           {o: outs[o] for o in compare.OUT_KEYS}))
                tally.outs(got, want, f"dispatch {i}")
        final = (compare.reference_state(ctl) if control else
                 compare.program_state(jax.device_get(self.sched.carry)))
        tally.state(final, compare.reference_state(st), "final state")
