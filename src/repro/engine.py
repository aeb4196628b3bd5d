"""The session API: one stateful, mesh-aware entry point for the platform.

The paper's value proposition is *fast iterative design exploration* —
and an exploration session is stateful: you compile a geometry once, run
a workload, look at the counters, tweak a knob or a policy, continue
from warm state, fan a grid out over devices, and keep going.
:class:`Engine` is that session as an object:

    from repro import Engine
    from repro.core import paper_platform

    engine = Engine(paper_platform().with_(chunk=512))
    state, outs = engine.run(trace)                 # one design point
    state, outs = engine.run(trace2, state=state)   # continue, in place
    res = engine.sweep(spec, trace, mesh="auto")    # grid, sharded
    res = engine.continue_sweep(res, trace2, mesh="auto")   # warm grid

An ``Engine`` owns three things:

* the **static geometry** (``config.static_key`` of its config) — the
  only thing that forces recompilation;
* a **frozen** :class:`~repro.core.policies.PolicyRegistry` — an
  immutable snapshot of the policy table taken at construction, so a
  session's compiled programs can never be invalidated (or silently
  changed) by later ``policies.register`` calls;
* the **unified jit entry-point cache** (module-level in
  ``core.emulator``, shared by every Engine): one cache keyed by
  (static geometry, registry, batch, donate, shape signature) subsumes
  the four hand-rolled jit variants the free-function API used to carry,
  so constructing a second same-geometry Engine reuses every cached
  executable and :attr:`Engine.compile_count` reports real compilations
  without poking jit internals.

States passed into ``run``/``run_stream``/``continue_sweep`` are
**donated by default**: the session contract is that carried state moves
forward in place (the packed table updates without an O(n_pages) copy)
and the passed-in object is CONSUMED — reading it afterwards raises.
Pass ``donate=False`` to keep your copy.
"""
from __future__ import annotations

from typing import Iterable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core import counters as counters_lib
from repro.core.config import (EmulatorConfig, RuntimeParams,
                               canonical_config, static_key)
from repro.core.emulator import (EmulatorState, Trace, as_registry,
                                 entry_cache_count, entry_point, init_state,
                                 pad_trace)
from repro.core.faults import FaultPlan
from repro.core.policies import PolicyRegistry
from repro.sweep.results import SweepResult
from repro.sweep.spec import DesignPoint, SweepSpec, build_points


class RunResult(NamedTuple):
    """Outcome of one :meth:`Engine.run` / :meth:`Engine.run_stream`:
    unpacks as ``(state, outs)``; ``outs`` maps ``returns`` / ``device``
    / ``latency`` to per-request arrays (trimmed to the trace length)."""

    state: EmulatorState
    outs: dict

    def summary(self) -> dict:
        """Host-side counter summary (per-tier traffic, latency, energy)."""
        return counters_lib.summary(self.state.counters)


def stack_params(points: list[DesignPoint]) -> RuntimeParams:
    """Stack per-point RuntimeParams into one pytree with a leading
    point axis (the vmap axis)."""
    ps = [p.params for p in points]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *ps)


def sweep_mesh():
    """A 1-D device mesh over every local device, for sharded sweeps
    (auto axis types: the point axis is placed by ``NamedSharding``)."""
    return Mesh(np.asarray(jax.devices()), ("data",))


def _prefetched(segments, depth: int):
    """Keep ``depth`` upcoming segments transferred to device ahead of
    consumption, so the host->device copy of segment ``k+1`` overlaps
    the in-flight emulation of segment ``k`` (JAX dispatch is async; the
    transfer is enqueued, not waited on). Bitwise-neutral: values are
    unchanged, only their placement time moves."""
    from collections import deque

    it = iter(segments)
    buf: deque = deque()

    def pull():
        try:
            buf.append(jax.tree.map(jax.device_put, next(it)))
        except StopIteration:
            pass

    for _ in range(max(depth, 1)):
        pull()
    while buf:
        yield buf.popleft()
        pull()


def _pad_to_multiple(tree, n: int, mult: int):
    """Pad the leading (point) axis of every leaf to a multiple of
    ``mult`` by repeating the last point. Works on stacked params and on
    stacked states alike."""
    pad = (-n) % mult
    if pad == 0:
        return tree, 0
    padded = jax.tree.map(
        lambda x: jnp.concatenate([x, jnp.repeat(x[-1:], pad, axis=0)]),
        tree,
    )
    return padded, pad


class Engine:
    """A compiled, stateful session over one static platform geometry.

    ``cfg`` supplies the static geometry (and the default runtime design
    point); ``registry`` optionally restricts/overrides the policy table
    — a ``PolicyRegistry``, a tuple of registered names, or None for a
    snapshot of everything registered so far. All methods accept an
    optional ``params`` (a ``RuntimeParams`` whose ``policy_id`` indexes
    *this engine's registry*) defaulting to the config's runtime point.
    """

    def __init__(self, cfg: EmulatorConfig, *, registry=None):
        self.cfg = cfg
        self.registry: PolicyRegistry = as_registry(registry)
        # Compiled programs are keyed on static geometry only; runtime
        # knobs travel in params, so geometry-equal sessions share every
        # executable.
        self._static = canonical_config(cfg)
        self._skey = static_key(cfg)
        self._valid_cache: dict[int, jax.Array] = {}
        if cfg.policy in self.registry:
            self._default_params = RuntimeParams.from_config(cfg)._replace(
                policy_id=jnp.int32(self.registry.index(cfg.policy)))
        else:
            # A restricted registry without the config's policy has no
            # well-defined default design point — defaulting to the
            # *global* policy_id would silently run a different policy
            # (the lax.switch clamps out-of-range ids). Defer the error
            # to default-params use; explicit params= always works.
            self._default_params = None

    @property
    def params(self) -> RuntimeParams:
        """The config's runtime design point, with ``policy_id`` indexing
        this engine's registry. Raises when the registry was restricted
        past ``cfg.policy`` — pass ``params=`` explicitly then."""
        if self._default_params is None:
            raise ValueError(
                f"config policy {self.cfg.policy!r} is not in this "
                f"engine's registry {self.registry.names}: there is no "
                "default design point — pass params= with a policy_id "
                "indexing the engine's registry")
        return self._default_params

    # ------------------------------------------------------------------
    # plumbing
    # ------------------------------------------------------------------
    @property
    def compile_count(self) -> int:
        """Compiled emulation programs held for this geometry (all
        sessions; backed by the unified entry-point cache)."""
        return entry_cache_count(self._skey)

    def init_state(self, params: RuntimeParams | None = None) -> EmulatorState:
        """Fresh platform state for this geometry (tier boundary and
        pre-pinned fraction read from ``params``). Every leaf gets its
        own buffer, so the state is safe to pass back with the default
        donation (a raw ``core.init_state`` shares one zero scalar
        across leaves, which XLA refuses to donate twice)."""
        state = init_state(self._static,
                           self.params if params is None else params)
        return jax.tree.map(jnp.array, state)

    def _entry_for(self, n: int, *, carried: bool, donate: bool,
                   fsig=None):
        """The compiled single-run entry point for an ``n``-request
        padded trace — the single source of truth for the run-path
        shape-sig layout (``benchmarks/bench_engine.py`` uses it for its
        raw-jit baseline). ``carried`` selects the continued-state
        program (fresh state is otherwise built inside the program, and
        donation only ever applies to a carried state). ``fsig`` is the
        :class:`FaultPlan` shape signature (None = no plan) — a plan's
        event-array shapes are executable determinants like everything
        else in the sig."""
        return entry_point(self._static, self.registry,
                           donate=donate and carried,
                           shape_sig=(n, False, not carried, fsig))

    @staticmethod
    def _fault_sig(faults):
        return None if faults is None else (faults.shape_sig,
                                            faults.is_batched)

    def _dispatch(self, trace, valid, state, params, donate, faults=None):
        fn = self._entry_for(len(trace), carried=state is not None,
                             donate=donate, fsig=self._fault_sig(faults))
        return fn(self._static, self.registry, trace, valid, state, params,
                  faults)

    @staticmethod
    def _resolve_donate(donate: bool | None, state) -> bool:
        """Tri-state donate: None (the default) means donate whatever
        carried state there is; an EXPLICIT True with no state to donate
        raises instead of being silently dropped."""
        if donate and state is None:
            raise ValueError(
                "donate=True requires state=...: a fresh run builds its "
                "state inside the program and has nothing of yours to "
                "donate (the default donate=None already donates a "
                "passed-in state)")
        return True if donate is None else donate

    def _ones_valid(self, n: int) -> jax.Array:
        """All-valid mask, cached per length: a chunk-aligned trace needs
        no padding, and rebuilding the mask every call is pure dispatch
        overhead on the continued/serving hot path."""
        v = self._valid_cache.get(n)
        if v is None:
            v = jnp.ones(n, bool)
            self._valid_cache[n] = v
        return v

    # ------------------------------------------------------------------
    # single design point
    # ------------------------------------------------------------------
    def run(self, trace: Trace, *, params: RuntimeParams | None = None,
            state: EmulatorState | None = None,
            valid: jax.Array | None = None,
            donate: bool | None = None,
            faults: FaultPlan | None = None) -> RunResult:
        """Run one trace through the platform at one design point.

        The trace is padded to a chunk multiple automatically (outputs
        come back trimmed to the original length); pass ``valid`` only
        with an already-padded trace. ``state`` continues a previous run
        and is **donated (consumed) by default** — the packed table
        updates in place; pass ``donate=False`` to keep it readable.
        ``faults`` injects a :class:`~repro.core.faults.FaultPlan`
        (events keyed on the carried state's absolute ``chunk_idx``);
        None is bitwise-identical to the empty plan.
        """
        params = self.params if params is None else params
        donate = self._resolve_donate(donate, state)
        n = len(trace)
        if valid is None:
            if n % self.cfg.chunk:
                trace, valid = pad_trace(self.cfg, trace)
            else:
                valid = self._ones_valid(n)
        elif n % self.cfg.chunk:
            raise ValueError("explicit valid= requires a chunk-multiple "
                             "trace (use pad_trace, or drop valid=)")
        state, outs = self._dispatch(trace, valid, state, params, donate,
                                     faults)
        if len(trace) != n:
            outs = jax.tree.map(lambda x: x[:n], outs)
        return RunResult(state, outs)

    def run_stream(self, segments: Iterable[Trace], *,
                   params: RuntimeParams | None = None,
                   state: EmulatorState | None = None,
                   donate: bool | None = None,
                   prefetch: int = 0,
                   faults: FaultPlan | None = None) -> RunResult:
        """Emulate a trace delivered as segments — the serving-scale path
        for streams larger than device memory.

        Segments may have arbitrary lengths: requests are re-chunked
        across segment boundaries (a sub-chunk remainder is carried into
        the next segment), so the result is **bitwise identical** to one
        :meth:`run` over the concatenated trace — same outputs, same
        final state. Segments of equal, chunk-multiple length share a
        single compiled executable; ragged lengths compile per distinct
        length. Intermediate states are engine-owned and always donated;
        ``donate`` governs only a caller-passed ``state`` (consumed by
        default, like :meth:`run`).

        ``prefetch`` > 0 keeps that many upcoming segments transferred
        to device ahead of consumption, overlapping the host->device
        copy of segment ``k+1`` (often a lazily *generated* segment)
        with the in-flight emulation of segment ``k``. Results are
        bitwise identical at any depth.

        One ``faults`` plan spans the whole stream: its events are keyed
        on the carried state's absolute ``chunk_idx``, so the same plan
        is threaded into every segment dispatch and each event fires in
        whichever segment reaches its stamp (the serving scheduler
        relies on this across dispatch boundaries).
        """
        params = self.params if params is None else params
        donate = self._resolve_donate(donate, state)
        if prefetch:
            segments = _prefetched(segments, prefetch)
        chunk = self.cfg.chunk
        carry: Trace | None = None
        parts: list[dict] = []
        first = True
        for seg in segments:
            buf = seg if carry is None else Trace(
                *(jnp.concatenate([a, b]) for a, b in zip(carry, seg)))
            m = len(buf) - len(buf) % chunk
            if m == 0:
                carry = buf
                continue
            head = Trace(*(x[:m] for x in buf))
            carry = Trace(*(x[m:] for x in buf)) if m < len(buf) else None
            state, outs = self._dispatch(
                head, self._ones_valid(m), state, params,
                donate if first else True, faults)
            parts.append(outs)
            first = False
        if carry is not None and len(carry):
            n = len(carry)
            padded, valid = pad_trace(self.cfg, carry)
            state, outs = self._dispatch(padded, valid, state, params,
                                         donate if first else True, faults)
            parts.append(jax.tree.map(lambda x: x[:n], outs))
        if not parts:
            z = jnp.zeros(0, jnp.int32)
            if state is None:
                state = self.init_state(params)
            return RunResult(state, {"returns": z, "device": z, "latency": z})
        outs = {k: jnp.concatenate([p[k] for p in parts]) for k in parts[0]}
        return RunResult(state, outs)

    def run_channels(self, traces: Trace, *,
                     params: RuntimeParams | None = None,
                     faults: FaultPlan | None = None):
        """FPGA-style spatial parallelism: emulate independent trace
        channels at once (``traces`` has a leading channel axis; each
        channel's length must be a chunk multiple). Returns
        ``(states, outs)`` with the channel axis leading. ``params`` —
        and the optional shared ``faults`` plan — apply to every
        channel."""
        params = self.params if params is None else params
        fn = entry_point(self._static, self.registry,
                         shape_sig=("channels", tuple(traces.page.shape),
                                    self._fault_sig(faults)))
        batched = jax.vmap(
            lambda t: fn(self._static, self.registry, t, None, None, params,
                         faults))
        return batched(traces)

    # ------------------------------------------------------------------
    # design-space sweeps
    # ------------------------------------------------------------------
    def _sweep_batch(self, spec):
        """Normalize spec/points/params into (points, registry, params)."""
        if isinstance(spec, RuntimeParams):
            # A pre-stacked params batch: policy_id already indexes this
            # engine's registry; synthesize index-only points for rows().
            n = int(jnp.shape(spec.policy_id)[0])
            points = [DesignPoint(index=i, coords=(("point", i),),
                                  cfg=self.cfg) for i in range(n)]
            return points, self.registry, spec
        points = list(spec) if isinstance(spec, (list, tuple)) \
            else build_points(spec)
        if not points:
            raise ValueError("empty sweep")
        keys = {static_key(p.cfg) for p in points}
        if keys != {self._skey}:
            raise ValueError(
                f"points disagree on this engine's static geometry: {keys}")
        # Compile the policy switch only over policies actually present;
        # remap each point's policy_id into that restricted registry.
        names: list[str] = []
        for p in points:
            if p.cfg.policy not in names:
                names.append(p.cfg.policy)
        registry = self.registry.subset(names)
        ids = jnp.asarray([registry.index(p.cfg.policy) for p in points],
                          jnp.int32)
        params = stack_params(points)._replace(policy_id=ids)
        return points, registry, params

    def sweep(self, spec: SweepSpec | list[DesignPoint] | RuntimeParams,
              trace: Trace, *, mesh=None, states=None,
              donate: bool | None = None,
              faults: FaultPlan | None = None) -> SweepResult:
        """Evaluate every design point of ``spec`` on ``trace`` in ONE
        compiled, vmapped emulation.

        ``spec``: a :class:`SweepSpec` grid, a ``DesignPoint`` list, or a
        pre-stacked ``RuntimeParams`` batch (``policy_id`` indexing this
        engine's registry). All points must share this engine's static
        geometry.

        ``mesh``: None runs on the default device; ``"auto"`` builds a
        1-D mesh over all local devices; an explicit ``jax.sharding.Mesh``
        shards the point axis over its first axis (the point count is
        padded to a mesh multiple by replicating the last point; padding
        is dropped from the results).

        ``states``: stacked per-point ``EmulatorState`` (a previous
        sweep's ``SweepResult.states``) to continue from. Continued
        sweeps **compose with mesh sharding**: the stacked states are
        padded and placed with the same ``NamedSharding`` as the params,
        so an incremental sweep fans out across devices exactly like a
        fresh one. ``donate`` defaults to True when ``states`` is given
        (the session contract — the passed-in states are CONSUMED where
        their sharding already matches; resharded states donate the
        transferred copy).

        ``faults``: one shared :class:`FaultPlan` applied to every
        point, or a stacked per-point batch (``faults.stack_plans`` —
        pad with ``pad_plan`` first so shapes agree) making the failure
        rate itself a swept design axis. A stacked batch is padded and
        sharded alongside the params.
        """
        points, registry, params = self._sweep_batch(spec)
        return self._sweep_exec(points, registry, params, trace,
                                mesh=mesh, states=states, donate=donate,
                                faults=faults)

    def _sweep_exec(self, points, registry, params, trace, *,
                    mesh, states, donate, faults=None) -> SweepResult:
        """Run an already-normalized (points, registry, stacked params)
        batch — shared by :meth:`sweep` and :meth:`continue_sweep`."""
        n = len(points)
        if donate is None:
            donate = states is not None
        if donate and states is None:
            raise ValueError(
                "donate=True requires states=... (a previous "
                "SweepResult.states): donation aliases the carried "
                "per-point states into the outputs, and a fresh-state "
                "sweep has nothing to donate — without states= the flag "
                "used to be silently ignored")
        stacked = params     # pre-padding batch, recorded for continuation
        padded, valid = pad_trace(self.cfg, trace)
        if mesh == "auto":
            mesh = sweep_mesh()
        n_padded = 0
        if mesh is not None:
            size = mesh.devices.shape[0]
            sharding = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
            params, n_padded = _pad_to_multiple(params, n, size)
            params = jax.device_put(params, sharding)
            if states is not None:
                states, _ = _pad_to_multiple(states, n, size)
                states = jax.device_put(states, sharding)
            if faults is not None and faults.is_batched:
                faults, _ = _pad_to_multiple(faults, n, size)
                faults = jax.device_put(faults, sharding)
        fn = entry_point(self._static, registry, batch=True, donate=donate,
                         shape_sig=(len(padded), n + n_padded,
                                    states is None, mesh,
                                    self._fault_sig(faults)))
        states, outs = fn(self._static, registry, padded, valid, states,
                          params, faults)
        if n_padded:
            states, outs = jax.tree.map(lambda x: x[:n], (states, outs))
        return SweepResult(points=points, states=states, outs=outs,
                           params=stacked, registry=registry)

    def continue_sweep(self, result: SweepResult, trace: Trace, *,
                       mesh=None, donate: bool = True,
                       faults: FaultPlan | None = None) -> SweepResult:
        """Continue a previous sweep on a further trace segment — every
        point resumes from its own warm state, donated (consumed) by
        default, optionally fanned out over ``mesh`` (the stacked states
        are sharded alongside the params). A mesh-sharded continued
        sweep is bitwise-equal to the single long unsharded sweep.

        The continuation replays the *recorded* stacked params/registry
        of ``result`` when present (exact for every sweep flavour,
        including pre-stacked ``RuntimeParams`` batches whose knobs are
        not recoverable from ``result.points``); results from older
        pickles without the record fall back to rebuilding from points.
        """
        if result.params is not None:
            return self._sweep_exec(result.points, result.registry,
                                    result.params, trace, mesh=mesh,
                                    states=result.states, donate=donate,
                                    faults=faults)
        return self.sweep(result.points, trace, mesh=mesh,
                          states=result.states, donate=donate, faults=faults)


__all__ = ["Engine", "RunResult", "PolicyRegistry", "stack_params",
           "sweep_mesh"]
