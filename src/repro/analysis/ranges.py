"""Pass ``ranges`` — interval-domain bounds/overflow prover for the
packed table and the chunk-step pipeline.

An abstract interpreter over jaxprs with the interval domain
(per-value ``[lo, hi]`` over unbounded Python numbers) plus a handful
of table-aware refinements:

* **lane-aware table lineage** — a value whose lineage reaches the
  packed ``int32[n_pages, 8]`` table carries *per-lane* intervals, and
  the interpreter tracks lane extraction (row gathers, lane-column
  gathers, ``slice``/``squeeze``) and lane-targeted scatters (each
  concatenated section of the ONE (row, lane) commit scatter lands on
  the lane its constant lane column names; a flat view's index
  arithmetic is tracked modulo 8 to the same end);
* **saturation certificates** — the ``saturating_weights`` idiom in
  core/table.py (``min(max(CAP - pre - psum, 0), w)``) is recognized
  structurally: a scatter-add of certified weights bounds the lane at
  ``max(pre, CAP)`` no matter how many updates alias one row;
* **exchange certificates** — updates of the shape ``new - gather(lane)``
  (the DMA commit's rebased deltas) bound the lane at
  ``join(pre, new)``;
* **gated increments** — ``cursor + cast(b)`` where ``b``'s lineage
  conjoins ``cursor < N`` proves ``cursor' <= max(cursor, N)`` (the
  fault-cursor consume);
* **guarded indexing** — every gather/scatter whose operand lineage
  reaches the table is classified *proved* (index interval within
  bounds), *guarded* (``mode=drop``/clip), or a finding (XLA's
  ``PROMISE_IN_BOUNDS`` with an unproven index is undefined behavior).

Three programs are checked, reusing the PR 9 path-linking machinery in
analysis/common.py: the ``lax.scan`` chunk body of
``emulator._emulate_impl`` (what a run actually compiles), and
``step_ref(seq=True/False)`` — the literal Pallas kernel body (the
schedule pass AST-pins that link) and the jnp reference — with
``RuntimeParams`` as *traced inputs* so the proofs are parametric over
the declared knob budget, not one config's values.

The run budget (``N_CHUNKS_BUDGET``, ``PARAM_BOUNDS``, trace bounds) is
declared below; per-chunk time growth ``G`` is measured by evaluating
the step from the time origin, giving the int32 horizon
``(2^31-1) // G`` that must cover the declared budget. The idiom
recognizers' side conditions (delta rebasing against the same rows,
time-translation covariance of the step) are property-tested in
tests/test_ranges.py; the runtime ``check_table`` lane asserts are the
dynamic backstop.

Fixture protocol: ``reprolint_case()`` returning
``{"kind": "ranges", "make": lambda: (fn, args)}``; ``fn(*args)`` is
traced with the table as argument 0 and all other inputs bound to the
documented fixture budget (ints ``[0, 2^20]``).
"""
from __future__ import annotations

import math
import pathlib

from .common import (Finding, apply_pragmas, eqn_loc, rel, scan_body_info,
                     trace_step_ref)

PASS = "ranges"

INT32 = (-(1 << 31), (1 << 31) - 1)
INF = float("inf")

# --------------------------------------------------------------------------- #
# The declared per-run budget. The prover's claim is conditional on runs
# staying inside it; `validate_budget` checks the repo's own configs
# against it so the declaration cannot silently rot.
# --------------------------------------------------------------------------- #

#: Chunks per emulation run the int32 proofs cover. With chunk width c,
#: that is `N_CHUNKS_BUDGET * c` requests per `Engine.run` call.
N_CHUNKS_BUDGET = 1 << 10

#: Declared intervals for every RuntimeParams leaf (params are traced
#: inputs on the step_ref paths, so the proofs hold for ALL values in
#: these ranges). A params leaf missing here is itself a finding.
PARAM_BOUNDS = {
    "fast_read_lat": (0, 1 << 11),
    "fast_write_lat": (0, 1 << 11),
    "fast_bytes_per_cycle": (1.0, 1024.0),
    "slow_read_lat": (0, 1 << 11),
    "slow_write_lat": (0, 1 << 11),
    "slow_bytes_per_cycle": (1.0, 1024.0),
    "link_lat": (0, 1 << 11),
    "link_bytes_per_cycle": (1.0, 1024.0),
    "issue_gap": (0, 1 << 8),
    "dma_cycles_per_subblock": (1, 1 << 10),
    "n_fast_pages": (1, None),          # None -> n_pages
    "hot_threshold": (0, 1 << 20),
    "hotness_decay_shift": (0, 31),
    "decay_every": (1, 1 << 20),
    "write_weight": (1, 1 << 10),       # the budget's max_weight
    "wear_slack": (0, 1 << 29),
    "pin_fast_fraction": (0.0, 1.0),
    "endurance_budget": (-(1 << 29), 1 << 29),
    "policy_id": (0, 1 << 4),
    "power_pj_per_bit_fast": (0.0, 1024.0),
    "power_pj_per_bit_slow_read": (0.0, 1024.0),
    "power_pj_per_bit_slow_write": (0.0, 1024.0),
}

#: Request-trace bounds (per field of the traced chunk).
TRACE_BOUNDS = {
    "page": (0, None),                  # None -> n_pages - 1
    "offset": (0, (1 << 12) - 1),       # within one page
    "size": (0, 1 << 12),               # at most one page per request
}

# Carry/StepScalars field policies. TIME fields grow by at most G per
# chunk (G measured from the origin; translation covariance is
# property-tested); MONO fields grow by a measured constant rate;
# everything else must be inductive under its declared interval.
_TIME_FIELDS = ("clock", "bank_free", "link_free_rx", "link_free_tx",
                "last_return", "dma.start")
_MONO_FIELDS = ("chunk_idx", "dma.swaps_done")


def _inductive_fields(n_pages, nd):
    return {
        "clock_ptr": (0, n_pages - 1),
        "dma.active": (0, 1),
        "dma.page_a": (-1, n_pages - 1),
        "dma.page_b": (-1, n_pages - 1),
        "rescue_page": (-1, n_pages - 1),
        "min_wear": (0, 1 << 30),
        "fault_cursor": (0, nd),
    }


def _lane_invariants(n_pages, epoch_hi):
    from repro.core import table as t
    inv = [None] * t.ROW_W
    inv[t.DEVICE] = (0, 1)
    inv[t.FRAME] = (0, n_pages - 1)
    inv[t.HOTNESS] = (0, t.HOTNESS_CAP)
    inv[t.WEAR] = (0, t.WEAR_CAP)
    inv[t.OWNER] = (0, n_pages - 1)
    inv[t.EPOCH] = (0, epoch_hi)
    inv[t.FLAGS] = (0, 15)
    inv[t._PAD] = (0, 0)
    return inv


_LANE_NAMES = ("DEVICE", "FRAME", "HOTNESS", "WEAR", "OWNER", "EPOCH",
               "FLAGS", "_PAD")
#: Lanes checked inductively; EPOCH is time-like (bounded by the cycle
#: budget instead), _PAD never written.
_INDUCTIVE_LANES = (0, 1, 2, 3, 4, 6)


# --------------------------------------------------------------------------- #
# Interval helpers (lo/hi are Python ints, floats, or +-inf).
# --------------------------------------------------------------------------- #


def _join(a, b):
    return (min(a[0], b[0]), max(a[1], b[1]))


def _pmul(a, b):
    if a == 0 or b == 0:
        return 0
    return a * b


def _iv_add(a, b):
    return (a[0] + b[0], a[1] + b[1])


def _iv_sub(a, b):
    return (a[0] - b[1], a[1] - b[0])


def _iv_mul(a, b):
    cs = [_pmul(x, y) for x in a for y in b]
    return (min(cs), max(cs))


def _contains(outer, inner):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def _dtype_kind(dtype):
    import numpy as np
    d = np.dtype(dtype)
    if d.kind == 'b':
        return 'b', 1
    if d.kind in 'iu':
        return 'i', d.itemsize * 8
    return 'f', d.itemsize * 8


def _dtype_top(kind, bits):
    if kind == 'b':
        return (0, 1)
    if kind == 'i':
        return (-(1 << (bits - 1)), (1 << (bits - 1)) - 1)
    return (-INF, INF)


class AVal:
    """Abstract value: interval + optional refinements.

    const   — concrete np.ndarray (constant folding)
    lanes   — tuple of 8 intervals when the value is table-lineage
              (2-D (n, 8) or the flat reshape(-1) view)
    lane_src— (gen_marker, lane) for an elementwise gather of one lane
    mod     — value ≡ mod (modulo 8), for flat-index lane attribution
    pieces  — [(length, AVal)] axis-0 concatenation structure (1-D)
    cols    — [AVal] per-column structure of a dim=1 concat of (N,1)
    sat     — (lane, cap): saturating_weights certificate
    capminus— (lane, C): value <= C - gather(lane) (sat intermediate)
    exch    — (lane, new_iv): `new - gather(lane)` exchange certificate
    alt     — scalar const of a select_n branch folded into this value
              (drop-guarded sentinel narrowing at scatters)
    padz    — (offset, period): 1-D value is a zero-interior-padded
              dilation — nonzero entries only at positions ≡ offset
              (mod period).  Lets `p_add` recognise the
              pad+pad+add *interleave* step of `lax.associative_scan`
              (disjoint supports ⇒ join, not sum)
    gates   — frozenset of (id(base), bound): value != 0 implies
              base < bound held (lt-lineage of a bool)
    """

    __slots__ = ("shape", "kind", "bits", "iv", "const", "lanes",
                 "lane_src", "mod", "pieces", "cols", "sat", "capminus",
                 "exch", "alt", "gates", "padz")

    def __init__(self, shape, kind, bits, iv, const=None, lanes=None,
                 lane_src=None, mod=None, pieces=None, cols=None,
                 sat=None, capminus=None, exch=None, alt=None,
                 gates=frozenset(), padz=None):
        self.shape = tuple(shape)
        self.kind = kind
        self.bits = bits
        if kind == 'b':
            iv = (max(iv[0], 0), min(iv[1], 1))
        self.iv = iv
        self.const = const
        self.lanes = lanes
        self.lane_src = lane_src
        self.mod = mod
        self.pieces = pieces
        self.cols = cols
        self.sat = sat
        self.capminus = capminus
        self.exch = exch
        self.alt = alt
        self.gates = gates
        self.padz = padz

    # -- constructors ------------------------------------------------------ #

    @classmethod
    def of_const(cls, arr):
        import numpy as np
        arr = np.asarray(arr)
        kind, bits = _dtype_kind(arr.dtype)
        if arr.size:
            lo, hi = arr.min().item(), arr.max().item()
            if kind == 'b':
                lo, hi = int(lo), int(hi)
        else:
            lo, hi = 0, 0
        mod = None
        if kind == 'i' and arr.size:
            mods = np.unique(arr % 8)
            if mods.size == 1:
                mod = int(mods[0])
        return cls(arr.shape, kind, bits, (lo, hi), const=arr, mod=mod)

    @classmethod
    def top_for(cls, aval):
        kind, bits = _dtype_kind(aval.dtype)
        return cls(aval.shape, kind, bits, _dtype_top(kind, bits))

    def with_(self, **kw):
        out = AVal(self.shape, self.kind, self.bits, self.iv)
        for s in self.__slots__:
            setattr(out, s, getattr(self, s))
        for k, v in kw.items():
            setattr(out, k, v)
        return out

    def plain(self, shape=None, iv=None):
        return AVal(self.shape if shape is None else shape, self.kind,
                    self.bits, self.iv if iv is None else iv)

    @property
    def scalar_const(self):
        if self.const is not None and self.const.size == 1:
            return self.const.reshape(()).item()
        return None

    def __repr__(self):
        return (f"AVal{self.shape}{self.kind}{self.bits} iv={self.iv}"
                + (" table" if self.lanes else ""))


def _const_or_none(*avs):
    if all(a.const is not None for a in avs):
        return [a.const for a in avs]
    return None


_FOLD_LIMIT = 1 << 16


# --------------------------------------------------------------------------- #
# The interpreter.
# --------------------------------------------------------------------------- #


class Interp:
    """One abstract evaluation of a jaxpr. Collects index-safety
    results, int32 overflow notes and analysis gaps as it goes."""

    #: optional ``(eqn, ins, outs) -> None`` debug callback (tests only).
    trace_hook = None

    def __init__(self, track_overflow=True):
        self.track_overflow = track_overflow
        self.index_findings = []    # (loc, message)
        self.overflow = []          # (loc, prim, iv)
        self.gaps = []              # (loc, message)
        self.n_proved = 0
        self.n_guarded = 0

    # -- plumbing ---------------------------------------------------------- #

    def eval_closed(self, closed, in_avals):
        jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
        consts = list(getattr(closed, "consts", ()) or ())
        return self.eval_jaxpr(jaxpr, consts, in_avals)

    def eval_jaxpr(self, jaxpr, consts, in_avals):
        env = {}

        def read(atom):
            if hasattr(atom, "val"):        # Literal
                return AVal.of_const(atom.val)
            return env[atom]

        def write(var, aval):
            if type(var).__name__ != "DropVar":
                env[var] = aval

        for v, c in zip(jaxpr.constvars, consts):
            import numpy as np
            env[v] = AVal.of_const(np.asarray(c))
        assert len(jaxpr.invars) == len(in_avals), \
            (len(jaxpr.invars), len(in_avals))
        for v, a in zip(jaxpr.invars, in_avals):
            env[v] = a

        for eqn in jaxpr.eqns:
            ins = [read(x) for x in eqn.invars]
            prim = eqn.primitive.name
            fn = getattr(self, "p_" + prim.replace("-", "_"), None)
            try:
                if fn is None:
                    raise NotImplementedError(prim)
                outs = fn(eqn, ins)
            except NotImplementedError as e:
                self.gaps.append((eqn_loc(eqn),
                                  f"unhandled primitive `{e}`"))
                outs = [AVal.top_for(o.aval) for o in eqn.outvars]
            if isinstance(outs, AVal):
                outs = [outs]
            if self.trace_hook is not None:
                self.trace_hook(eqn, ins, outs)
            if self.track_overflow and prim in ("add", "sub", "mul"):
                out = outs[0]
                if (out.kind == 'i' and out.bits == 32
                        and not _contains(INT32, out.iv)):
                    self.overflow.append((eqn_loc(eqn), prim, out.iv))
            for v, a in zip(eqn.outvars, outs):
                write(v, a)
        return [read(v) for v in jaxpr.outvars]

    # -- generic elementwise machinery ------------------------------------- #

    def _ew2(self, a, b, ivf, constf=None, meta=None):
        """Elementwise binary: broadcasts shapes, folds constants, maps
        over concat pieces when one side is scalar-like or both align."""
        import numpy as np
        shape = np.broadcast_shapes(a.shape, b.shape)
        cs = _const_or_none(a, b)
        if cs is not None and constf is not None:
            arr = constf(*cs)
            if arr.size <= _FOLD_LIMIT:
                return AVal.of_const(arr)
        out = AVal(shape, a.kind if a.kind != 'b' else b.kind,
                   max(a.bits, b.bits), ivf(a.iv, b.iv))
        # piece mapping: keep concat structure through the arithmetic
        # that builds the flattened commit indices/updates.
        for x, y in ((a, b), (b, a)):
            if x.pieces is not None and x.shape == shape:
                if y.scalar_const is not None or y.shape in ((), (1,)):
                    out.pieces = [(ln, self._ew2(p, y, ivf, constf, meta))
                                  for ln, p in x.pieces]
                    break
                if (y.pieces is not None
                        and [ln for ln, _ in y.pieces]
                        == [ln for ln, _ in x.pieces]):
                    if x is a:
                        out.pieces = [
                            (ln, self._ew2(p, q, ivf, constf, meta))
                            for (ln, p), (_, q) in zip(x.pieces, y.pieces)]
                    break
                if (y.const is not None and y.shape == shape
                        and len(shape) == 1):
                    # constant vector against a pieces value: slice the
                    # constant per piece (rows*8 + const lane vector).
                    segs = []
                    off = 0
                    for ln, p in x.pieces:
                        seg = AVal.of_const(
                            np.ascontiguousarray(y.const[off:off + ln]))
                        off += ln
                        pair = (p, seg) if x is a else (seg, p)
                        segs.append((ln, self._ew2(*pair, ivf, constf,
                                                   meta)))
                    out.pieces = segs
                    break
        if meta is not None:
            meta(out, a, b)
        return out

    # -- arithmetic -------------------------------------------------------- #

    def p_add(self, eqn, ins):
        a, b = ins

        def meta(out, a, b):
            for x, y in ((a, b), (b, a)):
                c = y.scalar_const
                if c is not None and x.mod is not None:
                    out.mod = (x.mod + c) % 8
                    break
            else:
                if a.mod is not None and b.mod is not None:
                    out.mod = (a.mod + b.mod) % 8
            # gated increment: x + g where g's gates bound x.
            for x, g in ((a, b), (b, a)):
                for base, bound in g.gates:
                    if base == id(x):
                        out.iv = (out.iv[0] if out.iv[0] < x.iv[0]
                                  else x.iv[0],
                                  max(x.iv[1], bound))
            # capminus survives adding a nonpositive term; sat/exch are
            # consumed at the scatter, not propagated through adds.
            # associative_scan's interleave step: add of two zero-
            # dilated pads with disjoint supports — every output
            # element is an element of ONE operand (or zero), so the
            # sound interval is the join, not the sum.
            if (a.padz is not None and b.padz is not None
                    and a.padz[1] == b.padz[1]
                    and a.padz[0] != b.padz[0]):
                out.iv = _join(a.iv, b.iv)
        return self._ew2(a, b, _iv_add, lambda x, y: x + y, meta)

    def p_sub(self, eqn, ins):
        a, b = ins

        def meta(out, a, b):
            c = b.scalar_const
            if c is not None and a.mod is not None:
                out.mod = (a.mod - c) % 8
            elif a.mod is not None and b.mod is not None:
                out.mod = (a.mod - b.mod) % 8
            # `C - gather(lane)`: the root of the saturation chain.
            ca = a.scalar_const
            if ca is not None and b.lane_src is not None:
                out.capminus = (b.lane_src[1], ca)
            # `capminus - nonneg` (subtracting the prefix sum) keeps it.
            elif a.capminus is not None and b.iv[0] >= 0:
                out.capminus = a.capminus
            # `new - gather(lane)`: exchange certificate.
            if b.lane_src is not None:
                out.exch = (b.lane_src[1], a.iv)
        return self._ew2(a, b, _iv_sub, lambda x, y: x - y, meta)

    def p_mul(self, eqn, ins):
        a, b = ins

        def meta(out, a, b):
            for x, y in ((a, b), (b, a)):
                c = y.scalar_const
                if c is not None and c % 8 == 0:
                    out.mod = 0     # n*8k ≡ 0 (mod 8) for any n
                    break
                if c is not None and x.mod is not None:
                    out.mod = (x.mod * c) % 8
                    break
        return self._ew2(a, b, _iv_mul, lambda x, y: x * y, meta)

    def p_max(self, eqn, ins):
        a, b = ins

        def ivf(x, y):
            return (max(x[0], y[0]), max(x[1], y[1]))

        def meta(out, a, b):
            import numpy as np
            # max(C - pre - psum, 0) -> a certified "allowance".
            for x, y in ((a, b), (b, a)):
                if x.capminus is not None and y.scalar_const == 0:
                    out.capminus = x.capminus
        return self._ew2(a, b, ivf, lambda x, y: __import__("numpy")
                         .maximum(x, y), meta)

    def p_min(self, eqn, ins):
        a, b = ins

        def ivf(x, y):
            return (min(x[0], y[0]), min(x[1], y[1]))

        def meta(out, a, b):
            # min(allowance, w): the full saturating_weights certificate.
            for x, y in ((a, b), (b, a)):
                if x.capminus is not None and y.iv[0] >= 0:
                    out.sat = x.capminus
                    break
        return self._ew2(a, b, ivf, lambda x, y: __import__("numpy")
                         .minimum(x, y), meta)

    def p_div(self, eqn, ins):
        import numpy as np
        a, b = ins
        if a.kind == 'f' or b.kind == 'f':
            def ivf(x, y):
                if y[0] > 0 or y[1] < 0:
                    cs = [u / v for u in x for v in y if v]
                    return (min(cs), max(cs))
                return (-INF, INF)
            return self._ew2(a, b, ivf, lambda x, y: x / y)

        def tdiv(u, v):
            q = abs(u) // abs(v)
            return q if (u >= 0) == (v >= 0) else -q

        def ivf(x, y):
            if y[0] >= 1 or y[1] <= -1:
                cs = [tdiv(u, v) for u in x for v in y]
                return (min(cs), max(cs))
            return _dtype_top('i', max(a.bits, b.bits))
        return self._ew2(a, b, ivf,
                         lambda x, y: (np.sign(x) * np.sign(y)
                                       * (abs(x) // abs(y))).astype(x.dtype))

    def p_rem(self, eqn, ins):
        a, b = ins

        def ivf(x, y):
            m = max(abs(y[0]), abs(y[1]))
            if m == 0:
                return _dtype_top(a.kind, a.bits)
            if x[0] >= 0:
                return (0, min(x[1], m - 1))
            return (-(m - 1), m - 1)
        return self._ew2(a, b, ivf)

    def p_pow(self, eqn, ins):
        raise NotImplementedError("pow")

    def p_neg(self, eqn, ins):
        a, = ins
        import numpy as np
        out = a.plain(iv=(-a.iv[1], -a.iv[0]))
        if a.const is not None:
            return AVal.of_const(-a.const)
        return out

    def p_abs(self, eqn, ins):
        a, = ins
        lo, hi = a.iv
        if lo >= 0:
            return a
        return a.plain(iv=(0 if hi >= 0 else min(-hi, -lo),
                           max(abs(lo), abs(hi))))

    def p_sign(self, eqn, ins):
        a, = ins
        lo = -1 if a.iv[0] < 0 else (0 if a.iv[0] == 0 else 1)
        hi = 1 if a.iv[1] > 0 else (0 if a.iv[1] == 0 else -1)
        return a.plain(iv=(lo, hi))

    def p_ceil(self, eqn, ins):
        a, = ins
        return a.plain(iv=(a.iv[0], a.iv[1] if a.iv[1] == INF
                           else math.ceil(a.iv[1])))

    def p_floor(self, eqn, ins):
        a, = ins
        return a.plain(iv=(a.iv[0] if a.iv[0] == -INF
                           else math.floor(a.iv[0]), a.iv[1]))

    def p_round(self, eqn, ins):
        a, = ins
        return a.plain()

    def p_shift_right_arithmetic(self, eqn, ins):
        a, b = ins

        def ivf(x, y):
            slo, shi = max(y[0], 0), min(y[1], 63)
            cs = [u >> s for u in x for s in (slo, shi)]
            return (min(cs), max(cs))
        return self._ew2(a, b, ivf, lambda x, y: x >> y)

    def p_shift_right_logical(self, eqn, ins):
        a, b = ins
        if a.iv[0] >= 0:
            return self.p_shift_right_arithmetic(eqn, ins)
        return self._ew2(a, b,
                         lambda x, y: (0, (1 << a.bits) - 1))

    def p_shift_left(self, eqn, ins):
        a, b = ins

        def ivf(x, y):
            slo, shi = max(y[0], 0), min(y[1], 63)
            cs = [_pmul(u, 1 << s) for u in x for s in (slo, shi)]
            return (min(cs), max(cs))
        return self._ew2(a, b, ivf, lambda x, y: x << y)

    # -- boolean / bitwise -------------------------------------------------- #

    def _cmp(self, eqn, ins, op, constf):
        a, b = ins
        out = self._ew2(a, b, lambda x, y: (0, 1), constf)
        out.kind, out.bits = 'b', 1
        lo, hi = op(a.iv, b.iv)
        out.iv = (lo, hi)
        return out

    def p_lt(self, eqn, ins):
        import numpy as np
        a, b = ins
        out = self._cmp(
            eqn, ins,
            lambda x, y: ((1, 1) if x[1] < y[0]
                          else (0, 0) if x[0] >= y[1] else (0, 1)),
            lambda x, y: x < y)
        c = b.scalar_const
        if c is not None:
            out.gates = frozenset({(id(a), c)})
        return out

    def p_le(self, eqn, ins):
        a, b = ins
        out = self._cmp(
            eqn, ins,
            lambda x, y: ((1, 1) if x[1] <= y[0]
                          else (0, 0) if x[0] > y[1] else (0, 1)),
            lambda x, y: x <= y)
        c = b.scalar_const
        if c is not None and a.kind == 'i':
            out.gates = frozenset({(id(a), c + 1)})
        return out

    def p_gt(self, eqn, ins):
        return self._cmp(
            eqn, ins,
            lambda x, y: ((1, 1) if x[0] > y[1]
                          else (0, 0) if x[1] <= y[0] else (0, 1)),
            lambda x, y: x > y)

    def p_ge(self, eqn, ins):
        return self._cmp(
            eqn, ins,
            lambda x, y: ((1, 1) if x[0] >= y[1]
                          else (0, 0) if x[1] < y[0] else (0, 1)),
            lambda x, y: x >= y)

    def p_eq(self, eqn, ins):
        return self._cmp(
            eqn, ins,
            lambda x, y: ((1, 1) if x[0] == x[1] == y[0] == y[1]
                          else (0, 0) if x[1] < y[0] or y[1] < x[0]
                          else (0, 1)),
            lambda x, y: x == y)

    def p_ne(self, eqn, ins):
        return self._cmp(
            eqn, ins,
            lambda x, y: ((0, 0) if x[0] == x[1] == y[0] == y[1]
                          else (1, 1) if x[1] < y[0] or y[1] < x[0]
                          else (0, 1)),
            lambda x, y: x != y)

    def p_and(self, eqn, ins):
        a, b = ins
        if a.kind == 'b':
            out = self._ew2(a, b, lambda x, y: (0, min(x[1], y[1])),
                            lambda x, y: x & y)
            out.gates = a.gates | b.gates
            return out

        def ivf(x, y):
            if x[0] >= 0 or y[0] >= 0:
                hi = min(x[1] if x[0] >= 0 else (1 << a.bits),
                         y[1] if y[0] >= 0 else (1 << a.bits))
                return (0, hi)
            # masking with an all-negative (high-bit) constant mask:
            # u & v = u - (u & ~v), and ~v ∈ [0, -v_lo - 1], so the
            # result lives in [u_lo - (-v_lo - 1), u_hi].
            for u, v in ((x, y), (y, x)):
                if v[1] < 0:
                    return (u[0] - (-v[0] - 1), u[1])
            return _dtype_top('i', a.bits)
        return self._ew2(a, b, ivf, lambda x, y: x & y)

    def p_or(self, eqn, ins):
        a, b = ins
        if a.kind == 'b':
            out = self._ew2(a, b, lambda x, y: (max(x[0], y[0]), 1),
                            lambda x, y: x | y)
            out.gates = a.gates & b.gates
            return out

        def ivf(x, y):
            if x[0] >= 0 and y[0] >= 0:
                m = max(x[1], y[1])
                return (0, (1 << max(1, m.bit_length())) - 1)
            # or-ing in a nonnegative value only sets bits below the
            # sign bit: result keeps u's sign, never drops below u,
            # and a negative u stays ≤ -1.
            for u, v in ((x, y), (y, x)):
                if v[0] >= 0:
                    return (u[0], (u[1] + v[1]) if u[1] >= 0 else -1)
            return _dtype_top('i', a.bits)
        return self._ew2(a, b, ivf, lambda x, y: x | y)

    def p_xor(self, eqn, ins):
        return self.p_or(eqn, ins)

    def p_not(self, eqn, ins):
        a, = ins
        if a.kind == 'b':
            return AVal(a.shape, 'b', 1, (1 - a.iv[1], 1 - a.iv[0]))
        return a.plain(iv=_dtype_top('i', a.bits))

    def p_select_n(self, eqn, ins):
        pred, *cases = ins
        if pred.iv == (0, 0):
            return [cases[0]]
        if pred.iv == (1, 1) and len(cases) == 2:
            return [cases[1]]
        c = pred.scalar_const
        if c is not None:
            return [cases[int(c)]]
        import numpy as np
        # full constant fold: a constant pred *vector* over constant
        # cases (the lane-id where-chains in table.swap_commit_lanes).
        if (pred.const is not None
                and all(x.const is not None for x in cases)):
            shape = np.broadcast_shapes(pred.shape,
                                        *[x.shape for x in cases])
            if int(np.prod(shape, dtype=np.int64)) <= _FOLD_LIMIT:
                sel = np.broadcast_to(pred.const, shape).astype(np.int64)
                arrs = [np.broadcast_to(np.asarray(x.const), shape)
                        for x in cases]
                return [AVal.of_const(np.choose(sel, arrs))]
        # piecewise: a constant pred vector over aligned pieces selects
        # each piece exactly (the plan's lane-masked where()s).
        lens = None
        for x in cases:
            if x.pieces is not None:
                lens = [ln for ln, _ in x.pieces]
        if (lens is not None and pred.const is not None
                and pred.const.ndim == 1
                and all(x.pieces is None or
                        [ln for ln, _ in x.pieces] == lens for x in cases)
                and sum(lens) == pred.const.size and len(cases) == 2):
            out_pieces = []
            off = 0
            for i, ln in enumerate(lens):
                seg = pred.const[off:off + ln]
                off += ln
                sub = [x.pieces[i][1] if x.pieces is not None
                       else x for x in cases]
                if not seg.any():
                    out_pieces.append((ln, sub[0]))
                elif seg.all():
                    out_pieces.append((ln, sub[1]))
                else:
                    j = self._joinv(sub[0], sub[1])
                    out_pieces.append((ln, j))
            iv = out_pieces[0][1].iv
            for _, p in out_pieces[1:]:
                iv = _join(iv, p.iv)
            out = AVal(cases[0].shape if cases[0].shape else cases[1].shape,
                       cases[1].kind, cases[1].bits, iv, pieces=out_pieces)
            return [out]
        out = cases[0]
        for x in cases[1:]:
            out = self._joinv(out, x)
        out = out.with_(gates=frozenset.intersection(
            *[x.gates for x in cases]) if cases[0].kind == 'b'
            else frozenset())
        # sentinel narrowing: select against a uniform constant keeps
        # the other branch's lane attribution, recording the constant so
        # a drop-guarded scatter can discharge it.
        for i, x in enumerate(cases):
            if len(cases) != 2:
                break
            sc = x.scalar_const
            other = cases[1 - i]
            if sc is not None and other.scalar_const is None:
                out = out.with_(mod=other.mod, alt=sc, pieces=other.pieces,
                                sat=other.sat if other.sat and sc == 0
                                else None,
                                exch=other.exch if other.exch and sc == 0
                                else None)
                break
        return [out]

    def _joinv(self, a, b):
        import numpy as np
        shape = np.broadcast_shapes(a.shape, b.shape)
        out = AVal(shape, a.kind if a.kind != 'b' else b.kind,
                   max(a.bits, b.bits), _join(a.iv, b.iv))
        if a.mod is not None and a.mod == b.mod:
            out.mod = a.mod
        if a.lane_src is not None and a.lane_src == b.lane_src:
            out.lane_src = a.lane_src
        if (a.lanes is not None and b.lanes is not None
                and a.shape == b.shape):
            out.lanes = tuple(_join(x, y)
                              for x, y in zip(a.lanes, b.lanes))
        if (a.pieces is not None and b.pieces is not None
                and [ln for ln, _ in a.pieces]
                == [ln for ln, _ in b.pieces]):
            out.pieces = [(ln, self._joinv(p, q))
                          for (ln, p), (_, q) in zip(a.pieces, b.pieces)]
        if a.exch and b.exch and a.exch[0] == b.exch[0]:
            out.exch = (a.exch[0], _join(a.exch[1], b.exch[1]))
        if a.sat and b.sat and a.sat == b.sat:
            out.sat = a.sat
        if a.capminus and a.capminus == b.capminus:
            out.capminus = a.capminus
        return out

    # -- structure --------------------------------------------------------- #

    def p_broadcast_in_dim(self, eqn, ins):
        import numpy as np
        a, = ins
        shape = eqn.params["shape"]
        if a.const is not None:
            try:
                arr = np.broadcast_to(
                    a.const.reshape([a.const.shape[
                        eqn.params["broadcast_dimensions"].index(d)]
                        if d in eqn.params["broadcast_dimensions"] else 1
                        for d in range(len(shape))]), shape)
                if arr.size <= _FOLD_LIMIT:
                    return a.with_(shape=tuple(shape),
                                   const=np.ascontiguousarray(arr))
            except Exception:
                pass
        out = a.with_(shape=tuple(shape), const=None)
        if a.shape and a.shape != tuple(shape):
            # (n,) -> (n, 1, ...) keeps flatten order: the axis-0 piece
            # structure survives (the scatter index column needs it).
            bdims = tuple(eqn.params["broadcast_dimensions"])
            keep = (len(a.shape) == 1 and bdims == (0,)
                    and shape[0] == a.shape[0]
                    and all(d == 1 for d in shape[1:]))
            out.cols = None
            if not keep:
                out.pieces = None
        return out

    def p_reshape(self, eqn, ins):
        import numpy as np
        a, = ins
        shape = eqn.params["new_sizes"]
        if a.const is not None:
            return a.with_(shape=tuple(shape),
                           const=a.const.reshape(shape))
        out = a.with_(shape=tuple(shape), const=None, pieces=None,
                      cols=None)
        # the table <-> flat view alias keeps lanes; anything else drops
        if a.lanes is not None and not (
                len(shape) == 1 or
                (len(shape) == 2 and shape[1] == len(a.lanes))):
            out.lanes = None
        return out

    def p_squeeze(self, eqn, ins):
        a, = ins
        import numpy as np
        shape = tuple(d for i, d in enumerate(a.shape)
                      if i not in eqn.params["dimensions"])
        if a.const is not None:
            return a.with_(shape=shape, const=a.const.reshape(shape))
        return a.with_(shape=shape, const=None, cols=None)

    def p_transpose(self, eqn, ins):
        a, = ins
        perm = eqn.params["permutation"]
        shape = tuple(a.shape[p] for p in perm)
        if a.const is not None:
            return AVal.of_const(a.const.transpose(perm))
        return a.plain(shape=shape)

    def p_concatenate(self, eqn, ins):
        import numpy as np
        dim = eqn.params["dimension"]
        cs = _const_or_none(*ins)
        if cs is not None:
            arr = np.concatenate(cs, axis=dim)
            if arr.size <= _FOLD_LIMIT:
                return AVal.of_const(arr)
        iv = ins[0].iv
        for x in ins[1:]:
            iv = _join(iv, x.iv)
        shape = list(ins[0].shape)
        shape[dim] = sum(x.shape[dim] for x in ins)
        out = AVal(tuple(shape), ins[0].kind, ins[0].bits, iv)
        mods = {x.mod for x in ins}
        if len(mods) == 1:
            out.mod = mods.pop()
        if dim == 0 and len(ins[0].shape) == 1:
            pieces = []
            for x in ins:
                if x.pieces is not None:
                    pieces.extend(x.pieces)
                else:
                    pieces.append((x.shape[0], x))
            out.pieces = pieces
        elif (dim == 1 and len(ins[0].shape) == 2
              and all(x.shape[1] == 1 for x in ins)):
            out.cols = [x for x in ins]
        return out

    def p_iota(self, eqn, ins):
        import numpy as np
        shape = eqn.params["shape"]
        d = eqn.params["dimension"]
        if int(np.prod(shape)) <= _FOLD_LIMIT:
            ix = np.arange(shape[d], dtype=eqn.params["dtype"])
            arr = np.broadcast_to(
                ix.reshape([shape[d] if i == d else 1
                            for i in range(len(shape))]), shape)
            return AVal.of_const(np.ascontiguousarray(arr))
        kind, bits = _dtype_kind(eqn.params["dtype"])
        return AVal(shape, kind, bits, (0, shape[d] - 1))

    def p_slice(self, eqn, ins):
        import numpy as np
        a, = ins
        start = eqn.params["start_indices"]
        limit = eqn.params["limit_indices"]
        strides = eqn.params["strides"] or (1,) * len(start)
        if a.const is not None:
            sl = tuple(slice(s, l, st)
                       for s, l, st in zip(start, limit, strides))
            return AVal.of_const(a.const[sl])
        shape = tuple((l - s + st - 1) // st
                      for s, l, st in zip(start, limit, strides))
        out = a.plain(shape=shape)
        # lane extraction from a (rows, 8) table-lineage value — any row
        # subset (the fused swap gather splits its (chunk+2, 8) result
        # with partial row slices)
        if a.lanes is not None and len(a.shape) == 2 and a.shape[1] == 8:
            if start[1] + 1 == limit[1]:
                lane = start[1]
                out.iv = a.lanes[lane]
                out.lane_src = (id(a.lanes), lane)
            elif start[1] == 0 and limit[1] == 8:
                out = AVal(shape, a.kind, a.bits, a.iv, lanes=a.lanes)
        # ... and from a single packed row (the swap pair's row_a/row_b)
        if (a.lanes is not None and len(a.shape) == 1
                and a.shape[0] == len(a.lanes)
                and start[0] + 1 == limit[0]):
            lane = start[0]
            out.iv = a.lanes[lane]
            out.lane_src = (id(a.lanes), lane)
        # axis-0 sub-range of a pieces value: join overlapped pieces
        if a.pieces is not None and len(a.shape) == 1 and strides == (1,):
            off = 0
            ivs = []
            for ln, p in a.pieces:
                if off < limit[0] and off + ln > start[0]:
                    ivs.append(p.iv)
                off += ln
            if ivs:
                iv = ivs[0]
                for x in ivs[1:]:
                    iv = _join(iv, x)
                out.iv = iv
        return out

    def p_pad(self, eqn, ins):
        a, pv = ins
        import numpy as np
        if a.const is not None and pv.const is not None:
            lo, hi, inner = zip(*eqn.params["padding_config"])
            if all(i == 0 for i in inner) and all(
                    x >= 0 for x in lo + hi):
                arr = np.pad(a.const,
                             list(zip(lo, hi)), constant_values=pv.const)
                if arr.size <= _FOLD_LIMIT:
                    return AVal.of_const(arr)
        shape = tuple(d + l + h + (d - 1) * i
                      for d, (l, h, i) in zip(a.shape,
                                              eqn.params["padding_config"]))
        out = a.plain(shape=shape, iv=_join(a.iv, pv.iv))
        if (pv.scalar_const == 0 and len(a.shape) == 1
                and a.shape[0] >= 1):
            lo, hi, inner = eqn.params["padding_config"][0]
            if inner >= 1 and lo >= 0 and hi >= 0:
                # zero-dilated: nonzero only at lo + k*(inner+1)
                out.padz = (lo % (inner + 1), inner + 1)
        return out

    def p_rev(self, eqn, ins):
        a, = ins
        if a.const is not None:
            import numpy as np
            return AVal.of_const(np.flip(a.const,
                                         eqn.params["dimensions"]))
        return a.plain()

    # -- conversions ------------------------------------------------------- #

    def p_convert_element_type(self, eqn, ins):
        a, = ins
        kind, bits = _dtype_kind(eqn.params["new_dtype"])
        if kind == a.kind and bits == a.bits:
            return a               # identity: preserve object id (gates)
        if a.const is not None:
            import numpy as np
            return AVal.of_const(a.const.astype(eqn.params["new_dtype"]))
        lo, hi = a.iv
        if kind == 'i' and a.kind == 'f':
            lo = lo if lo == -INF else math.floor(lo)
            hi = hi if hi == INF else math.ceil(hi)
            lo, hi = (max(lo, _dtype_top(kind, bits)[0]),
                      min(hi, _dtype_top(kind, bits)[1]))
        if kind == 'b':
            lo, hi = (0 if lo <= 0 <= hi else 1, 0 if lo == hi == 0 else 1)
        out = AVal(a.shape, kind, bits, (lo, hi), gates=a.gates,
                   mod=a.mod if kind == 'i' and a.kind == 'i' else None)
        return out

    def p_device_put(self, eqn, ins):
        return ins[0]

    def p_copy(self, eqn, ins):
        return ins[0]

    def p_stop_gradient(self, eqn, ins):
        return ins[0]

    # -- reductions -------------------------------------------------------- #

    def _red_n(self, a, eqn):
        import numpy as np
        n = 1
        for ax in eqn.params["axes"]:
            n *= a.shape[ax]
        shape = tuple(d for i, d in enumerate(a.shape)
                      if i not in eqn.params["axes"])
        return n, shape

    def p_reduce_sum(self, eqn, ins):
        a, = ins
        n, shape = self._red_n(a, eqn)
        if a.const is not None:
            import numpy as np
            return AVal.of_const(a.const.sum(axis=eqn.params["axes"]))
        lo = _pmul(n, a.iv[0]) if a.iv[0] < 0 else min(a.iv[0], 0) \
            if n > 1 else a.iv[0]
        hi = _pmul(n, a.iv[1]) if a.iv[1] > 0 else max(a.iv[1], 0) \
            if n > 1 else a.iv[1]
        return a.plain(shape=shape, iv=(lo, hi))

    def p_reduce_max(self, eqn, ins):
        a, = ins
        _, shape = self._red_n(a, eqn)
        if a.const is not None:
            import numpy as np
            return AVal.of_const(a.const.max(axis=eqn.params["axes"]))
        return a.plain(shape=shape)

    def p_reduce_min(self, eqn, ins):
        a, = ins
        _, shape = self._red_n(a, eqn)
        if a.const is not None:
            import numpy as np
            return AVal.of_const(a.const.min(axis=eqn.params["axes"]))
        return a.plain(shape=shape)

    def p_reduce_or(self, eqn, ins):
        a, = ins
        _, shape = self._red_n(a, eqn)
        return AVal(shape, 'b', 1, a.iv,
                    gates=a.gates if a.shape == () or shape == a.shape
                    else frozenset())

    def p_reduce_and(self, eqn, ins):
        a, = ins
        _, shape = self._red_n(a, eqn)
        return AVal(shape, 'b', 1, a.iv)

    def p_argmax(self, eqn, ins):
        a, = ins
        axes = eqn.params["axes"]
        shape = tuple(d for i, d in enumerate(a.shape) if i not in axes)
        hi = max(a.shape[ax] for ax in axes) - 1
        kind, bits = _dtype_kind(eqn.params["index_dtype"])
        return AVal(shape, kind, bits, (0, hi))

    p_argmin = p_argmax

    def p_cumsum(self, eqn, ins):
        a, = ins
        n = a.shape[eqn.params["axis"]]
        lo = _pmul(n, a.iv[0]) if a.iv[0] < 0 else a.iv[0]
        hi = _pmul(n, a.iv[1]) if a.iv[1] > 0 else a.iv[1]
        return a.plain(iv=(lo, hi))

    def p_cummax(self, eqn, ins):
        return ins[0].plain()

    p_cummin = p_cummax

    def p_sort(self, eqn, ins):
        return [x.plain() for x in ins]

    # -- indexing ---------------------------------------------------------- #

    def _index_cols(self, idx, ndim_indexed):
        """Per-indexed-dimension column AVals of a gather/scatter index
        array of shape (..., k)."""
        import numpy as np
        k = idx.shape[-1] if idx.shape else 1
        if idx.const is not None:
            flat = idx.const.reshape(-1, k)
            return [AVal.of_const(flat[:, j]) for j in range(k)]
        if idx.cols is not None and len(idx.cols) == k:
            return idx.cols
        if (idx.pieces is not None and len(idx.pieces) == k
                and all(ln == 1 for ln, _ in idx.pieces)):
            return [p for _, p in idx.pieces]   # (1, k) single-site index
        if k == 1:
            return [idx]
        return [idx.plain(shape=(0,)) for _ in range(k)]

    def _check_index(self, eqn, cols, dims, sizes, guarded, what):
        """Classify one gather/scatter's table indexing."""
        ok = True
        for col, d in zip(cols, dims):
            lo, hi = col.iv
            # a drop-guarded select-against-sentinel narrows to the
            # live branch; the sentinel constant must itself be either
            # in range or discharged by the guard.
            if not (0 <= lo and hi < sizes[d]):
                ok = False
        if ok:
            self.n_proved += 1
        elif guarded:
            self.n_guarded += 1
        else:
            self.index_findings.append(
                (eqn_loc(eqn),
                 f"{what} index into the table not proven in bounds "
                 f"(index interval {[c.iv for c in cols]} vs dims "
                 f"{[sizes[d] for d in dims]}) and not guarded by "
                 "mode=drop/clip — XLA PROMISE_IN_BOUNDS is undefined "
                 "behavior out of range"))

    @staticmethod
    def _guarded_mode(eqn):
        mode = eqn.params.get("mode")
        name = getattr(mode, "name", str(mode))
        return any(k in str(name) for k in ("FILL_OR_DROP", "CLIP", "DROP"))

    def p_gather(self, eqn, ins):
        import numpy as np
        a, idx = ins
        dnums = eqn.params["dimension_numbers"]
        slice_sizes = eqn.params["slice_sizes"]
        out_aval = eqn.outvars[0].aval
        guarded = self._guarded_mode(eqn)
        cols = self._index_cols(idx, len(dnums.start_index_map))
        if a.lanes is not None:
            self._check_index(eqn, cols, dnums.start_index_map, a.shape,
                              guarded, "gather")
            out = AVal(out_aval.shape, a.kind, a.bits, a.iv)
            if len(a.shape) == 2 and a.shape[1] == len(a.lanes):
                if (len(slice_sizes) == 2
                        and slice_sizes[1] == len(a.lanes)):
                    # whole-row (or row-block) gather: rows keep
                    # per-lane structure
                    out.lanes = a.lanes
                    return out
                if (slice_sizes == (1, 1)
                        and getattr(dnums, "operand_batching_dims",
                                    ()) == (1,)
                        and dnums.start_index_map == (0,)):
                    # take_along_axis row gather: the lane axis is a
                    # batch axis, so rows keep per-lane structure too
                    out.lanes = a.lanes
                    return out
                if (slice_sizes == (1, 1) and len(cols) == 2
                        and cols[1].const is not None
                        and np.unique(cols[1].const).size == 1):
                    lane = int(cols[1].const.reshape(-1)[0])
                    out.iv = a.lanes[lane]
                    out.lane_src = (id(a.lanes), lane)
                    return out
            elif len(a.shape) == 1:
                # flat view: a known index mod narrows to one lane
                if cols and cols[0].mod is not None:
                    out.iv = a.lanes[cols[0].mod]
                    out.lane_src = (id(a.lanes), cols[0].mod)
                    return out
            iv = a.lanes[0]
            for l in a.lanes[1:]:
                iv = _join(iv, l)
            out.iv = iv
            return out
        cs = _const_or_none(a, idx)
        if cs is not None and a.const.size <= _FOLD_LIMIT:
            try:
                from jax import lax
                import jax
                with jax.disable_jit():
                    arr = lax.gather(
                        cs[0], cs[1], dnums, slice_sizes,
                        mode=eqn.params.get("mode"))
                return AVal.of_const(np.asarray(arr))
            except Exception:
                pass
        return AVal(out_aval.shape, a.kind, a.bits, a.iv)

    def _scatter_common(self, eqn, ins, op):
        import numpy as np
        a, idx, upd = ins
        dnums = eqn.params["dimension_numbers"]
        guarded = self._guarded_mode(eqn)
        out = AVal(a.shape, a.kind, a.bits, a.iv, lanes=a.lanes,
                   mod=a.mod)
        if a.lanes is None:
            if op == "add":
                out.iv = _iv_add(a.iv, (min(0, _pmul(
                    int(np.prod(upd.shape) or 1), upd.iv[0])),
                    max(0, _pmul(int(np.prod(upd.shape) or 1),
                                 upd.iv[1]))))
            else:
                out.iv = _join(a.iv, upd.iv)
            return out
        dims = dnums.scatter_dims_to_operand_dims
        cols = self._index_cols(idx, len(dims))
        self._check_index(eqn, cols, dims, a.shape, guarded, "scatter")
        lanes = list(a.lanes)

        def sections():
            """Aligned (length, idx_piece, upd_piece) sections of the
            flattened scatter (cut at every piece boundary)."""
            def cuts(av, total):
                if av.pieces is None:
                    return [(total, av)]
                return list(av.pieces)
            total = idx.shape[0] if idx.shape else 1
            ip = cuts(cols[0] if len(cols) == 1 else idx, total)
            up = cuts(upd, total)
            out_secs = []
            i = j = 0
            ioff = joff = 0
            while i < len(ip) and j < len(up):
                ilen, ipc = ip[i]
                jlen, upc = up[j]
                take = min(ilen - ioff, jlen - joff)
                out_secs.append((take, ipc, upc))
                ioff += take
                joff += take
                if ioff == ilen:
                    i, ioff = i + 1, 0
                if joff == jlen:
                    j, joff = j + 1, 0
            return out_secs

        def col_lane():
            c = cols[1]
            if c.const is not None:
                u = np.unique(c.const)
                if u.size == 1:
                    return int(u[0])
            if c.iv[0] == c.iv[1] and 0 <= c.iv[0] < 8:
                return int(c.iv[0])
            return None

        def lane_sections():
            """Sections of a (row, lane) scatter: with a constant lane
            column (the boundary commit's), one per lane value inside
            each update piece; else one section on ``col_lane()``."""
            total = int(np.prod(upd.shape) or 1)
            row = cols[0].iv
            lane_of = cols[1].const
            if lane_of is None or lane_of.size != total:
                return [(total, AVal((0,), 'i', 32, row, mod=col_lane()),
                         upd)]
            lane_of = np.asarray(lane_of).reshape(-1)
            out, off = [], 0
            for length, upc in (upd.pieces or [(total, upd)]):
                seg = lane_of[off:off + length]
                off += length
                for lane, cnt in zip(*np.unique(seg, return_counts=True)):
                    mod = int(lane) if 0 <= lane < 8 else None
                    out.append((int(cnt), AVal((0,), 'i', 32, row, mod=mod),
                                upc))
            return out

        if len(a.shape) == 2 and len(cols) == 2:
            # row/lane scatter on the 2-D table
            secs = lane_sections()
        else:
            secs = sections()
        for length, ipc, upc in secs:
            lane = ipc.mod if len(a.shape) == 1 else ipc.mod
            targets = range(8) if lane is None else [lane]
            # a drop-guarded sentinel branch contributes nothing when
            # its constant is out of range.
            if (lane is None and ipc.alt is not None and guarded
                    and ipc.mod is None):
                pass
            for ln in targets:
                pre = lanes[ln]
                if op == "set":
                    lanes[ln] = _join(pre, upc.iv)
                elif op == "max":
                    lanes[ln] = (pre[0], max(pre[1], upc.iv[1]))
                elif upc.sat is not None and upc.sat[0] == ln \
                        and upc.iv[0] >= 0:
                    lanes[ln] = (pre[0], max(pre[1], upc.sat[1]))
                elif upc.exch is not None and upc.exch[0] == ln:
                    lanes[ln] = _join(pre, upc.exch[1])
                elif upc.iv == (0, 0):
                    pass
                else:
                    lanes[ln] = (pre[0] + _pmul(length, min(0, upc.iv[0])),
                                 pre[1] + _pmul(length, max(0, upc.iv[1])))
        out.lanes = tuple(lanes)
        lo = min(l[0] for l in lanes)
        hi = max(l[1] for l in lanes)
        out.iv = (lo, hi)
        return out

    def p_scatter_add(self, eqn, ins):
        return self._scatter_common(eqn, ins, "add")

    def p_scatter(self, eqn, ins):
        return self._scatter_common(eqn, ins, "set")

    def p_scatter_max(self, eqn, ins):
        return self._scatter_common(eqn, ins, "max")

    def p_scatter_min(self, eqn, ins):
        a, idx, upd = ins
        out = self._scatter_common(eqn, ins, "set")
        return out

    def p_dynamic_slice(self, eqn, ins):
        a, *starts = ins
        shape = eqn.params["slice_sizes"]
        if a.const is not None and all(s.const is not None
                                       for s in starts):
            import numpy as np
            st = [int(np.clip(s.const, 0, d - z)) for s, d, z in
                  zip(starts, a.shape, shape)]
            sl = tuple(slice(s, s + z) for s, z in zip(st, shape))
            return AVal.of_const(a.const[sl])
        out = a.plain(shape=tuple(shape))
        # single-row fetch from the packed table (`table[scalar]` is a
        # dynamic_slice + squeeze): rows keep per-lane structure —
        # dynamic_slice clamps its start, so the read is always in
        # bounds.
        if (a.lanes is not None and len(a.shape) == 2
                and tuple(shape) == (1, a.shape[1])):
            out = AVal(tuple(shape), a.kind, a.bits, a.iv, lanes=a.lanes)
            iv = a.lanes[0]
            for l in a.lanes[1:]:
                iv = _join(iv, l)
            out.iv = iv
            return out
        # single-cell fetch `table[row, LANE]` with a constant lane
        # column: the cell's interval is that lane's interval.
        if (a.lanes is not None and len(a.shape) == 2
                and a.shape[1] == len(a.lanes)
                and tuple(shape) == (1, 1) and len(starts) == 2):
            c = starts[1].scalar_const
            if c is not None and 0 <= int(c) < len(a.lanes):
                lane = int(c)
                out = AVal(tuple(shape), a.kind, a.bits, a.lanes[lane])
                out.lane_src = (id(a.lanes), lane)
                return out
        if a.pieces is not None:
            iv = a.pieces[0][1].iv
            for _, p in a.pieces[1:]:
                iv = _join(iv, p.iv)
            out.iv = iv
        return out

    def p_dynamic_update_slice(self, eqn, ins):
        a, upd, *starts = ins
        return a.plain(iv=_join(a.iv, upd.iv))

    def p_clamp(self, eqn, ins):
        lo, x, hi = ins

        def c(a, b, d):
            return min(max(a, b), d)
        return x.plain(iv=(c(lo.iv[0], x.iv[0], hi.iv[0]),
                           c(lo.iv[1], x.iv[1], hi.iv[1])))

    # -- higher order ------------------------------------------------------ #

    def p_pjit(self, eqn, ins):
        return self.eval_closed(eqn.params["jaxpr"], ins)

    def p_closed_call(self, eqn, ins):
        return self.eval_closed(eqn.params["call_jaxpr"], ins)

    def p_custom_jvp_call(self, eqn, ins):
        return self.eval_closed(eqn.params["call_jaxpr"], ins)

    def p_custom_vjp_call(self, eqn, ins):
        return self.eval_closed(eqn.params["call_jaxpr"], ins)

    def p_remat(self, eqn, ins):
        return self.eval_jaxpr(eqn.params["jaxpr"], [], ins)

    def p_cond(self, eqn, ins):
        pred, *ops = ins
        branches = eqn.params["branches"]
        c = pred.scalar_const
        if c is not None:
            return self.eval_closed(branches[int(c)], ops)
        lo = max(int(pred.iv[0]), 0)
        hi = min(int(pred.iv[1]), len(branches) - 1)
        outs = None
        for b in range(lo, hi + 1):
            o = self.eval_closed(branches[b], ops)
            outs = o if outs is None else [
                self._joinv(x, y) for x, y in zip(outs, o)]
        return outs

    def p_scan(self, eqn, ins):
        nc = eqn.params["num_consts"]
        nk = eqn.params["num_carry"]
        T = eqn.params["length"]
        body = eqn.params["jaxpr"]
        consts, init, xs = ins[:nc], ins[nc:nc + nk], ins[nc + nk:]
        xelems = [x.plain(shape=x.shape[1:]) for x in xs]

        def run(carry):
            return self.eval_closed(body, consts + list(carry) + xelems)

        if T == 0:
            return list(init) + [
                AVal((0,) + tuple(x.shape[1:]), x.kind, x.bits, x.iv)
                for x in xs] if len(eqn.outvars) > nk else list(init)

        # Affine widening S_t ⊆ base + t·h: base joins the init with
        # the first abstract iteration (absorbing init-sentinel jumps),
        # h is the steady-state slope measured on the SECOND iteration.
        # Verified at both ends (t=0→1 and t=T-1→T); the loop bodies in
        # scope (max-plus pipelines, counters, scatter-set fills) are
        # 1-Lipschitz in the carry, so the two endpoint checks cover
        # the interior steps. A failed component widens to top and the
        # verification re-runs until the choice is stable.
        outs1 = run(init)
        base = [i0.plain(iv=_join(i0.iv, o.iv))
                for i0, o in zip(init, outs1[:nk])]
        outs2 = run(base)
        h = []
        for b, o in zip(base, outs2[:nk]):
            hlo = (min(0, o.iv[0] - b.iv[0]) if -INF < b.iv[0]
                   and -INF < o.iv[0] else -INF)
            hhi = (max(0, o.iv[1] - b.iv[1]) if b.iv[1] < INF
                   and o.iv[1] < INF else INF)
            h.append((hlo, hhi))

        def shift(t):
            out = []
            for b, (hl, hh) in zip(base, h):
                lo = b.iv[0] + _pmul(t, hl) if -INF < b.iv[0] \
                    and -INF < hl else -INF
                hi = b.iv[1] + _pmul(t, hh) if b.iv[1] < INF \
                    and hh < INF else INF
                out.append(b.plain(iv=(lo, hi)))
            return out

        wide = [False] * nk
        for _ in range(3):
            cand = shift(T)
            step1 = shift(1)
            carry3 = [b.plain(iv=_dtype_top(b.kind, b.bits)) if w else c
                      for w, b, c in zip(wide, base, shift(T - 1))]
            outs3 = run(carry3)
            changed = False
            for k in range(nk):
                if wide[k]:
                    continue
                ok = (_contains(step1[k].iv, outs2[k].iv)
                      and _contains(cand[k].iv, outs3[k].iv))
                if not ok:
                    wide[k] = True
                    changed = True
            if not changed:
                break
        final = []
        for k, (i0, c) in enumerate(zip(init, cand)):
            if wide[k]:
                final.append(i0.plain(iv=_dtype_top(i0.kind, i0.bits)))
            else:
                final.append(i0.plain(iv=c.iv))
        ys = []
        for o1, o3 in zip(outs1[nk:], outs3[nk:]):
            ys.append(AVal((T,) + o3.shape, o3.kind, o3.bits,
                           _join(o1.iv, o3.iv)))
        return final + ys

    def p_while(self, eqn, ins):
        raise NotImplementedError("while")


# --------------------------------------------------------------------------- #
# Binding the declared budget to program inputs.
# --------------------------------------------------------------------------- #


def _table_aval(var, n_pages, epoch_hi):
    inv = _lane_invariants(n_pages, epoch_hi)
    lanes = tuple(inv)
    lo = min(l[0] for l in lanes)
    hi = max(l[1] for l in lanes)
    return AVal(var.aval.shape, 'i', 32, (lo, hi), lanes=lanes)


def _field_iv(field, cfg, time_hi, n_chunks, nd, counter_hi=0):
    n_pages = cfg.n_pages
    if field in _TIME_FIELDS:
        return (0, time_hi)
    if field == "chunk_idx":
        return (0, n_chunks)
    if field == "dma.swaps_done":
        return (0, n_chunks)
    ind = _inductive_fields(n_pages, nd)
    if field in ind:
        return ind[field]
    if field.startswith("counters."):
        # event counters: the origin run measures the per-chunk rate,
        # the budget run re-declares them under rate × n_chunks.
        return (0, counter_hi)
    return None


def bind_invar(name, var, cfg, time_hi, n_chunks, nd, notes,
               counter_hi=0):
    """Declared AVal for one named program input, or None + note."""
    kind, bits = _dtype_kind(var.aval.dtype)
    shape = var.aval.shape

    def mk(lo, hi):
        return AVal(shape, kind, bits, (lo, hi))

    if name == "table" or name == "state.table":
        return _table_aval(var, cfg.n_pages, time_hi)
    for pref in ("sc.", "state."):
        if name.startswith(pref):
            iv = _field_iv(name[len(pref):], cfg, time_hi, n_chunks, nd,
                           counter_hi)
            if iv is not None:
                return mk(*iv)
            break
    if name == "bank_free" or name == "state.bank_free":
        return mk(0, time_hi)
    if name.startswith("params."):
        leaf = name.split(".", 1)[1]
        if leaf not in PARAM_BOUNDS:
            notes.append(f"params leaf `{leaf}` has no declared interval "
                         "in PARAM_BOUNDS — the budget declaration must "
                         "cover every runtime knob")
            return AVal.top_for(var.aval)
        lo, hi = PARAM_BOUNDS[leaf]
        if hi is None:
            hi = cfg.n_pages
        return mk(lo, hi)
    base = name.split(".")[-1]
    if base in ("page",):
        return mk(0, cfg.n_pages - 1)
    if base in TRACE_BOUNDS:
        lo, hi = TRACE_BOUNDS[base]
        return mk(lo, hi if hi is not None else cfg.n_pages - 1)
    if base in ("is_write", "valid"):
        return mk(0, 1)
    if name.startswith("faults."):
        return mk(-1, 1 << 30)
    notes.append(f"program input `{name}` has no declared interval")
    return AVal.top_for(var.aval)


# --------------------------------------------------------------------------- #
# Checking one program (origin run for growth, budget run for proofs).
# --------------------------------------------------------------------------- #


def _out_field(name):
    for pref in ("sc.", "state.", "out.sc.", "out.state."):
        if name.startswith(pref):
            return name[len(pref):]
    return name


def check_program(label, jaxpr, consts, invars, in_names, out_names,
                  cfg, nd=2):
    """Run the two-phase budget analysis on one program (all inputs
    bound by name from the declared budget).

    Returns ``(findings, bounds)``; bounds is the per-program proved
    summary that lands in the CLI report."""
    notes: list = []

    def bind(time_hi, counter_hi=0):
        return [bind_invar(name, var, cfg, time_hi, N_CHUNKS_BUDGET, nd,
                           notes, counter_hi)
                for name, var in zip(in_names, invars)]

    findings, bounds = _check_core(label, jaxpr, bind, out_names, cfg,
                                   nd, consts=consts)
    for n in dict.fromkeys(notes):
        findings.append(Finding(f"<{label}>", 0, PASS, f"[{label}] {n}"))
    return findings, bounds


# --------------------------------------------------------------------------- #
# Repo entry points.
# --------------------------------------------------------------------------- #

#: Filled by run_repo: per-program proved-bounds summaries for the CLI
#: report (`--report` embeds it under "proved_bounds").
LAST_BOUNDS: list = []


def validate_budget(cfg) -> list[str]:
    """The repo's own config must sit inside the declared budget."""
    import jax
    from repro.core.config import RuntimeParams
    params = RuntimeParams.from_config(cfg)
    out = []
    for name, leaf in params._asdict().items():
        if name not in PARAM_BOUNDS:
            out.append(f"params leaf `{name}` missing from PARAM_BOUNDS")
            continue
        lo, hi = PARAM_BOUNDS[name]
        if hi is None:
            hi = cfg.n_pages
        v = float(leaf)
        if not (lo <= v <= hi):
            out.append(f"config value {name}={v} outside the declared "
                       f"budget interval [{lo}, {hi}]")
    return out


def _pragma_filter(findings, root):
    """Apply source pragmas per referenced file (jaxpr locs point into
    real sources)."""
    by_path: dict = {}
    out = []
    for f in findings:
        p = root / f.path
        if f.path.startswith("<") or not p.is_file():
            out.append(f)
            continue
        by_path.setdefault(p, []).append(f)
    for p, fs in by_path.items():
        out.extend(apply_pragmas(fs, p.read_text()))
    return out


def run_repo(root: pathlib.Path) -> list[Finding]:
    from repro.core.config import small_platform
    from repro.core.emulator import as_registry

    cfg = small_platform()
    registry = as_registry(None)
    findings: list[Finding] = []
    LAST_BOUNDS.clear()

    for msg in validate_budget(cfg):
        findings.append(Finding("src/repro/analysis/ranges.py", 0, PASS,
                                msg))

    # scan path: the chunk body of the compiled `lax.scan`.
    info, err = scan_body_info(cfg, registry)
    if err is not None:
        findings.append(Finding("src/repro/core/emulator.py", 1, PASS,
                                err))
    else:
        f, b = _check_scan_path(info, cfg)
        findings += f
        LAST_BOUNDS.append(b)

    # step_ref paths: params as traced inputs -> parametric proofs.
    for seq, label in ((True, "pallas-body"), (False, "jnp-ref")):
        jaxpr, names, out_names = trace_step_ref(
            cfg, registry, seq, params_as_inputs=True)
        f, b = check_program(label, jaxpr.jaxpr, jaxpr.consts,
                             jaxpr.jaxpr.invars, names, out_names, cfg)
        findings += f
        LAST_BOUNDS.append(b)
    return _pragma_filter(findings, root)


def _check_scan_path(info, cfg):
    """Bind the scan body: evaluate the outer jaxpr prefix (trace/faults
    declared) to get the scan's const/xs operands, then run the budget
    analysis on the body with the carry declared."""
    outer = info["outer"]
    names = info["outer_names"]
    notes: list = []
    pre = Interp(track_overflow=False)
    env = {}
    import numpy as np
    for v, c in zip(outer.jaxpr.constvars, outer.consts):
        env[v] = AVal.of_const(np.asarray(c))
    for v, name in zip(outer.jaxpr.invars, names):
        env[v] = bind_invar(name, v, cfg, 0, N_CHUNKS_BUDGET, 2, notes)

    target = info["scan_eqn"]
    for eqn in outer.jaxpr.eqns:
        if eqn is target:
            break
        ins = [env[x] if not hasattr(x, "val") else AVal.of_const(x.val)
               for x in eqn.invars]
        fn = getattr(pre, "p_" + eqn.primitive.name.replace("-", "_"),
                     None)
        try:
            outs = (fn(eqn, ins) if fn is not None
                    else [AVal.top_for(o.aval) for o in eqn.outvars])
            if not isinstance(outs, list):
                outs = [outs]
        except Exception:
            outs = [AVal.top_for(o.aval) for o in eqn.outvars]
        for var, a in zip(eqn.outvars, outs):
            if type(var).__name__ != "DropVar":
                env[var] = a

    nc, nk = info["num_consts"], info["num_carry"]
    body = info["body"]

    def read_operand(x):
        if hasattr(x, "val"):
            return AVal.of_const(np.asarray(x.val))
        return env.get(x, AVal.top_for(x.aval))

    const_avs = [read_operand(x) for x in target.invars[:nc]]
    xs_avs = [read_operand(x) for x in target.invars[nc + nk:]]
    xelems = [x.plain(shape=x.shape[1:]) for x in xs_avs]

    core = body.jaxpr if hasattr(body, "jaxpr") else body
    bconsts = list(getattr(body, "consts", ()))
    carry_vars = core.invars[nc:nc + nk]
    out_names = info["carry_names"] + [
        f"ys{i}" for i in range(len(core.outvars) - nk)]

    def bind(time_hi, counter_hi=0):
        # scan consts and xs slices come from the evaluated outer
        # prefix (params, trace columns, fault schedule — all time-
        # independent); the carry is re-declared per phase.
        carry = [bind_invar(name, var, cfg, time_hi, N_CHUNKS_BUDGET,
                            2, notes, counter_hi)
                 for name, var in zip(info["carry_names"], carry_vars)]
        return const_avs + carry + xelems

    findings, bounds = _check_core(
        "scan-path", core, bind, out_names, cfg, nd=2, consts=bconsts)
    for n in dict.fromkeys(notes):
        findings.append(Finding("<scan-path>", 0, PASS,
                                f"[scan-path] {n}"))
    return findings, bounds


def _check_core(label, body, bind, out_names, cfg, nd,
                consts=()):
    findings: list = []
    bounds = {"label": label, "n_chunks_budget": N_CHUNKS_BUDGET}

    def program_finding(msg):
        findings.append(Finding(f"<{label}>", 0, PASS, f"[{label}] {msg}"))

    interp_b = Interp(track_overflow=False)
    try:
        outs_b = interp_b.eval_jaxpr(body, list(consts), bind(0))
    except Exception as e:
        program_finding(f"abstract evaluation failed: {type(e).__name__}: "
                        f"{e}")
        return findings, bounds
    for loc, msg in interp_b.gaps:
        findings.append(Finding(loc[0], loc[1], PASS,
                                f"[{label}] {msg} — interval analysis has "
                                "a soundness hole here"))
    G = 1
    mono_rates = {}
    for name, o in zip(out_names, outs_b):
        field = _out_field(name)
        if field in _TIME_FIELDS:
            if o.iv[1] == INF or o.iv[1] > INT32[1]:
                program_finding(
                    f"per-chunk growth of time field `{field}` is "
                    f"unbounded ({o.iv}) — cannot establish an int32 "
                    "horizon")
                return findings, bounds
            G = max(G, int(o.iv[1]))
        elif ((field in _MONO_FIELDS or field.startswith("counters."))
                and o.kind == 'i'):
            if o.iv[1] == INF:
                program_finding(
                    f"per-chunk growth of counter `{field}` is unbounded")
            else:
                mono_rates[field] = max(1, int(o.iv[1]))
    horizon = INT32[1] // max(G, 1)
    bounds["per_chunk_growth"] = G
    bounds["int32_horizon_chunks"] = horizon
    if horizon < N_CHUNKS_BUDGET:
        program_finding(
            f"int32 clock horizon is {horizon} chunks (per-chunk growth "
            f"{G}) but the declared budget is {N_CHUNKS_BUDGET} chunks — "
            "a budgeted run can overflow the cycle counters")

    B = G * N_CHUNKS_BUDGET
    bounds["cycle_budget"] = B
    counter_hi = max(
        [r for f, r in mono_rates.items() if f.startswith("counters.")],
        default=0) * N_CHUNKS_BUDGET
    interp = Interp(track_overflow=True)
    try:
        outs = interp.eval_jaxpr(body, list(consts),
                                 bind(B, min(counter_hi, INT32[1])))
    except Exception as e:
        program_finding(f"abstract evaluation (budget run) failed: "
                        f"{type(e).__name__}: {e}")
        return findings, bounds
    for loc, msg in interp.gaps:
        findings.append(Finding(loc[0], loc[1], PASS,
                                f"[{label}] {msg} — interval analysis has "
                                "a soundness hole here"))
    for loc, msg in interp.index_findings:
        findings.append(Finding(loc[0], loc[1], PASS, f"[{label}] {msg}"))
    for loc, prim, iv in interp.overflow:
        findings.append(Finding(
            loc[0], loc[1], PASS,
            f"[{label}] int32 `{prim}` can overflow under the declared "
            f"budget (interval {iv}) — saturate or widen it"))
    bounds["table_gathers_proved"] = interp.n_proved
    bounds["table_gathers_guarded"] = interp.n_guarded

    from repro.core import table as table_lib
    inv = _lane_invariants(cfg.n_pages, B)
    for name, o in zip(out_names, outs):
        field = _out_field(name)
        if field == "table" and o.lanes is None:
            program_finding("the table output lost its per-lane interval "
                            "lineage — the lane proofs do not cover this "
                            "program")
        elif field == "table":
            lane_bounds = {}
            for ln in range(8):
                lane_bounds[_LANE_NAMES[ln]] = [o.lanes[ln][0],
                                                o.lanes[ln][1]]
                if ln in _INDUCTIVE_LANES and not _contains(
                        inv[ln], o.lanes[ln]):
                    program_finding(
                        f"{_LANE_NAMES[ln]} lane not inductive: declared "
                        f"{inv[ln]}, one chunk reaches {o.lanes[ln]} — "
                        "an unsaturated accumulation reached the scan "
                        "carry")
                if o.lanes[ln][1] != INF and o.lanes[ln][1] > INT32[1]:
                    program_finding(
                        f"{_LANE_NAMES[ln]} lane can exceed int32 "
                        f"({o.lanes[ln]})")
            epoch = o.lanes[table_lib.EPOCH]
            if epoch[1] != INF and epoch[1] > INT32[1]:
                program_finding(f"EPOCH lane exceeds int32 ({epoch})")
            bounds["lanes"] = lane_bounds
        elif field in _TIME_FIELDS:
            if o.iv[1] == INF or o.iv[1] > INT32[1]:
                program_finding(
                    f"time field `{field}` exceeds int32 under the "
                    f"budget ({o.iv})")
        elif ((field in _MONO_FIELDS or field.startswith("counters."))
                and o.kind == 'i'):
            rate = mono_rates.get(field, 1)
            if rate * N_CHUNKS_BUDGET > INT32[1]:
                program_finding(
                    f"monotone counter `{field}` (rate {rate}/chunk) "
                    "overflows int32 under the budget")
        else:
            ind = _inductive_fields(cfg.n_pages, nd)
            if field in ind and not _contains(ind[field], o.iv):
                program_finding(
                    f"carry field `{field}` not inductive: declared "
                    f"{ind[field]}, one chunk reaches {o.iv}")
    return findings, bounds


#: Fixture inputs: non-table ints are declared in [0, 2^20].
_FIXTURE_INT_HI = 1 << 20


def run_paths(paths) -> list[Finding]:
    import jax

    from .common import fixture_case

    findings: list[Finding] = []
    for path in paths:
        case = fixture_case(path)
        if not case or case.get("kind") != "ranges":
            continue
        fn, args = case["make"]()
        jaxpr = jax.make_jaxpr(fn)(*args)
        findings += check_fixture(jaxpr, pathlib.Path(path).stem)
    return findings


def check_fixture(jaxpr, label):
    """Budget analysis for a fixture: argument 0 is the table (2-D
    (n, 8) or flat), other ints are bound to the fixture budget."""
    core = jaxpr.jaxpr
    findings: list = []

    def bind(time_hi, counter_hi=0):
        avs = []
        for i, v in enumerate(core.invars):
            kind, bits = _dtype_kind(v.aval.dtype)
            shape = tuple(v.aval.shape)
            if i == 0:
                n_pages = (shape[0] if len(shape) == 2
                           else shape[0] // 8)
                avs.append(_table_aval(v, n_pages, time_hi))
            elif kind == 'b':
                avs.append(AVal(shape, 'b', 1, (0, 1)))
            elif kind == 'i':
                avs.append(AVal(shape, kind, bits, (0, _FIXTURE_INT_HI)))
            else:
                avs.append(AVal(shape, kind, bits, (0.0, INF)))
        return avs

    tshape = tuple(core.invars[0].aval.shape)
    n_pages = tshape[0] if len(tshape) == 2 else tshape[0] // 8

    class _Cfg:
        pass

    cfg = _Cfg()
    cfg.n_pages = n_pages
    out_names = []
    for v in core.outvars:
        if tuple(v.aval.shape) in (tshape, (n_pages, 8), (n_pages * 8,)):
            out_names.append("table")
        else:
            out_names.append("y")
    f, _b = _check_core(label, core, bind, out_names, cfg, nd=2)
    return [Finding(x.path, x.line, PASS, x.message) for x in f]
