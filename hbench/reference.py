"""Plain reference of the emulated platform's chunked semantics, in numpy.

This is the yardstick that decides ``correct``. It imports nothing of the
program under test: it is written from the platform's stated semantics
(arXiv:2011.04567 §III, and the chunk contract the configuration files
name), on plain numpy arrays, one chunk at a time.

A chunk of requests goes through:

1. RX link: request ``i`` issues at ``clock + issue_gap * (i + 1)``; the
   link serialises headers (16 B) and write payloads, then adds half the
   link round trip.
2. Lookup against the *pre-chunk* table, then the DMA redirect: a request
   to a page in the in-flight swap whose sub-block has already been
   exchanged goes to the counterpart's pre-swap location.
3. Per-device bank queues (``bank = device * n_banks + frame % n_banks``),
   each first-come first-served.
4. In-order return: a response leaves no earlier than every response
   before it (tag matching, §III-C); then the TX link, then half the round
   trip.
5. Boundary commit: hotness (+1 per access, +``write_weight`` per write
   under ``write_bias``), demand-write wear per slow frame, the swap
   commit if the engine finished by ``now``, the aging shift every
   ``decay_every`` chunks, and the global min-wear register on those
   boundaries.
6. Policy: the hottest slow, unpinned page of the chunk against the first
   unpinned CLOCK victim within eight fast frames; a swap starts if the
   single DMA engine is idle.

Every sequential recurrence is the literal per-request loop in meaning;
the code evaluates it in closed form (running maxima of prefix sums),
which ``hbench/tests/test_reference.py`` checks against the loops.

The reference covers what the configurations in ``configs/`` run: the
``hotness``, ``write_bias`` and ``static`` policies, pin contracts, no
fault plan and no endurance retirement. It raises where a run would leave
that scope (a saturating accumulator, a 32-bit overflow), rather than
guess.

``redirect=False`` is the control of the correctness check: the same
reference with the swap-progress redirect guarantee broken.
"""
from __future__ import annotations

import math

import numpy as np

FAST, SLOW = 0, 1
# Packed-row lane order of the platform's redirection table.
LANES = ("device", "frame", "hotness", "wear", "owner", "epoch", "flags",
         "pad")
PIN_FAST, PIN_SLOW, POISONED, RETIRED = 1, 2, 4, 8
PINNED = PIN_FAST | PIN_SLOW
NEG = -(2 ** 30)          # arrival time of an invalid (padding) slot
CLOCK_WINDOW = 8          # frames a policy probes for an unpinned victim
CAP = 1 << 29             # saturation point of the hotness and wear lanes
I32 = (-(2 ** 31), 2 ** 31 - 1)
POLICIES = ("hotness", "write_bias", "static")


def _ceil_div(size: np.ndarray, bytes_per_cycle: float) -> np.ndarray:
    """Transfer cycles, computed in float32 as the platform does."""
    q = size.astype(np.float32) / np.float32(bytes_per_cycle)
    return np.ceil(q).astype(np.int64)


def maxplus(arrival: np.ndarray, service: np.ndarray) -> np.ndarray:
    """``done[i] = max(arrival[i], done[i-1]) + service[i]`` (done[-1] is
    minus infinity), as ``max_j<=i (arrival[j] + sum service[j..i])``."""
    cs = np.cumsum(service)
    return np.maximum.accumulate(arrival - (cs - service)) + cs


def bank_queues(arrival, service, bank, bank_free):
    """One first-come first-served queue per bank, in request order:
    ``done = max(arrival, free[bank]) + service; free[bank] = done``.
    Returns (done, new bank_free)."""
    order = np.argsort(bank, kind="stable")
    b = bank[order]
    head = np.empty(len(b), bool)
    head[0] = True
    head[1:] = b[1:] != b[:-1]
    a = arrival[order].copy()
    a[head] = np.maximum(a[head], bank_free[b[head]])
    s = service[order]
    cs = np.cumsum(s)
    m = a - (cs - s)
    # A running max that restarts at each bank's first request: offset
    # each bank's segment above every earlier one.
    seg = np.cumsum(head)
    big = np.int64(1) << 40
    run = np.maximum.accumulate(m + seg * big) - seg * big
    done_sorted = run + cs
    done = np.empty_like(done_sorted)
    done[order] = done_sorted
    tail = np.empty(len(b), bool)
    tail[-1] = True
    tail[:-1] = b[1:] != b[:-1]
    free = bank_free.copy()
    free[b[tail]] = done_sorted[tail]
    return done, free


class Platform:
    """Static geometry and one design point's parameters, read from a
    configuration file's ``platform`` and ``technologies`` sections."""

    def __init__(self, platform: dict, technologies: dict,
                 overrides: dict | None = None):
        p = dict(platform)
        p.update(overrides or {})
        fast = technologies[p["fast"]]
        slow = technologies[p["slow"]]
        self.n_pages = p["n_fast_pages"] + p["n_slow_pages"]
        self.n_fast = p["n_fast_pages"]
        self.chunk = p["chunk"]
        self.n_banks = p["n_banks"]
        self.issue_gap = p["issue_gap"]
        self.link_lat = p["link_lat"]
        self.link_bpc = p["link_bytes_per_cycle"]
        self.fast = (fast["read_lat"], fast["write_lat"],
                     fast["bytes_per_cycle"])
        self.slow = (slow["read_lat"], slow["write_lat"],
                     slow["bytes_per_cycle"])
        self.subblock = p["subblock"]
        self.subblocks_per_page = p["page_size"] // p["subblock"]
        dma_cycles = max(1, round(p["subblock"] / p["dma_bytes_per_cycle"]))
        self.exchange = 3 * dma_cycles
        self.swap_duration = self.subblocks_per_page * self.exchange
        self.migration_wear = p["page_size"] // p["line_size"]
        self.policy = p["policy"]
        if self.policy not in POLICIES:
            raise ValueError(f"the reference has no policy {self.policy!r}")
        self.hot_threshold = p["hot_threshold"]
        self.decay_shift = p["hotness_decay_shift"]
        self.decay_every = p["decay_every"]
        self.write_weight = (p["write_weight"]
                             if self.policy == "write_bias" else 1)
        self.pin_fast_fraction = p["pin_fast_fraction"]
        self.power = tuple(np.float32(p[k]) for k in (
            "power_pj_per_bit_fast", "power_pj_per_bit_slow_read",
            "power_pj_per_bit_slow_write"))
        if p["endurance_budget"] > 0:
            raise ValueError("the reference does not model retirement")


INT_COUNTERS = ("reads_fast", "writes_fast", "reads_slow", "writes_slow",
                "n_reads", "max_latency", "reorder_held", "poison_faults",
                "frames_retired", "transient_faults")
FLOAT_COUNTERS = ("bytes_read_fast", "bytes_write_fast", "bytes_read_slow",
                  "bytes_write_slow", "sum_read_latency", "energy_pj")
SCALARS = ("clock_ptr", "chunk_idx", "dma_active", "dma_page_a",
           "dma_page_b", "dma_start", "swaps_done", "clock",
           "link_free_rx", "link_free_tx", "last_return", "rescue_page",
           "min_wear", "fault_cursor")


class State:
    """The platform's carried state: the redirection table, one array per
    lane, the bank free times, the scalar registers and the counters."""

    def __init__(self, pf: Platform):
        n, nf = pf.n_pages, pf.n_fast
        ar = np.arange(n, dtype=np.int64)
        self.lanes = {k: np.zeros(n, np.int64) for k in LANES}
        self.lanes["device"][:] = np.where(ar < nf, FAST, SLOW)
        self.lanes["frame"][:] = np.where(ar < nf, ar, ar - nf)
        self.lanes["owner"][:] = ar
        n_pin = math.floor(np.float32(pf.pin_fast_fraction) * np.float32(nf))
        self.lanes["flags"][:n_pin] = PIN_FAST
        self.bank_free = np.zeros(2 * pf.n_banks, np.int64)
        self.s = {k: 0 for k in SCALARS}
        self.s.update(dma_page_a=-1, dma_page_b=-1, rescue_page=-1)
        self.c = {k: 0 for k in INT_COUNTERS}
        self.c.update({k: np.float32(0) for k in FLOAT_COUNTERS})

    def table(self) -> np.ndarray:
        """The packed ``[n_pages, 8]`` table."""
        return np.stack([self.lanes[k] for k in LANES], axis=1)

    def check_range(self) -> None:
        for k, v in self.lanes.items():
            if v.min() < I32[0] or v.max() > I32[1]:
                raise OverflowError(f"table lane {k} leaves int32")
        for k in ("hotness", "wear"):
            if self.lanes[k].max(initial=0) > CAP:
                raise OverflowError(f"{k} lane reached its saturation point")
        for k, v in list(self.s.items()) + [("bank_free",
                                             self.bank_free.max())]:
            if not I32[0] <= int(v) <= I32[1]:
                raise OverflowError(f"register {k} leaves int32")


def _in_chunks(x: np.ndarray, chunk: int) -> np.ndarray:
    """Prefix sums of ``x`` restarting at every chunk."""
    return np.cumsum(x.reshape(-1, chunk), axis=1).reshape(-1)


def run(pf: Platform, st: State, page, offset, is_write, size, valid=None,
        *, redirect: bool = True) -> dict:
    """Emulate a chunk-multiple trace, updating ``st``. Returns the
    per-request ``returns`` (0 where invalid), ``latency`` and ``device``
    (-1 where invalid) as int64 arrays.

    ``redirect=False`` is the control of the correctness check: the same
    platform with the swap-progress redirect (§III-D) left out, so a
    request to a page mid-migration reads the stale location."""
    n, chunk = len(page), pf.chunk
    if n % chunk:
        raise ValueError("trace length is not a multiple of the chunk")
    valid = np.ones(n, bool) if valid is None else np.asarray(valid, bool)
    page = np.asarray(page, np.int64)
    offset = np.asarray(offset, np.int64)
    w = np.asarray(is_write, bool)
    size = np.where(valid, np.asarray(size, np.int64), 0)
    # Everything that depends on a request alone, computed once.
    rd_f, wr_f, bpc_f = pf.fast
    rd_s, wr_s, bpc_s = pf.slow
    rx_srv = np.where(valid, _ceil_div(np.where(w, size, 16), pf.link_bpc),
                      0)
    tx_srv = np.where(valid, _ceil_div(np.where(w, 16, size), pf.link_bpc),
                      0)
    rx_cs, tx_cs = _in_chunks(rx_srv, chunk), _in_chunks(tx_srv, chunk)
    req = {
        "page": page, "offset": offset, "is_write": w, "size": size,
        "valid": valid,
        "rx_cs": rx_cs, "rx_excl": rx_cs - rx_srv,
        "tx_cs": tx_cs, "tx_excl": tx_cs - tx_srv,
        "half_link": np.where(valid, pf.link_lat // 2, 0),
        "media_fast": np.where(valid, np.where(w, wr_f, rd_f)
                               + _ceil_div(size, bpc_f), 0),
        "media_slow": np.where(valid, np.where(w, wr_s, rd_s)
                               + _ceil_div(size, bpc_s), 0),
        "hot_w": np.where(valid, 1 + (pf.write_weight - 1) * w, 0),
        "cls": np.where(valid, 2 * w, 4),
        "read": valid & ~w,
    }
    gaps = pf.issue_gap * np.arange(1, chunk + 1, dtype=np.int64)
    out = {k: np.empty(n, np.int64) for k in ("returns", "latency",
                                              "device")}
    for c0 in range(0, n, chunk):
        sl = slice(c0, c0 + chunk)
        q = {k: v[sl] for k, v in req.items()}
        r = _chunk(pf, st, q, gaps, bool(q["valid"].all()), redirect)
        for k in out:
            out[k][sl] = r[k]
    st.check_range()
    return out


def _chunk(pf: Platform, st: State, q: dict, gaps, full: bool,
           redirect: bool) -> dict:
    L, s = st.lanes, st.s
    page, valid = q["page"], q["valid"]
    n = len(page)
    any_valid = full or bool(valid.any())

    # 1. RX link.
    issue = s["clock"] + gaps
    rx_arr = np.maximum(issue, s["link_free_rx"])
    if not full:
        issue = np.where(valid, issue, NEG)
        rx_arr = np.where(valid, rx_arr, NEG)
    rx_done = np.maximum.accumulate(rx_arr - q["rx_excl"]) + q["rx_cs"]
    arrive = rx_done + q["half_link"]

    # 2. Lookup against the pre-chunk table, then the swap redirect.
    pg = page if full else np.clip(page, 0, pf.n_pages - 1)
    dev, frm = L["device"][pg], L["frame"][pg]
    a, b = s["dma_page_a"], s["dma_page_b"]
    ia, ib = max(a, 0), max(b, 0)
    da, fa, ea = L["device"][ia], L["frame"][ia], L["epoch"][ia]
    db, fb, eb = L["device"][ib], L["frame"][ib], L["epoch"][ib]
    if s["dma_active"] and redirect:
        prog = np.clip((arrive - s["dma_start"]) // pf.exchange, 0,
                       pf.subblocks_per_page)
        moved = q["offset"] // pf.subblock < prog
        to_b = moved & (page == a)
        to_a = moved & (page == b)
        dev = np.where(to_b, db, np.where(to_a, da, dev))
        frm = np.where(to_b, fb, np.where(to_a, fa, frm))
    poisoned = valid & ((L["flags"][pg] & POISONED) != 0)

    # 3. Bank queues and media access.
    slow = dev == SLOW
    med_done, st.bank_free = bank_queues(
        arrive, np.where(slow, q["media_slow"], q["media_fast"]),
        dev * pf.n_banks + frm % pf.n_banks, st.bank_free)

    # 4. In-order return (tag matching), then the TX link.
    complete = med_done if full else np.where(valid, med_done, NEG)
    ordered = np.maximum(np.maximum.accumulate(complete), s["last_return"])
    later = ordered > med_done
    held = int(np.count_nonzero(later if full else later & valid))
    tx_arr = np.maximum(ordered, s["link_free_tx"] if full else
                        np.where(valid, s["link_free_tx"], NEG))
    returns = (np.maximum.accumulate(tx_arr - q["tx_excl"]) + q["tx_cs"]
               + q["half_link"])
    latency = returns - issue
    if not full:
        latency = np.where(valid, latency, 0)

    # Counters, per post-redirect device.
    _count(pf, st, slow, q, latency, held, poisoned)

    # 5. Boundary commit, every delta against the pre-chunk table.
    if full:
        last_ret = int(returns.max())
    elif any_valid:
        last_ret = int(np.max(np.where(valid, returns, s["last_return"])))
    else:
        last_ret = s["last_return"]
    now = max(s["clock"] + pf.issue_gap * n, last_ret)
    np.add.at(L["hotness"], page, q["hot_w"])
    if L["hotness"][page].max() > CAP:
        raise OverflowError("hotness lane passed its saturation point")
    np.add.at(L["wear"], frm[q["is_write"] & valid & slow], 1)
    done = bool(s["dma_active"]) and now >= s["dma_start"] + pf.swap_duration
    if done:
        L["device"][ia], L["device"][ib] = db, da
        L["frame"][ia], L["frame"][ib] = fb, fa
        L["epoch"][ia] += now - ea
        L["epoch"][ib] += now - eb
        if db == SLOW:
            L["wear"][fb] += pf.migration_wear
        if da == SLOW:
            L["wear"][fa] += pf.migration_wear
        if db == FAST:        # the promoted page owns its new fast frame
            L["owner"][fb] = a
        s.update(dma_active=0, dma_page_a=-1, dma_page_b=-1,
                 swaps_done=s["swaps_done"] + 1)
    if s["chunk_idx"] % pf.decay_every == pf.decay_every - 1:
        L["hotness"] >>= pf.decay_shift
        n_slow = pf.n_pages - pf.n_fast
        s["min_wear"] = int(L["wear"][:n_slow].min(initial=2 ** 30))

    # 6. Policy: propose one swap against the committed table.
    if pf.policy != "static":
        ptr = s["clock_ptr"]
        eligible = (L["flags"][page] & (PINNED | RETIRED)) == 0
        ok = valid & (L["device"][page] == SLOW) & eligible
        heat = np.where(ok, L["hotness"][page], -1)
        j = int(np.argmax(heat))
        cand = int(page[j])
        frames = (ptr + np.arange(CLOCK_WINDOW)) % pf.n_fast
        owners = L["owner"][frames]
        blocked = (L["flags"][owners] & (PINNED | RETIRED)) != 0
        first = int(np.argmin(blocked))
        found = not blocked[first]
        victim = int(owners[first])
        skip = first if found else CLOCK_WINDOW
        proposed = (found and heat[j] >= pf.hot_threshold
                    and heat[j] > L["hotness"][victim])
        new_ptr = (ptr + skip + int(proposed)) % pf.n_fast
        started = (proposed and any_valid and not s["dma_active"]
                   and L["device"][cand] == SLOW
                   and L["device"][victim] == FAST
                   and not ((L["flags"][cand] | L["flags"][victim])
                            & (PINNED | RETIRED)))
        if started:
            s.update(dma_active=1, dma_page_a=cand, dma_page_b=victim,
                     dma_start=now)
        # A proposal consumes its victim frame only if the swap starts.
        if started or not proposed:
            s["clock_ptr"] = new_ptr

    s["clock"] = now
    s["chunk_idx"] += 1
    if any_valid:
        s["link_free_rx"] = int(rx_done[-1])
        s["link_free_tx"] = int(returns[-1])
    s["last_return"] = last_ret
    if full:
        return {"returns": returns, "latency": latency, "device": dev}
    return {"returns": np.where(valid, returns, 0), "latency": latency,
            "device": np.where(valid, dev, -1)}


def _count(pf, st, slow, q, latency, held, poisoned):
    c = st.c
    # Request classes: 0 fast read, 1 slow read, 2 fast write, 3 slow
    # write; 4 and 5 invalid.
    cls = q["cls"] + slow
    counts = np.bincount(cls, minlength=6)
    byts = np.bincount(cls, weights=q["size"], minlength=6)
    f32 = np.float32
    brf, brs, bwf, bws = (f32(x) for x in byts[:4])
    c["reads_fast"] += int(counts[0])
    c["reads_slow"] += int(counts[1])
    c["writes_fast"] += int(counts[2])
    c["writes_slow"] += int(counts[3])
    c["bytes_read_fast"] = f32(c["bytes_read_fast"] + brf)
    c["bytes_write_fast"] = f32(c["bytes_write_fast"] + bwf)
    c["bytes_read_slow"] = f32(c["bytes_read_slow"] + brs)
    c["bytes_write_slow"] = f32(c["bytes_write_slow"] + bws)
    p_fast, p_slow_rd, p_slow_wr = pf.power
    energy = (f32(8.0) * (brf + bwf)) * p_fast + f32(8.0) * brs * p_slow_rd \
        + f32(8.0) * bws * p_slow_wr
    c["energy_pj"] = f32(c["energy_pj"] + energy)
    c["sum_read_latency"] = f32(c["sum_read_latency"] + f32(np.sum(
        latency[q["read"]].astype(np.float32))))
    c["n_reads"] += int(counts[0] + counts[1])
    c["max_latency"] = max(c["max_latency"], int(latency.max()))
    c["reorder_held"] += held
    c["poison_faults"] += int(np.count_nonzero(poisoned))


def stamp_pins(pf: Platform, st: State, pages) -> None:
    """Pin contracts (§III-G): each page is pinned to the tier it sits on,
    or, while it is a member of the in-flight swap, to the tier the swap
    moves it to; a page on a dead frame is not pinned."""
    L, s = st.lanes, st.s
    pages = np.asarray(pages, np.int64)
    dev = L["device"][pages].copy()
    if s["dma_active"]:
        dev[pages == s["dma_page_a"]] = FAST
        dev[pages == s["dma_page_b"]] = SLOW
    cur = L["flags"][pages]
    healthy = (cur & (POISONED | RETIRED)) == 0
    bit = np.where(dev == FAST, PIN_FAST, PIN_SLOW)
    L["flags"][pages[healthy]] = (cur | bit)[healthy]


def release_pins(pf: Platform, st: State, pages) -> None:
    """Release pin contracts: both pin bits cleared."""
    pages = np.asarray(pages, np.int64)
    st.lanes["flags"][pages] = st.lanes["flags"][pages] & ~PINNED
