"""Plain reference for the grid cells: the Shuhai DRAM timing model, one
point at a time, in explicit loops.

A copy of the loop model the program keeps as its own oracle
(``core/_timing_reference.py``), with every constant it needs taken from
the configuration file instead of from the program: the memory's timings
and geometry, its address-mapping policies, and its switch fabric.  It
imports nothing of the program.

One point is ``engines`` engines, each reading (or writing, or both) an RST
stream ``T[i] = A + (i * S) mod W`` of ``n`` bursts of ``B`` bytes over its
own window, placed on one channel port (``same_channel``) or spread over
the ports of a mini-switch (``same_switch``, ``cross_switch``), with grants
of ``g`` consecutive bursts rotating over the engines of a port.  The
throughput is the slowest of three bounds on the port's command stream
(data bus and bank-group column spacing, row activations per bank, the
four-activate window), derated by refresh and scheduling overhead and
capped at the channel's wire rate; a spread placement sums its ports and
is capped by the fabric.  All arithmetic is in ``dtype``: float64 as the
configuration states, or a lower precision for the control.
"""
from __future__ import annotations

import itertools
from typing import Dict, List

import numpy as np

OPS = ("read", "write", "duplex")
# The memory's times (ns), clock (MHz) and scheduling overhead: the numbers
# the model computes with, so the numbers the control lowers in precision.
FLOAT_KEYS = ("axi_mhz", "t_refi_ns", "t_rfc_ns", "t_rc_ns", "t_ccd_l_ns",
              "t_faw_ns", "sched_overhead", "t_wr_ns", "t_wtr_ns", "t_rtw_ns")


class Memory:
    """The configuration's memory, with every float in `dtype`."""

    def __init__(self, config: dict, dtype=np.float64):
        m = config["memory"]
        self.dtype = dtype
        self.f = {k: dtype(m[k]) for k in FLOAT_KEYS}
        self.bus = int(m["bus_bytes_per_cycle"])
        self.lsb = int(m["addr_lsb"])
        self.widths = {"R": m["row_bits"], "BG": m["bankgroup_bits"],
                       "B": m["bank_bits"], "C": m["column_bits"]}
        self.cycle_ns = dtype(1e3) / self.f["axi_mhz"]
        self.peak_gbps = (dtype(self.bus) * self.f["axi_mhz"] * dtype(1e6)
                          / dtype(1e9))
        model = config["model"]
        self.max_expand = int(model["max_expand"])
        self.window = int(model["reorder_window"])
        self.policies = {name: _parse(desc) for name, desc in
                         config["policies"]["table"].items()}
        self.default_policy = config["policies"]["default"]
        self.fabric = config["fabric"]

    def cycles(self, ns):
        return ns / self.cycle_ns

    def decode(self, addrs: np.ndarray, policy: str) -> Dict[str, np.ndarray]:
        fields = self.policies[policy or self.default_policy]
        a = addrs.astype(np.int64) >> self.lsb
        out: Dict[str, np.ndarray] = {}
        pos = sum(n for _, n in fields)
        for f, n in fields:                    # most significant first
            pos -= n
            piece = (a >> pos) & ((1 << n) - 1)
            out[f] = piece if f not in out else (out[f] << n) | piece
        for f in ("R", "BG", "B", "C"):
            out.setdefault(f, np.zeros_like(a))
        return out


def _parse(desc: str) -> List[tuple]:
    """'14R-1BG-2B-5C-1BG' -> [('R', 14), ('BG', 1), ...], MSB first."""
    fields = []
    for tok in desc.split("-"):
        i = 0
        while tok[i].isdigit():
            i += 1
        fields.append((tok[i:], int(tok[:i])))
    return fields


def grant_beats(arbitration: str, burst_beats: int, txns: int) -> int:
    if arbitration == "round_robin":
        return 1
    if arbitration == "exclusive":
        return max(1, txns)
    if arbitration == "burst":
        return min(burst_beats, max(1, txns))
    raise ValueError(f"unknown arbitration {arbitration!r}")


def direction_overheads(mem: Memory, op: str):
    """(turnaround cycles per reorder window, extra cycles per activation):
    none for reads; write recovery per activation for writes; for duplex
    half of it, and one read-to-write plus one write-to-read turnaround per
    window."""
    zero = mem.dtype(0)
    if op == "read":
        return zero, zero
    wr = mem.cycles(mem.f["t_wr_ns"])
    if op == "write":
        return zero, wr
    if op == "duplex":
        return (mem.cycles(mem.f["t_rtw_ns"] + mem.f["t_wtr_ns"]),
                mem.dtype(0.5) * wr)
    raise ValueError(f"unknown op {op!r}")


def port_gbps(mem: Memory, pt: dict, engines: int) -> float:
    """Aggregate GB/s of `engines` engines sharing one channel port."""
    d = mem.dtype
    turnaround, act_extra = direction_overheads(mem, pt["op"])
    b, s, w, a = int(pt["b"]), int(pt["s"]), int(pt["w"]), int(pt["a"])
    n = min(int(pt["n"]), mem.max_expand)
    txn = a + (np.arange(n, dtype=np.int64) * s) % w
    cmds_per_txn = max(1, b // mem.bus)
    max_txns = max(16, (mem.max_expand // cmds_per_txn) // engines)
    txn = txn[:max_txns]
    bb = grant_beats(pt["arbitration"], int(pt["burst_beats"]), len(txn))
    addr_list = []
    pos = 0
    while pos < len(txn):                       # one grant round
        hi = min(pos + bb, len(txn))
        for k in range(engines):                # rotate over the engines
            for t in range(pos, hi):            # bb consecutive bursts
                base = int(txn[t]) + k * w
                for c in range(cmds_per_txn):   # burst -> column commands
                    addr_list.append(base + c * mem.bus)
        pos = hi
    addrs = np.asarray(addr_list, dtype=np.int64)
    ncmd = len(addrs)
    dec = mem.decode(addrs, pt.get("policy"))
    bank = dec["BG"] * (1 << mem.widths["B"]) + dec["B"]
    row, bg = dec["R"], dec["BG"]

    # Command issue: the data bus, and column commands to one bank group
    # spaced by tCCD_L, with as many groups in flight as the window holds.
    ccd_l = mem.cycles(mem.f["t_ccd_l_ns"])
    transitions = int(np.count_nonzero(bg[1:] != bg[:-1]))
    run_len = d(ncmd) / d(transitions + 1)
    g_cap = max(d(1), d(mem.window) / (d(2) * run_len))
    issue = d(0)
    windows = 0
    for lo in range(0, ncmd, mem.window):
        chunk = bg[lo:lo + mem.window]
        groups = min(d(len(np.unique(chunk))), g_cap)
        rate = min(d(1), groups / ccd_l)
        issue = issue + d(len(chunk)) / rate
        windows += 1
    issue = issue + turnaround * d(windows)

    # Banks: row activations to one bank serialize at tRC.
    open_row: Dict[int, int] = {}
    acts = 0
    t_rc = mem.cycles(mem.f["t_rc_ns"])
    bank_cycles = d(0)
    for lo in range(0, ncmd, mem.window):
        per_bank: Dict[int, int] = {}
        for i in range(lo, min(lo + mem.window, ncmd)):
            b_, r_ = int(bank[i]), int(row[i])
            if open_row.get(b_) != r_:
                per_bank[b_] = per_bank.get(b_, 0) + 1
                open_row[b_] = r_
                acts += 1
        if per_bank:
            bank_cycles = bank_cycles + d(max(per_bank.values())) * (
                t_rc + act_extra)

    faw = d(acts) * mem.cycles(mem.f["t_faw_ns"]) / d(4)
    steady = max(issue, bank_cycles, faw)
    eff = ((d(1) - mem.f["t_rfc_ns"] / mem.f["t_refi_ns"])
           * (d(1) - mem.f["sched_overhead"]))
    total_bytes = d(len(txn) * engines * b)
    seconds = steady * mem.cycle_ns * d(1e-9)
    gbps = total_bytes / seconds / d(1e9) * eff if seconds > 0 else d(0)
    return min(gbps, mem.peak_gbps)


def point_gbps(mem: Memory, pt: dict) -> float:
    """Aggregate GB/s of one contention point, placement included."""
    engines, placement = int(pt["engines"]), pt["placement"]
    if placement == "same_channel":
        return port_gbps(mem, pt, engines)
    fab = mem.fabric
    ports = min(engines, int(fab["axi_per_switch"]))
    counts = [engines // ports + (1 if i < engines % ports else 0)
              for i in range(ports)]
    per_count = {c: port_gbps(mem, pt, c) for c in set(counts)}
    raw = mem.dtype(0)
    for c in counts:
        raw = raw + per_count[c]
    caps = [fab["switch_agg_gbps"]]
    if placement == "cross_switch" and int(fab["mini_switches"]) > 1:
        caps.append(fab["lateral_gbps"])
    cap = mem.dtype(min(c for c in caps if c is not None))
    return min(raw, cap)


def ladder_points(axes: dict, ns: List[int], b: int) -> List[dict]:
    """The points of one grid request, in the grid's lane order: params
    (with their own n), then policies, ops, engine counts, arbitrations
    and placements, the last fastest."""
    params = [{"n": n, "b": b, "s": p["s"], "w": p["w"], "a": 0}
              for p, n in zip(axes["params"], ns)]
    return [{**p, "policy": pol, "op": op, "engines": eng,
             "arbitration": arb, "burst_beats": bb, "placement": plc}
            for p, pol, op, eng, (arb, bb), plc in itertools.product(
                params, axes["policies"], axes["ops"], axes["engines"],
                axes["arbitrations"], axes["placements"])]
