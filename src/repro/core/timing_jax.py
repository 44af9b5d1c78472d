"""JAX grid evaluation of the throughput timing model (jit + vmap + mesh).

The NumPy model (`core/timing_model.py`) evaluates one (params, policy,
op, contention) point per host call; a campaign cross-product over the
paper's knobs — policy x burst x arbitration x placement x N engines —
is 10^4..10^6 points and therefore bounded by Python dispatch.  This
module ports the segment-reduction throughput analysis to JAX as a pure
function of stacked per-point scalars, so an entire grid lowers into ONE
compiled XLA program:

* :func:`throughput` / :func:`contended_throughput` — drop-in
  single-point mirrors of the NumPy entry points (same result
  dataclasses, same detail keys; ``op="write"``/``"duplex"`` select the
  same direction overheads).  The ``jaxgrid`` backend routes per-point
  protocol calls here.
* :func:`evaluate_points` — the batch primitive: a flat list of point
  requests evaluated in one ``jit(vmap)`` call.  ``Sweep.run()`` uses it
  to prefill its memo caches on grid-capable backends.
* :func:`evaluate_grid` — the cross-product planner: :class:`GridAxes`
  -> vectorized host prep -> one batched kernel call ->
  :class:`GridResult`, with optional mesh sharding of the leading
  (point) axis via ``launch/mesh.py`` (`shard_grid`).

Implementation tower (DESIGN.md sec. 12): `_timing_reference.py` (loop
oracle) pins `timing_model.py` (NumPy) bit-exactly / at 1e-9;
`timing_model.py` in turn pins this module within :data:`REL_TOLERANCE`.
The JAX port reproduces the identical float64 formulas; the residual
differences are reduction order (pairwise vs sequential summation) and
the zero-padded tail of the bucketed command capacity, both O(eps)
effects.  Integer outputs (activation counts, command totals) match
exactly; the *bound name* can legitimately flip between implementations
when two resource bounds tie within float noise, so name assertions
apply only away from ties (tests/core/test_timing_differential.py).

Serial latency stays NumPy-only: its epoch loop is data-dependent
(refresh-crossing retries) and already fast per point, so the
``jaxgrid`` backend reports ``supports_latency=False`` and latency
points keep running through ``sim``.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import math
import time
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import spans
from repro.core.address_mapping import AddressMapping, get_mapping
from repro.core.engine import (PLACEMENTS, combine_placement,
                               combine_placement_ports, placement_mix_slices,
                               placement_port_counts)
from repro.core.engine_mix import EngineMix, normalize_mix
from repro.core.hwspec import MemorySpec
from repro.core.params import RSTParams
from repro.core.switch import SwitchModel
from repro.core.channels import topology_for
from repro.core.timing_model import (_MAX_EXPAND, _REORDER_WINDOW,
                                     ContentionResult, ThroughputResult,
                                     _direction_overheads, _grant_beats,
                                     _mixed_grant_schedule,
                                     _turnaround_between)

#: Documented NumPy<->JAX agreement bound (relative) for float outputs —
#: both paths compute the same float64 formulas; only summation order and
#: command-capacity padding differ.  See module docstring / DESIGN.md §12.
REL_TOLERANCE = 1e-9

_WIN = _REORDER_WINDOW
_BOUND_NAMES = ("bus/ccd", "bank", "faw")


# --------------------------------------------------------------- host prep
@functools.lru_cache(maxsize=None)
def _segment_table(mapping: AddressMapping
                   ) -> Tuple[Tuple[int, int, int, int, int], ...]:
    """(bit_pos, mask, row_weight, bg_weight, bank_weight) per segment.

    Mirrors ``AddressMapping.decode``: MSB-first fields, a field split
    across segments reassembling as ``(prev << n) | piece`` — i.e. each
    segment contributes ``piece << trailing_width`` where trailing_width
    sums the later segments of the *same* field.  Bank weights fold
    ``bank_id_from`` in directly (BG segments carry an extra
    ``<< bank_bits``).  Column segments never enter the bounds and are
    dropped.
    """
    entries = []
    pos = mapping.mapped_bits
    for f, n in mapping.fields:
        pos -= n
        entries.append((f, n, pos))
    trail = {"R": 0, "BG": 0, "B": 0, "C": 0}
    out = []
    for f, n, p in reversed(entries):
        shift = trail[f]
        trail[f] += n
        if f == "C":
            continue
        row_w = (1 << shift) if f == "R" else 0
        bg_w = (1 << shift) if f == "BG" else 0
        if f == "BG":
            bank_w = (1 << shift) << mapping.spec.bank_bits
        elif f == "B":
            bank_w = 1 << shift
        else:
            bank_w = 0
        out.append((p, (1 << n) - 1, row_w, bg_w, bank_w))
    out.reverse()
    return tuple(out)


def _bucket(n: int, quantum: int) -> int:
    """Smallest ``quantum * 2^k >= n`` — a small ladder of static shapes
    so jit recompiles O(log) times instead of once per batch size."""
    size = quantum
    while size < n:
        size *= 2
    return size


# ------------------------------------------------------------- the kernel
@functools.lru_cache(maxsize=None)
def _grid_kernel(spec: MemorySpec, cap: int, nseg: int,
                 periodic: bool = False):
    """Compiled ``vmap`` evaluator for `cap`-command streams on `spec`.

    One lane = one (params, mapping, op, engines, arbitration) unit; the
    lane computes the grant-interleaved command stream, the address
    decode, and the three resource bounds of
    ``timing_model._stream_bounds``, entirely from per-lane scalars.
    Lanes are padded to `cap` commands; invalid slots carry sentinel
    bank/bank-group ids one past the real range so every windowed
    reduction ignores them.

    ``periodic=True`` is the steady-state fast path (cap = two reorder
    windows): eligible lanes (see `_unit_row`) have an address stream
    that is exactly periodic from command 0 with period dividing the
    reorder window, so every window past the first is identical — the
    kernel evaluates the cold window plus one steady window and
    extrapolates the remaining ``nwin - 1`` windows in closed form.
    The per-window sums this replaces are sums of *identical* values,
    so integer quantities (activations, per-window bank maxima, bank-
    group transitions) match the full expansion exactly and float
    quantities differ only by multiply-vs-repeated-add rounding, far
    inside :data:`REL_TOLERANCE`.  This is where the 100-1000x over the
    per-point NumPy path comes from: NumPy expands all
    ``timing_model._MAX_EXPAND`` commands per point, the periodic lane
    costs O(two windows) regardless of stream length.
    """
    nw = cap // _WIN
    nbg = 1 << spec.bankgroup_bits
    nb = spec.num_banks
    bus = spec.bus_bytes_per_cycle
    lsb = spec.addr_lsb
    ccd_l = spec.ns_to_cycles(spec.t_ccd_l_ns)
    t_rc = spec.ns_to_cycles(spec.t_rc_ns)
    faw4 = spec.ns_to_cycles(spec.t_faw_ns) / 4.0
    cycle_ns = spec.cycle_ns
    peak = spec.peak_channel_gbps

    def point(d: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        i = jnp.arange(cap, dtype=jnp.int32)
        txns, eng, cmds, bb = d["txns"], d["eng"], d["cmds"], d["bb"]
        if periodic:
            totalf, txnef, nwinf = d["totalf"], d["txnef"], d["nwinf"]
            valid = jnp.ones(cap, dtype=bool)
        else:
            total_txn = txns * eng
            total = total_txn * cmds
            totalf = total.astype(jnp.float64)
            txnef = total_txn.astype(jnp.float64)
            valid = i < total

        # Grant-interleaved stream (_contended_command_addresses): full
        # bb-beat rounds flatten as (round, engine, beat); the trailing
        # partial round is engine-major.  eng=1 degenerates to the plain
        # single-engine expansion, element for element.
        q = i // cmds
        off = ((i % cmds) * bus).astype(jnp.int64)
        nfull = (txns // bb) * bb
        split = nfull * eng
        ebb = eng * bb
        m_full = q % ebb
        e_full = m_full // bb
        t_full = (q // ebb) * bb + m_full % bb
        q2 = q - split
        rem = jnp.maximum(txns - nfull, 1)
        in_full = q < split
        e = jnp.where(in_full, e_full, q2 // rem)
        t = jnp.where(in_full, t_full, nfull + q2 % rem)
        # (t*S) mod W == (t mod (W//S)) * S for pow2 S <= W: keeps the
        # product inside int64 for any valid RST tuple.
        addr = (d["a"] + (t % d["wos"]).astype(jnp.int64) * d["s"]
                + e.astype(jnp.int64) * d["w"] + off)

        # Decode via the per-lane segment table (column segments dropped).
        m = addr >> lsb
        row = jnp.zeros(cap, jnp.int32)
        bg = jnp.zeros(cap, jnp.int32)
        bank = jnp.zeros(cap, jnp.int32)
        for k in range(nseg):
            piece = ((m >> d["seg_pos"][k]) & d["seg_mask"][k])
            piece = piece.astype(jnp.int32)
            row = row + piece * d["seg_row"][k]
            bg = bg + piece * d["seg_bg"][k]
            bank = bank + piece * d["seg_bank"][k]
        # Sentinels one past the real id range: padded slots never match
        # a real bank/bank-group in the windowed reductions below.
        bg_s = jnp.where(valid, bg, nbg)
        bank_s = jnp.where(valid, bank, nb)

        # --- command-issue bound (data bus + bank-group tCCD_L) --------
        diffs = (bg_s[1:] != bg_s[:-1]) & valid[1:]
        if periodic:
            # Transitions are periodic in i from i=1 on: window 0
            # contributes its 63 interior pairs, every later window the
            # 64 pairs starting at its boundary — all equal to window
            # 1's by periodicity.
            s0 = jnp.sum(diffs[:_WIN - 1].astype(jnp.int32))
            s1 = jnp.sum(diffs[_WIN - 1:].astype(jnp.int32))
            trans = (s0.astype(jnp.float64)
                     + s1.astype(jnp.float64) * (nwinf - 1.0))
        else:
            trans = jnp.sum(diffs.astype(jnp.int32)).astype(jnp.float64)
        run_len = totalf / (trans + 1.0)
        g_cap = jnp.maximum(1.0, _WIN / (2.0 * run_len))
        bgw = bg_s.reshape(nw, _WIN)
        uniq = jnp.sum(jnp.any(
            bgw[:, :, None] == jnp.arange(nbg, dtype=jnp.int32)[None, None],
            axis=1).astype(jnp.int32), axis=1)
        if periodic:
            # All windows share window 1's bank-group population (the
            # address stream itself is periodic from command 0).
            g1 = jnp.minimum(uniq[1].astype(jnp.float64), g_cap)
            denom1 = jnp.minimum(1.0, g1 / ccd_l)
            per_w = _WIN / jnp.maximum(denom1, 1e-300)
            issue = nwinf * per_w + d["turn"] * nwinf
        else:
            wlen = jnp.clip(total - jnp.arange(nw, dtype=jnp.int32) * _WIN,
                            0, _WIN)
            g = jnp.minimum(uniq.astype(jnp.float64), g_cap)
            denom = jnp.minimum(1.0, g / ccd_l)
            per = jnp.where(wlen > 0,
                            wlen.astype(jnp.float64)
                            / jnp.maximum(denom, 1e-300), 0.0)
            nw_used = jnp.sum((wlen > 0).astype(jnp.int32))
            issue = jnp.sum(per) + d["turn"] * nw_used.astype(jnp.float64)

        # --- bank bound (activations serialize at tRC per bank) -------
        # Previous same-bank slot via one exclusive running max per bank
        # (the shifted-argsort of _prev_same_bank, without the sort).
        prev = jnp.full(cap, -1, jnp.int32)
        for b in range(nb):
            is_b = bank_s == b
            cand = jnp.where(is_b, i, -1)
            run = lax.cummax(cand, axis=0)
            run_excl = jnp.concatenate(
                [jnp.full((1,), -1, jnp.int32), run[:-1]])
            prev = jnp.where(is_b, run_excl, prev)
        row_prev = jnp.take(row, jnp.clip(prev, 0, cap - 1))
        act = valid & ((prev < 0) | (row_prev != row))
        counts = jnp.sum(
            (act.reshape(nw, _WIN)[:, :, None]
             & (bank_s.reshape(nw, _WIN)[:, :, None]
                == jnp.arange(nb, dtype=jnp.int32)[None, None]))
            .astype(jnp.int32), axis=1)
        pwmax = jnp.max(counts, axis=1)
        if periodic:
            # Window 1 is the steady state: the activation pattern
            # repeats with the stream period (first-touch activations
            # all land in window 0), so windows 1..nwin-1 are identical.
            per_window_acts = jnp.sum(act.reshape(nw, _WIN)
                                      .astype(jnp.int32), axis=1)
            acts_f = (per_window_acts[0].astype(jnp.float64)
                      + per_window_acts[1].astype(jnp.float64)
                      * (nwinf - 1.0))
            pw_sum = (pwmax[0].astype(jnp.float64)
                      + pwmax[1].astype(jnp.float64) * (nwinf - 1.0))
        else:
            acts_f = jnp.sum(act.astype(jnp.int32)).astype(jnp.float64)
            pw_sum = jnp.sum(pwmax).astype(jnp.float64)
        bank_cycles = pw_sum * (t_rc + d["extra"])

        # --- four-activate-window bound --------------------------------
        faw = acts_f * faw4

        bounds = jnp.stack([issue, bank_cycles, faw])
        steady = jnp.max(bounds)
        eff = d["eff"]
        bytes_ = txnef * d["bf"]
        seconds = steady * cycle_ns * 1e-9
        gbps = jnp.where(seconds > 0.0,
                         bytes_ / jnp.maximum(seconds, 1e-300) / 1e9 * eff,
                         0.0)
        gbps = jnp.minimum(gbps, peak)

        mean_service = jnp.where(
            txnef > 0.0, steady / jnp.maximum(txnef, 1.0), 0.0)
        engf = eng.astype(jnp.float64)
        bbf = bb.astype(jnp.float64)
        stream = txns.astype(jnp.float64) * mean_service
        is_excl = d["excl"] > 0
        queueing = jnp.where(is_excl, 0.5 * (engf - 1.0) * stream,
                             (engf - 1.0) * mean_service)
        head = jnp.where(is_excl, (engf - 1.0) * stream,
                         (engf - 1.0) * bbf * mean_service)

        return {"gbps": gbps, "bidx": jnp.argmax(bounds),
                "issue": issue, "bank": bank_cycles, "faw": faw,
                "acts": acts_f, "cmds_total": totalf,
                "mean_service": mean_service, "queueing": queueing,
                "head": head}

    return jax.jit(jax.vmap(point))


@functools.lru_cache(maxsize=None)
def _mix_kernel(spec: MemorySpec, cap: int, nseg: int, maxN: int):
    """Compiled ``vmap`` evaluator for *mixed-engine* lanes on `spec`.

    The heterogeneous sibling of :func:`_grid_kernel`: one lane = one
    stackable :class:`EngineMix` unit — every engine has the same
    transaction count and commands-per-transaction (ragged mixes fall
    back to the NumPy mixed model per lane), but carries its *own* RST
    tuple and direction overheads in padded per-engine parameter stacks
    of width `maxN` (pad entries repeat engine 0 and are never gathered:
    the computed engine index stays below the lane's real engine count).
    The grant-interleave index math is exactly the homogeneous kernel's;
    per-engine address terms, per-window *mean* turnaround, the
    activation weights of the bank bound, and the host-computed
    grant-boundary bus-reversal cost (``bcost``) generalize the scalar
    lane fields.  Mixed lanes never take the periodic fast path: engines
    may disagree on period, which is precisely what routes them here
    (`_route`).
    """
    nw = cap // _WIN
    nbg = 1 << spec.bankgroup_bits
    nb = spec.num_banks
    bus = spec.bus_bytes_per_cycle
    lsb = spec.addr_lsb
    ccd_l = spec.ns_to_cycles(spec.t_ccd_l_ns)
    t_rc = spec.ns_to_cycles(spec.t_rc_ns)
    faw4 = spec.ns_to_cycles(spec.t_faw_ns) / 4.0
    cycle_ns = spec.cycle_ns
    peak = spec.peak_channel_gbps

    def point(d: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        i = jnp.arange(cap, dtype=jnp.int32)
        txns, eng, cmds, bb = d["txns"], d["eng"], d["cmds"], d["bb"]
        total_txn = txns * eng
        total = total_txn * cmds
        totalf = total.astype(jnp.float64)
        txnef = total_txn.astype(jnp.float64)
        valid = i < total

        # Same grant-interleave index math as the homogeneous kernel
        # (equal counts by stackability), but every per-engine scalar is
        # a gather from the lane's parameter stacks.
        q = i // cmds
        off = ((i % cmds) * bus).astype(jnp.int64)
        nfull = (txns // bb) * bb
        split = nfull * eng
        ebb = eng * bb
        m_full = q % ebb
        e_full = m_full // bb
        t_full = (q // ebb) * bb + m_full % bb
        q2 = q - split
        rem = jnp.maximum(txns - nfull, 1)
        in_full = q < split
        e = jnp.where(in_full, e_full, q2 // rem)
        t = jnp.where(in_full, t_full, nfull + q2 % rem)
        e_c = jnp.clip(e, 0, maxN - 1)
        a_e = jnp.take(d["stk_a"], e_c)        # absolute base incl. window
        s_e = jnp.take(d["stk_s"], e_c)
        wos_e = jnp.take(d["stk_wos"], e_c)
        addr = a_e + (t % wos_e).astype(jnp.int64) * s_e + off

        m = addr >> lsb
        row = jnp.zeros(cap, jnp.int32)
        bg = jnp.zeros(cap, jnp.int32)
        bank = jnp.zeros(cap, jnp.int32)
        for k in range(nseg):
            piece = ((m >> d["seg_pos"][k]) & d["seg_mask"][k])
            piece = piece.astype(jnp.int32)
            row = row + piece * d["seg_row"][k]
            bg = bg + piece * d["seg_bg"][k]
            bank = bank + piece * d["seg_bank"][k]
        bg_s = jnp.where(valid, bg, nbg)
        bank_s = jnp.where(valid, bank, nb)

        # --- command-issue bound (data bus + bank-group tCCD_L) --------
        diffs = (bg_s[1:] != bg_s[:-1]) & valid[1:]
        trans = jnp.sum(diffs.astype(jnp.int32)).astype(jnp.float64)
        run_len = totalf / (trans + 1.0)
        g_cap = jnp.maximum(1.0, _WIN / (2.0 * run_len))
        bgw = bg_s.reshape(nw, _WIN)
        uniq = jnp.sum(jnp.any(
            bgw[:, :, None] == jnp.arange(nbg, dtype=jnp.int32)[None, None],
            axis=1).astype(jnp.int32), axis=1)
        wlen = jnp.clip(total - jnp.arange(nw, dtype=jnp.int32) * _WIN,
                        0, _WIN)
        g = jnp.minimum(uniq.astype(jnp.float64), g_cap)
        denom = jnp.minimum(1.0, g / ccd_l)
        per = jnp.where(wlen > 0,
                        wlen.astype(jnp.float64)
                        / jnp.maximum(denom, 1e-300), 0.0)
        # Per-window *mean* of the per-command turnaround (each command
        # contributes its issuing engine's duplex share), plus the
        # host-computed grant-boundary bus-reversal segments.
        turn_i = jnp.where(valid, jnp.take(d["stk_turn"], e_c), 0.0)
        tw = jnp.sum(turn_i.reshape(nw, _WIN), axis=1)
        per_turn = jnp.where(wlen > 0,
                             tw / jnp.maximum(wlen.astype(jnp.float64), 1.0),
                             0.0)
        issue = jnp.sum(per) + jnp.sum(per_turn) + d["bcost"]

        # --- bank bound (activations serialize at tRC per bank) -------
        prev = jnp.full(cap, -1, jnp.int32)
        for b in range(nb):
            is_b = bank_s == b
            cand = jnp.where(is_b, i, -1)
            run = lax.cummax(cand, axis=0)
            run_excl = jnp.concatenate(
                [jnp.full((1,), -1, jnp.int32), run[:-1]])
            prev = jnp.where(is_b, run_excl, prev)
        row_prev = jnp.take(row, jnp.clip(prev, 0, cap - 1))
        act = valid & ((prev < 0) | (row_prev != row))
        # Each activation extends tRC by its own engine's write-recovery
        # term: weighted per-(window, bank) sums instead of counts.
        w_i = jnp.where(act, t_rc + jnp.take(d["stk_extra"], e_c), 0.0)
        sums = jnp.sum(
            (w_i.reshape(nw, _WIN)[:, :, None]
             * (bank_s.reshape(nw, _WIN)[:, :, None]
                == jnp.arange(nb, dtype=jnp.int32)[None, None])
             .astype(jnp.float64)), axis=1)
        pwmax = jnp.max(sums, axis=1)
        acts_f = jnp.sum(act.astype(jnp.int32)).astype(jnp.float64)
        bank_cycles = jnp.sum(pwmax)

        # --- four-activate-window bound --------------------------------
        faw = acts_f * faw4

        bounds = jnp.stack([issue, bank_cycles, faw])
        steady = jnp.max(bounds)
        eff = d["eff"]
        seconds = steady * cycle_ns * 1e-9
        gbps = jnp.where(seconds > 0.0,
                         d["bytesf"] / jnp.maximum(seconds, 1e-300)
                         / 1e9 * eff, 0.0)
        gbps = jnp.minimum(gbps, peak)

        # Equal counts and commands-per-txn make every engine's service
        # share identical, so the homogeneous queueing forms apply.
        mean_service = jnp.where(
            txnef > 0.0, steady / jnp.maximum(txnef, 1.0), 0.0)
        engf = eng.astype(jnp.float64)
        bbf = bb.astype(jnp.float64)
        stream = txns.astype(jnp.float64) * mean_service
        is_excl = d["excl"] > 0
        queueing = jnp.where(is_excl, 0.5 * (engf - 1.0) * stream,
                             (engf - 1.0) * mean_service)
        head = jnp.where(is_excl, (engf - 1.0) * stream,
                         (engf - 1.0) * bbf * mean_service)

        return {"gbps": gbps, "bidx": jnp.argmax(bounds),
                "issue": issue, "bank": bank_cycles, "faw": faw,
                "acts": acts_f, "cmds_total": totalf,
                "mean_service": mean_service, "queueing": queueing,
                "head": head, "opsw": d["bcost"]}

    return jax.jit(jax.vmap(point))


# ------------------------------------------------- unit batching + results
# A "unit" is one same-channel kernel lane: (params, mapping, op,
# engine_count, arbitration, requested_burst_beats).  Placement points
# decompose into per-port units (engine.placement_port_counts) and are
# recombined host-side (engine.combine_placement), exactly like
# Engine._contention_unscaled.
_Unit = Tuple[RSTParams, AddressMapping, str, int, str, int]

# A mixed-engine kernel lane: (mix, mapping, arbitration,
# requested_burst_beats).  Only genuinely mixed EngineMix values appear
# here — uniform mixes normalize to a homogeneous _Unit before the units
# dict is built, so the two spellings share lanes (and memo keys).
_MixUnit = Tuple[EngineMix, AddressMapping, str, int]


def _efficiency(spec: MemorySpec) -> float:
    return ((1.0 - spec.t_rfc_ns / spec.t_refi_ns)
            * (1.0 - spec.sched_overhead))


def _unit_row(spec: MemorySpec, unit: _Unit) -> Dict[str, object]:
    """Host-side scalar row for one kernel lane (mirrors the caps and
    clamps of _command_addresses / _contended_command_addresses).

    Also decides periodic-kernel eligibility: the grant-interleaved
    stream repeats exactly with period ``cmds * wos`` commands for one
    engine (the interleave is the identity), and with period
    ``cmds * eng * bb * (wos // gcd(bb, wos))`` for multiple engines
    when the per-engine stream has no partial grant round
    (``txns % bb == 0`` — always true for pow2 txns and grant sizes).
    A lane is eligible when that period divides one reorder window and
    the stream spans at least two whole windows, so window 1 onward are
    identical and the kernel can extrapolate instead of expanding."""
    p, mapping, op, count, arbitration, burst_beats = unit
    turn, extra = _direction_overheads(spec, op)
    cmds = max(1, p.b // spec.bus_bytes_per_cycle)
    max_txns = max(16, (_MAX_EXPAND // cmds) // count)
    txns = min(p.n, _MAX_EXPAND, max_txns)
    bb = _grant_beats(arbitration, burst_beats, txns)
    wos = p.w // p.s
    total = txns * count * cmds
    if count == 1:
        period = cmds * wos
    elif txns % bb == 0:
        period = cmds * count * bb * (wos // math.gcd(bb, wos))
    else:
        period = 0
    periodic = (0 < period <= _WIN and _WIN % period == 0
                and total >= 2 * _WIN and total % _WIN == 0)
    return {"txns": txns, "eng": count, "cmds": cmds, "bb": bb,
            "excl": int(arbitration == "exclusive"),
            "a": p.a, "s": p.s, "w": p.w, "wos": wos, "b": p.b,
            "turn": turn, "extra": extra, "seg": _segment_table(mapping),
            "periodic": periodic, "totalf": float(total),
            "txnef": float(txns * count), "nwinf": float(total // _WIN),
            "unit": unit}


def _mix_row(spec: MemorySpec, unit: _MixUnit) -> Dict[str, object]:
    """Host-side row for one *mixed* kernel lane.

    Mirrors `_contended_throughput_mixed`'s caps exactly: the shared
    command budget splits `_MAX_EXPAND` across engines at the widest
    per-transaction command count, per-engine streams truncate to it,
    and grant beats clamp against the longest stream.  The grant-boundary
    bus-reversal cost (`bcost`) is data-independent of the addresses, so
    it is summed host-side along the real `_mixed_grant_schedule` grant
    sequence and added to the kernel's issue bound as a scalar.  A lane
    is *stackable* (eligible for `_mix_kernel`) when every engine has the
    same transaction count and commands-per-transaction — the padded
    parameter stacks then share the homogeneous interleave index math;
    ragged mixes fall back to the NumPy mixed model per lane.  Mixed
    lanes are never periodic: engines may disagree on period, which is
    what routes them off the homogeneous fast path in the first place.
    """
    mix, mapping, arbitration, burst_beats = unit
    mix.validate(spec)
    n_eng = len(mix)
    bus = spec.bus_bytes_per_cycle
    over = [_direction_overheads(spec, op_k) for op_k in mix.ops]
    cmds_e = [max(1, p_k.b // bus) for p_k in mix.params]
    max_txns = max(16, (_MAX_EXPAND // max(cmds_e)) // n_eng)
    counts = [min(p_k.n, _MAX_EXPAND, max_txns) for p_k in mix.params]
    bb = _grant_beats(arbitration, burst_beats, max(counts))
    _, _, grants = _mixed_grant_schedule(counts, bb, arbitration)
    pair_cost = np.array(
        [[_turnaround_between(spec, oi, oj) for oj in mix.ops]
         for oi in mix.ops], dtype=np.float64)
    bcost = (float(pair_cost[grants[:-1], grants[1:]].sum())
             if len(grants) > 1 else 0.0)
    w_offs = np.concatenate(([0], np.cumsum(
        np.array([p_k.w for p_k in mix.params], dtype=np.int64))))[:-1]
    stackable = len(set(counts)) == 1 and len(set(cmds_e)) == 1
    total = int(sum(c * cm for c, cm in zip(counts, cmds_e)))
    total_txns = int(sum(counts))
    bytesf = float(sum(c * p_k.b for c, p_k in zip(counts, mix.params)))
    return {"txns": counts[0], "eng": n_eng, "cmds": cmds_e[0], "bb": bb,
            "excl": int(arbitration == "exclusive"),
            "stk_a": np.array(
                [p_k.a + int(w_offs[k])
                 for k, p_k in enumerate(mix.params)], dtype=np.int64),
            "stk_s": np.array([p_k.s for p_k in mix.params],
                              dtype=np.int64),
            "stk_wos": np.array([p_k.w // p_k.s for p_k in mix.params],
                                dtype=np.int32),
            "stk_turn": np.array([t for t, _ in over], dtype=np.float64),
            "stk_extra": np.array([x for _, x in over], dtype=np.float64),
            "bcost": bcost, "bytesf": bytesf,
            "seg": _segment_table(mapping), "periodic": False,
            "stackable": stackable, "totalf": float(total),
            "txnef": float(total_txns), "mix": mix, "mix_unit": unit}


_I32 = ("txns", "eng", "cmds", "bb", "excl", "wos")
_I64 = ("a", "s", "w")
_F64 = ("turn", "extra", "totalf", "txnef", "nwinf")

#: Longest command stream the full-expansion kernel will materialize.
#: Non-periodic lanes past this fall back to the NumPy oracle per lane —
#: the windowed one-hot reductions are O(commands x banks) per lane, so
#: an unbounded cap would trade the whole batch's memory for a tail the
#: vectorized path cannot amortize anyway.
_FULL_KERNEL_MAX_CMDS = 8192

#: Lane-chunk budget in command slots: a full-kernel call materializes at
#: most ~budget x num_banks one-hot elements at a time.
_LANE_SLOT_BUDGET = 1 << 21


def _batch_columns(spec: MemorySpec, rows: Sequence[Dict[str, object]],
                   nseg: int) -> Dict[str, np.ndarray]:
    """Stack host rows (already padded to the lane count) into the
    `_grid_kernel` operand: one [lanes] column per scalar, [lanes, nseg]
    segment tables."""
    lanes = len(rows)
    cols: Dict[str, np.ndarray] = {}
    for k in _I32:
        cols[k] = np.array([r[k] for r in rows], dtype=np.int32)
    for k in _I64:
        cols[k] = np.array([r[k] for r in rows], dtype=np.int64)
    for k in _F64:
        cols[k] = np.array([r[k] for r in rows], dtype=np.float64)
    cols["bf"] = np.array([r["b"] for r in rows], dtype=np.float64)
    cols["eff"] = np.full(lanes, _efficiency(spec), dtype=np.float64)
    seg = np.zeros((lanes, nseg, 5), dtype=np.int64)
    for j, r in enumerate(rows):
        for k, ent in enumerate(r["seg"]):
            seg[j, k] = ent
    cols["seg_pos"] = seg[:, :, 0]
    cols["seg_mask"] = seg[:, :, 1]
    cols["seg_row"] = seg[:, :, 2].astype(np.int32)
    cols["seg_bg"] = seg[:, :, 3].astype(np.int32)
    cols["seg_bank"] = seg[:, :, 4].astype(np.int32)
    return cols


def _to_host(out: Dict[str, jax.Array], n: int) -> Dict[str, np.ndarray]:
    """Kernel outputs -> host arrays of the first `n` (real) lanes, plus
    a "devices" column: how many devices the outputs were sharded over
    (0 marks the NumPy fallback lanes, which run on the host)."""
    ndev = len(out["gbps"].sharding.device_set)
    host = {k: np.asarray(v)[:n] for k, v in out.items()}
    host["devices"] = np.full(n, ndev)
    return host


def _run_batch(spec: MemorySpec, rows: Sequence[Dict[str, object]],
               periodic: bool, mesh=None) -> Dict[str, np.ndarray]:
    """One batched kernel call over host rows -> dict of [len(rows)]
    output arrays.  Pads the lane axis to a pow2 bucket (shape-stable jit
    cache) and, under a mesh, to the device count; padding lanes repeat
    row 0 and are sliced off.  Off-mesh, wide batches of long streams
    split into fixed-size lane chunks to bound the kernel's working set.
    """
    n = len(rows)
    if periodic:
        cap = 2 * _WIN
    else:
        cap = _bucket(max(r["txns"] * r["eng"] * r["cmds"] for r in rows),
                      _WIN)
    if mesh is None:
        chunk = _bucket(max(1, _LANE_SLOT_BUDGET // cap), 1)
        if n > chunk:
            parts = [_run_batch(spec, rows[lo:lo + chunk], periodic)
                     for lo in range(0, n, chunk)]
            return {k: np.concatenate([p[k] for p in parts])
                    for k in parts[0]}
    nseg = max(len(r["seg"]) for r in rows)
    lanes = _bucket(n, 1)
    if mesh is not None:
        ndev = int(np.prod(mesh.devices.shape))
        lanes += (-lanes) % ndev

    with spans.span("repro.grid.columns"):
        cols = _batch_columns(spec, list(rows) + [rows[0]] * (lanes - n),
                              nseg)
    return _dispatch(_grid_kernel(spec, cap, nseg, periodic), cols, mesh,
                     "periodic" if periodic else "full", n)


def _dispatch(kernel, cols: Dict[str, np.ndarray], mesh, route: str,
              n: int) -> Dict[str, np.ndarray]:
    """One device call of a grid kernel over padded columns, through the
    copy of its first `n` lanes back to the host."""
    lanes = len(cols["eff"])
    with spans.span("repro.grid.dispatch", route=route, lanes=lanes,
                    real=n), jax.enable_x64(True):
        if mesh is not None:
            from repro.launch.mesh import shard_grid
            cols = {k: shard_grid(v, mesh, pad=False)[0]
                    for k, v in cols.items()}
        return _to_host(kernel(cols), n)


_MIX_I32 = ("txns", "eng", "cmds", "bb", "excl")
_MIX_F64 = ("bcost", "bytesf", "totalf", "txnef")
_MIX_STACKS = (("stk_a", np.int64), ("stk_s", np.int64),
               ("stk_wos", np.int32), ("stk_turn", np.float64),
               ("stk_extra", np.float64))


def _run_mix_batch(spec: MemorySpec, rows: Sequence[Dict[str, object]],
                   mesh=None) -> Dict[str, np.ndarray]:
    """One batched `_mix_kernel` call over stackable mixed rows.

    Same lane bucketing/chunking/mesh-padding discipline as `_run_batch`;
    additionally pads the engine axis to a shared pow2 width, repeating
    each lane's engine-0 stack entry (pad entries are never gathered —
    the kernel's engine index stays below the lane's real engine count).
    """
    n = len(rows)
    cap = _bucket(max(int(r["totalf"]) for r in rows), _WIN)
    maxN = _bucket(max(int(r["eng"]) for r in rows), 1)
    if mesh is None:
        chunk = _bucket(max(1, _LANE_SLOT_BUDGET // cap), 1)
        if n > chunk:
            parts = [_run_mix_batch(spec, rows[lo:lo + chunk])
                     for lo in range(0, n, chunk)]
            return {k: np.concatenate([p[k] for p in parts])
                    for k in parts[0]}
    nseg = max(len(r["seg"]) for r in rows)
    lanes = _bucket(n, 1)
    if mesh is not None:
        ndev = int(np.prod(mesh.devices.shape))
        lanes += (-lanes) % ndev

    with spans.span("repro.grid.columns"):
        cols = _mix_batch_columns(spec, list(rows) + [rows[0]] * (lanes - n),
                                  nseg, maxN)
    return _dispatch(_mix_kernel(spec, cap, nseg, maxN), cols, mesh,
                     "mixfull", n)


def _mix_batch_columns(spec: MemorySpec, rows: Sequence[Dict[str, object]],
                       nseg: int, maxN: int) -> Dict[str, np.ndarray]:
    """Stack mixed host rows (already padded to the lane count) into the
    `_mix_kernel` operand, each engine stack padded to `maxN` with its
    engine-0 entry."""
    lanes = len(rows)
    cols: Dict[str, np.ndarray] = {}
    for k in _MIX_I32:
        cols[k] = np.array([r[k] for r in rows], dtype=np.int32)
    for k in _MIX_F64:
        cols[k] = np.array([r[k] for r in rows], dtype=np.float64)
    for k, dt in _MIX_STACKS:
        arr = np.empty((lanes, maxN), dtype=dt)
        for j, r in enumerate(rows):
            v = r[k]
            arr[j, :len(v)] = v
            arr[j, len(v):] = v[0]
        cols[k] = arr
    cols["eff"] = np.full(lanes, _efficiency(spec), dtype=np.float64)
    seg = np.zeros((lanes, nseg, 5), dtype=np.int64)
    for j, r in enumerate(rows):
        for k, ent in enumerate(r["seg"]):
            seg[j, k] = ent
    cols["seg_pos"] = seg[:, :, 0]
    cols["seg_mask"] = seg[:, :, 1]
    cols["seg_row"] = seg[:, :, 2].astype(np.int32)
    cols["seg_bg"] = seg[:, :, 3].astype(np.int32)
    cols["seg_bank"] = seg[:, :, 4].astype(np.int32)
    return cols


def _numpy_rows(spec: MemorySpec, rows: Sequence[Dict[str, object]]
                ) -> Dict[str, np.ndarray]:
    """NumPy-oracle fallback for lanes the kernels decline (non-periodic
    streams past `_FULL_KERNEL_MAX_CMDS`): same output schema, computed
    by `timing_model.contended_throughput` per lane."""
    from repro.core import timing_model
    keys = ("gbps", "bidx", "issue", "bank", "faw", "acts", "cmds_total",
            "mean_service", "queueing", "head")
    out = {k: np.empty(len(rows), dtype=np.float64) for k in keys}
    with spans.span("repro.grid.numpy_lanes", route="numpy",
                    lanes=len(rows)):
        for j, r in enumerate(rows):
            p, mapping, op, count, arb, bb_req = r["unit"]
            res = timing_model.contended_throughput(
                p, mapping, spec, num_engines=count, op=op,
                arbitration=arb, burst_beats=bb_req)
            out["gbps"][j] = res.aggregate_gbps
            out["bidx"][j] = _BOUND_NAMES.index(res.bound)
            out["issue"][j] = res.detail["bus/ccd"]
            out["bank"][j] = res.detail["bank"]
            out["faw"][j] = res.detail["faw"]
            out["acts"][j] = res.detail["total_acts"]
            out["cmds_total"][j] = res.detail["txns"]
            out["mean_service"][j] = res.detail["mean_service_cycles"]
            out["queueing"][j] = res.queueing_delay_cycles
            out["head"][j] = res.detail["grant_head_wait_cycles"]
    out["bidx"] = out["bidx"].astype(np.int64)
    out["devices"] = np.zeros(len(rows))
    return out


def _numpy_mix_rows(spec: MemorySpec, rows: Sequence[Dict[str, object]]
                    ) -> Dict[str, np.ndarray]:
    """NumPy-oracle fallback for mixed lanes `_mix_kernel` declines
    (ragged counts/commands, or streams past `_FULL_KERNEL_MAX_CMDS`):
    same output schema, computed by `timing_model.contended_throughput_mix`
    per lane."""
    from repro.core import timing_model
    keys = ("gbps", "bidx", "issue", "bank", "faw", "acts", "cmds_total",
            "mean_service", "queueing", "head", "opsw")
    out = {k: np.empty(len(rows), dtype=np.float64) for k in keys}
    with spans.span("repro.grid.numpy_lanes", route="mixnumpy",
                    lanes=len(rows)):
        for j, r in enumerate(rows):
            mix, mapping, arb, bb_req = r["mix_unit"]
            res = timing_model.contended_throughput_mix(
                mix, mapping, spec, arbitration=arb, burst_beats=bb_req)
            out["gbps"][j] = res.aggregate_gbps
            out["bidx"][j] = _BOUND_NAMES.index(res.bound)
            out["issue"][j] = res.detail["bus/ccd"]
            out["bank"][j] = res.detail["bank"]
            out["faw"][j] = res.detail["faw"]
            out["acts"][j] = res.detail["total_acts"]
            out["cmds_total"][j] = res.detail["txns"]
            out["mean_service"][j] = res.detail["mean_service_cycles"]
            out["queueing"][j] = res.queueing_delay_cycles
            out["head"][j] = res.detail["grant_head_wait_cycles"]
            out["opsw"][j] = res.detail.get("op_switch_cycles", 0.0)
    out["bidx"] = out["bidx"].astype(np.int64)
    out["devices"] = np.zeros(len(rows))
    return out


def _route(row: Dict[str, object]) -> str:
    if "mix_unit" in row:
        if row["stackable"] and row["totalf"] <= _FULL_KERNEL_MAX_CMDS:
            return "mixfull"
        return "mixnumpy"
    if row["periodic"]:
        return "periodic"
    if row["txns"] * row["eng"] * row["cmds"] > _FULL_KERNEL_MAX_CMDS:
        return "numpy"
    return "full"


def _run_rows(spec: MemorySpec, rows: Sequence[Dict[str, object]],
              mesh=None) -> Dict[str, np.ndarray]:
    """Evaluate host rows, routing each lane to the periodic kernel, the
    full-expansion kernel, or the NumPy fallback (see `_route`), and
    merge the outputs back into original row order as float64/int64
    arrays."""
    n = len(rows)
    merged: Dict[str, np.ndarray] = {}
    with spans.span("repro.grid.route"):
        for route in ("full", "periodic", "numpy", "mixfull", "mixnumpy"):
            idxs = [j for j in range(n) if _route(rows[j]) == route]
            if not idxs:
                continue
            sub = [rows[j] for j in idxs]
            if route == "numpy":
                out = _numpy_rows(spec, sub)
            elif route == "mixnumpy":
                out = _numpy_mix_rows(spec, sub)
            elif route == "mixfull":
                out = _run_mix_batch(spec, sub, mesh)
            else:
                out = _run_batch(spec, sub, route == "periodic", mesh)
            for k, v in out.items():
                if k not in merged:
                    dt = np.int64 if k == "bidx" else np.float64
                    merged[k] = np.empty(n, dtype=dt)
                merged[k][idxs] = v
    return merged


def _tp_result(spec: MemorySpec, rows, out, j: int) -> ThroughputResult:
    return ThroughputResult(
        gbps=float(out["gbps"][j]),
        bound=_BOUND_NAMES[int(out["bidx"][j])],
        detail={"bus/ccd": float(out["issue"][j]),
                "bank": float(out["bank"][j]),
                "faw": float(out["faw"][j]),
                "txns": float(out["cmds_total"][j]),
                "cmds_per_txn": float(rows[j]["cmds"]),
                "total_acts": float(out["acts"][j]),
                "efficiency": _efficiency(spec)})


def _cont_result(spec: MemorySpec, rows, out, j: int, arbitration: str,
                 burst_beats: int) -> ContentionResult:
    r = rows[j]
    return ContentionResult(
        num_engines=int(r["eng"]),
        aggregate_gbps=float(out["gbps"][j]),
        bound=_BOUND_NAMES[int(out["bidx"][j])],
        queueing_delay_cycles=float(out["queueing"][j]),
        detail={"bus/ccd": float(out["issue"][j]),
                "bank": float(out["bank"][j]),
                "faw": float(out["faw"][j]),
                "txns": float(out["cmds_total"][j]),
                "cmds_per_txn": float(r["cmds"]),
                "txns_per_engine": float(r["txns"]),
                "total_acts": float(out["acts"][j]),
                "mean_service_cycles": float(out["mean_service"][j]),
                "grant_head_wait_cycles": float(out["head"][j]),
                "grant_beats": float(r["bb"]),
                "efficiency": _efficiency(spec)},
        arbitration=arbitration,
        burst_beats=burst_beats)


def _cont_result_mix(spec: MemorySpec, rows, out, j: int,
                     arbitration: str, burst_beats: int) -> ContentionResult:
    r = rows[j]
    mix: EngineMix = r["mix"]
    txnef = float(r["txnef"])
    return ContentionResult(
        num_engines=len(mix),
        aggregate_gbps=float(out["gbps"][j]),
        bound=_BOUND_NAMES[int(out["bidx"][j])],
        queueing_delay_cycles=float(out["queueing"][j]),
        detail={"bus/ccd": float(out["issue"][j]),
                "bank": float(out["bank"][j]),
                "faw": float(out["faw"][j]),
                "txns": float(out["cmds_total"][j]),
                "cmds_per_txn": float(r["totalf"]) / txnef if txnef else 0.0,
                "txns_per_engine": txnef / len(mix),
                "total_acts": float(out["acts"][j]),
                "mean_service_cycles": float(out["mean_service"][j]),
                "grant_head_wait_cycles": float(out["head"][j]),
                "grant_beats": float(r["bb"]),
                "op_switch_cycles": float(out["opsw"][j]),
                "mix_size": float(len(mix)),
                "efficiency": _efficiency(spec)},
        arbitration=arbitration,
        burst_beats=burst_beats,
        mix=mix)


def _switch_for(spec: MemorySpec) -> SwitchModel:
    # Matches Engine._switch_model for an engine built without an explicit
    # switch: the placement combine sees identical capacity terms.
    return SwitchModel(topology_for(spec), enabled=True)


# ----------------------------------------------------------- public: points
def throughput(p: RSTParams, mapping: AddressMapping, spec: MemorySpec, *,
               op: str = "read") -> ThroughputResult:
    """JAX mirror of :func:`repro.core.timing_model.throughput`.

    Same signature, same result type, same detail keys; float fields
    agree within :data:`REL_TOLERANCE`, integer fields exactly.
    """
    unit: _Unit = (p.validate(spec), mapping, op, 1, "round_robin", 1)
    rows = [_unit_row(spec, unit)]
    out = _run_rows(spec, rows)
    return _tp_result(spec, rows, out, 0)


def contended_throughput(p: RSTParams, mapping: AddressMapping,
                         spec: MemorySpec, *, num_engines: int = 1,
                         op: str = "read",
                         arbitration: str = "round_robin",
                         burst_beats: int = 1) -> ContentionResult:
    """JAX mirror of :func:`repro.core.timing_model.contended_throughput`
    (same-channel placement; the cross-channel placements are combined by
    the engine/evaluate_points layer, as on the NumPy path)."""
    if num_engines < 1:
        raise ValueError(f"num_engines must be >= 1, got {num_engines}")
    unit: _Unit = (p.validate(spec), mapping, op, num_engines,
                   arbitration, burst_beats)
    rows = [_unit_row(spec, unit)]
    out = _run_rows(spec, rows)
    return _cont_result(spec, rows, out, 0, arbitration, burst_beats)


def contended_throughput_mix(mix: EngineMix, mapping: AddressMapping,
                             spec: MemorySpec, *,
                             arbitration: str = "round_robin",
                             burst_beats: int = 1) -> ContentionResult:
    """JAX mirror of :func:`repro.core.timing_model.contended_throughput_mix`.

    A uniform mix delegates to the homogeneous :func:`contended_throughput`
    (keeping its periodic fast path and bit-for-bit agreement with the
    homogeneous NumPy model); a genuinely mixed mix runs the stacked
    `_mix_kernel` lane (or the NumPy mixed model for ragged/oversized
    lanes) and agrees with `timing_model.contended_throughput_mix` within
    :data:`REL_TOLERANCE`.
    """
    uni = mix.uniform_entry()
    if uni is not None:
        return contended_throughput(
            uni[0], mapping, spec, num_engines=len(mix), op=uni[1],
            arbitration=arbitration, burst_beats=burst_beats)
    unit: _MixUnit = (mix.validate(spec), mapping, arbitration, burst_beats)
    rows = [_mix_row(spec, unit)]
    out = _run_rows(spec, rows)
    return _cont_result_mix(spec, rows, out, 0, arbitration, burst_beats)


def evaluate_points(spec: MemorySpec, reqs: Sequence[Tuple], *,
                    mesh=None) -> List[object]:
    """Evaluate a flat batch of sweep-style requests in one compiled call.

    Each request is ``("tp", params, policy, op)`` or ``("cont", params,
    policy, op, num_engines, arbitration, burst_beats, placement)``,
    optionally extended with a ninth ``mix`` element (an
    :class:`EngineMix` or None) — exactly the memo-key fields of
    ``Sweep``'s deterministic caches.  Mix requests normalize first
    (uniform mix -> the homogeneous spelling, sharing its lanes and memo
    keys); genuinely mixed placements decompose the entry tuple
    *contiguously* across the per-port engine counts, re-normalizing each
    port's sub-mix, and recombine through
    ``engine.combine_placement_ports`` (ordered per-port results — two
    same-count ports may carry different sub-mixes, which the count-keyed
    homogeneous combine cannot represent).  Placement requests decompose
    into per-port units and recombine through the same switch-capacity
    model as ``Engine._contention_unscaled``; duplicate units across the
    batch evaluate once.  Returns result objects aligned with `reqs`.
    """
    with spans.span("repro.grid.evaluate"):
        with spans.span("repro.grid.plan"):
            units: Dict[_Unit, int] = {}
            plans: List[Tuple] = []
            sw: Optional[SwitchModel] = None
            for req in reqs:
                if req[0] == "tp":
                    _, p, policy, op = req
                    unit: _Unit = (p.validate(spec), get_mapping(spec, policy),
                                   op, 1, "round_robin", 1)
                    units.setdefault(unit, len(units))
                    plans.append(("tp", unit, None))
                elif req[0] == "cont":
                    if len(req) == 9:
                        _, p, policy, op, n_eng, arb, bb, placement, mix = req
                    else:
                        _, p, policy, op, n_eng, arb, bb, placement = req
                        mix = None
                    if n_eng < 1:
                        raise ValueError(
                            f"num_engines must be >= 1, got {n_eng}")
                    mix, p, op, n_eng = normalize_mix(mix, p, op, n_eng)
                    p = p.validate(spec)
                    mapping = get_mapping(spec, policy)
                    if placement not in PLACEMENTS:
                        raise ValueError(f"unknown placement {placement!r}; "
                                         f"valid: {PLACEMENTS}")
                    if mix is not None:
                        mix.validate(spec)
                        if placement == "same_channel":
                            munit: _MixUnit = (mix, mapping, arb, bb)
                            units.setdefault(munit, len(units))
                            plans.append(("mix", munit, (arb, bb)))
                            continue
                        sw = sw or _switch_for(spec)
                        effective, counts = placement_port_counts(
                            sw, placement, n_eng)
                        ports = []
                        for lo, hi in placement_mix_slices(counts):
                            sub = EngineMix.of(mix.entries[lo:hi])
                            uni = sub.uniform_entry()
                            if uni is not None:
                                u = (uni[0], mapping, uni[1], len(sub), arb, bb)
                            else:
                                u = (sub, mapping, arb, bb)
                            units.setdefault(u, len(units))
                            ports.append((hi - lo, u))
                        plans.append(("mixpl", ports, (n_eng, arb, bb, placement,
                                                       effective, mix)))
                        continue
                    if placement == "same_channel":
                        effective, counts = placement, [n_eng]
                    else:
                        sw = sw or _switch_for(spec)
                        effective, counts = placement_port_counts(
                            sw, placement, n_eng)
                    cunits = {c: (p, mapping, op, c, arb, bb)
                              for c in set(counts)}
                    for u in cunits.values():
                        units.setdefault(u, len(units))
                    plans.append(("cont", cunits, (n_eng, arb, bb, placement,
                                                   effective, counts)))
                else:
                    raise ValueError(f"unknown request kind {req[0]!r}")
        if not plans:
            return []
        ordered = sorted(units, key=units.get)
        with spans.span("repro.grid.rows"):
            rows = [_mix_row(spec, u) if isinstance(u[0], EngineMix)
                    else _unit_row(spec, u) for u in ordered]
        out = _run_rows(spec, rows, mesh)

        with spans.span("repro.grid.results"):
            results: List[object] = []
            for plan in plans:
                if plan[0] == "tp":
                    results.append(_tp_result(spec, rows, out, units[plan[1]]))
                    continue
                if plan[0] == "mix":
                    munit, (arb, bb) = plan[1], plan[2]
                    results.append(_cont_result_mix(
                        spec, rows, out, units[munit], arb, bb))
                    continue
                if plan[0] == "mixpl":
                    ports, (n_eng, arb, bb, placement, effective, mix) = \
                        plan[1], plan[2]
                    port_results = []
                    for count, u in ports:
                        jdx = units[u]
                        if isinstance(u[0], EngineMix):
                            port_results.append(
                                (count, _cont_result_mix(spec, rows, out, jdx,
                                                         arb, bb)))
                        else:
                            port_results.append(
                                (count, _cont_result(spec, rows, out, jdx,
                                                     arb, bb)))
                    assert sw is not None
                    results.append(combine_placement_ports(
                        sw, placement, effective, n_eng, port_results,
                        arbitration=arb, burst_beats=bb, mix=mix))
                    continue
                _, cunits, (n_eng, arb, bb, placement, effective, counts) = plan
                per_count = {c: _cont_result(spec, rows, out, units[u], arb, bb)
                             for c, u in cunits.items()}
                if placement == "same_channel":
                    results.append(per_count[n_eng])
                else:
                    assert sw is not None
                    results.append(combine_placement(
                        sw, placement, effective, n_eng, counts, per_count,
                        arbitration=arb, burst_beats=bb))
            return results


# ------------------------------------------------------------- public: grid
@dataclasses.dataclass(frozen=True)
class GridAxes:
    """One experiment cross-product, in Sweep-cache-key axis order.

    The flat point order is ``itertools.product(params, policies, ops,
    num_engines, arbitrations, placements)`` — rightmost axis fastest —
    matching the field order of the Sweep memo keys, so lane ``i`` of a
    :class:`GridResult` is the point ``sweep_points()[i]`` and the two
    orderings compare element for element.  ``arbitrations`` entries are
    ``(arbitration, burst_beats)`` pairs, validated like the per-point
    path.  ``kind="throughput"`` evaluates single-engine throughput
    points and requires the contention axes to stay at their defaults.
    """

    params: Tuple[RSTParams, ...]
    policies: Tuple[Optional[str], ...] = (None,)
    ops: Tuple[str, ...] = ("read",)
    num_engines: Tuple[int, ...] = (1,)
    arbitrations: Tuple[Tuple[str, int], ...] = (("round_robin", 1),)
    placements: Tuple[str, ...] = ("same_channel",)
    kind: str = "contention"

    def __post_init__(self):
        if self.kind not in ("throughput", "contention"):
            raise ValueError(f"unknown grid kind {self.kind!r}")
        if not self.params:
            raise ValueError("GridAxes needs at least one params point")
        if self.kind == "throughput" and (
                self.num_engines != (1,)
                or self.arbitrations != (("round_robin", 1),)
                or self.placements != ("same_channel",)):
            raise ValueError("throughput grids fix the contention axes "
                             "(num_engines/arbitrations/placements)")
        for n in self.num_engines:
            if n < 1:
                raise ValueError(f"num_engines must be >= 1, got {n}")
        for pl in self.placements:
            if pl not in PLACEMENTS:
                raise ValueError(f"unknown placement {pl!r}; "
                                 f"valid: {PLACEMENTS}")

    @property
    def shape(self) -> Tuple[int, ...]:
        return (len(self.params), len(self.policies), len(self.ops),
                len(self.num_engines), len(self.arbitrations),
                len(self.placements))

    @property
    def size(self) -> int:
        return int(np.prod(self.shape))

    def product(self) -> Iterator[Tuple]:
        return itertools.product(self.params, self.policies, self.ops,
                                 self.num_engines, self.arbitrations,
                                 self.placements)

    def sweep_points(self) -> List[object]:
        """The same cross-product as per-point SweepPoints, in lane
        order — the bridge grid-equivalence tests compare along."""
        from repro.core.sweep import (KIND_CONTENTION, KIND_THROUGHPUT,
                                      SweepPoint)
        pts = []
        for p, pol, op, n, (arb, bb), pl in self.product():
            if self.kind == "throughput":
                pts.append(SweepPoint(p, pol, op=op,
                                      kind=KIND_THROUGHPUT))
            else:
                pts.append(SweepPoint(p, pol, op=op,
                                      kind=KIND_CONTENTION,
                                      num_engines=n, arbitration=arb,
                                      burst_beats=bb, placement=pl))
        return pts


@dataclasses.dataclass
class GridResult:
    """Stacked outputs of one :func:`evaluate_grid` call, lane-major.

    ``gbps``/``bound``/``queueing_delay_cycles`` are flat arrays over the
    cross-product (``axes.shape`` row-major, ``sweep_points()`` order);
    ``gbps`` is aggregate GB/s (equals single-engine throughput for
    ``kind="throughput"``).  Full per-point result dataclasses
    materialize lazily through :meth:`results` — building 10^5 Python
    detail dicts would dominate the batched evaluation itself.
    """

    spec: MemorySpec
    axes: GridAxes
    gbps: np.ndarray
    bound: np.ndarray
    queueing_delay_cycles: np.ndarray
    elapsed_seconds: float
    #: Unit lanes per `_route` lane ("periodic"/"full"/"numpy"): lanes
    #: on "numpy" ran on the host, not in the compiled kernel.
    lanes_by_route: Dict[str, int]
    #: Most devices any kernel output was sharded over (1 unsharded,
    #: the mesh size under `mesh`, 0 if every lane ran on the host).
    output_devices: int
    _builder: object = dataclasses.field(repr=False, compare=False)

    @property
    def size(self) -> int:
        return len(self.gbps)

    @property
    def points_per_second(self) -> float:
        return (self.size / self.elapsed_seconds
                if self.elapsed_seconds > 0 else float("inf"))

    def sweep_points(self) -> List[object]:
        return self.axes.sweep_points()

    def results(self) -> List[object]:
        """Materialized per-point result objects, lane order."""
        if not hasattr(self, "_materialized"):
            self._materialized = self._builder()
        return self._materialized

    def result(self, i: int) -> object:
        return self.results()[i]


def evaluate_grid(spec: MemorySpec, axes: GridAxes, *,
                  mesh=None) -> GridResult:
    """Lower one experiment cross-product into one compiled program.

    Expands `axes` to its unit grid (params x policies x ops x
    engine-counts x arbitrations — placements share per-port units),
    evaluates every unit in a single ``jit(vmap)`` kernel call, and maps
    units back onto the point cross-product.  With `mesh` (a 1-D device
    mesh from ``launch.mesh.grid_mesh``) the unit batch is sharded over
    the mesh's ``grid`` axis, padding explicitly via ``shard_grid``.

    Point lane ``i`` corresponds to ``axes.sweep_points()[i]``; a
    per-point ``Sweep`` over those points matches within
    :data:`REL_TOLERANCE` of the NumPy path (grid-equivalence tests).
    """
    t0 = time.perf_counter()
    with spans.span("repro.grid.evaluate"):
        with spans.span("repro.grid.plan"):
            mappings = [get_mapping(spec, pol) for pol in axes.policies]
            for op in axes.ops:
                _direction_overheads(spec, op)   # validate ops eagerly
            for arb, bb in axes.arbitrations:
                _grant_beats(arb, bb, 1 << 30)   # validate pairs eagerly
            for p in axes.params:
                p.validate(spec)

            # Engine-counts needed per (N, placement), plus the per-port combine
            # recipe for non-same_channel placements.
            sw: Optional[SwitchModel] = None
            recipes: Dict[Tuple[int, str], Tuple[str, List[int]]] = {}
            needed = set()
            for n in axes.num_engines:
                for pl in axes.placements:
                    if pl == "same_channel":
                        recipes[(n, pl)] = (pl, [n])
                        needed.add(n)
                    else:
                        sw = sw or _switch_for(spec)
                        effective, counts = placement_port_counts(sw, pl, n)
                        recipes[(n, pl)] = (effective, counts)
                        needed.update(counts)
            ucounts = sorted(needed)
            cpos = {c: k for k, c in enumerate(ucounts)}
            nunits = (len(axes.params) * len(mappings) * len(axes.ops)
                      * len(ucounts) * len(axes.arbitrations))

        # Unit grid: product(params, policies, ops, ucounts, arbitrations),
        # one kernel lane each; host rows built per-axis, then broadcast.
        with spans.span("repro.grid.rows"):
            unit_rows = [
                _unit_row(spec, (p, mapping, op, c, arb, bb))
                for p, mapping, op, c, (arb, bb) in itertools.product(
                    axes.params, mappings, axes.ops, ucounts,
                    axes.arbitrations)]
        out = _run_rows(spec, unit_rows, mesh)

        with spans.span("repro.grid.results"):
            # Map units onto points.  Unit flat index of (ip, ipol, iop, ic, ia):
            # (((ip*npol + ipol)*nop + iop)*ncnt + ic)*narb + ia.
            npm, npol, nop, nn, narb, npl = axes.shape
            ncnt = len(ucounts)
            ip = np.arange(npm).reshape(npm, 1, 1, 1, 1, 1)
            ipol = np.arange(npol).reshape(1, npol, 1, 1, 1, 1)
            iop = np.arange(nop).reshape(1, 1, nop, 1, 1, 1)
            ia = np.arange(narb).reshape(1, 1, 1, 1, narb, 1)
            base = (((ip * npol + ipol) * nop + iop) * ncnt)
            bound_tbl = np.array(_BOUND_NAMES)

            gbps = np.empty(axes.shape, dtype=np.float64)
            bound = np.empty(axes.shape, dtype=object)
            queueing = np.empty(axes.shape, dtype=np.float64)
            for j, n in enumerate(axes.num_engines):
                for k, pl in enumerate(axes.placements):
                    effective, counts = recipes[(n, pl)]
                    if pl == "same_channel":
                        idx = ((base + cpos[n]) * narb + ia)[..., 0, :, 0]
                        gbps[:, :, :, j, :, k] = out["gbps"][idx]
                        bound[:, :, :, j, :, k] = bound_tbl[out["bidx"][idx]]
                        queueing[:, :, :, j, :, k] = out["queueing"][idx]
                        continue
                    # Per-port combine, vectorized over the sub-grid: the count
                    # multiset is fixed per (N, placement), so the capacity cap
                    # and dominant-port choice are, too (engine.combine_placement
                    # materializes the same recipe per point on results()).
                    mult = {c: counts.count(c) for c in set(counts)}
                    raw = np.zeros((npm, npol, nop, narb))
                    qsum = np.zeros((npm, npol, nop, narb))
                    for c, m in mult.items():
                        idxc = ((base + cpos[c]) * narb + ia)[..., 0, :, 0]
                        raw += m * out["gbps"][idxc]
                        qsum += m * c * out["queueing"][idxc]
                    dom = ((base + cpos[max(counts)]) * narb + ia)[..., 0, :, 0]
                    bnd = bound_tbl[out["bidx"][dom]].astype(object)
                    agg = raw.copy()
                    assert sw is not None
                    cap = sw.capacity_cap_gbps(effective)
                    if cap is not None:
                        capped = raw > cap
                        agg = np.where(capped, cap, raw)
                        lateral = sw.topology.lateral_gbps
                        name = ("lateral" if effective == "cross_switch"
                                and lateral is not None and cap == lateral
                                else "switch")
                        bnd = np.where(capped, name, bnd)
                    gbps[:, :, :, j, :, k] = agg
                    bound[:, :, :, j, :, k] = bnd
                    queueing[:, :, :, j, :, k] = qsum / n

            def build() -> List[object]:
                res: List[object] = []
                for (ip_, p), (ipol_, pol), (iop_, op), (_, n), \
                        (ia_, (arb, bb)), (_, pl) in itertools.product(
                        enumerate(axes.params), enumerate(axes.policies),
                        enumerate(axes.ops), enumerate(axes.num_engines),
                        enumerate(axes.arbitrations), enumerate(axes.placements)):
                    del p, pol, op

                    def uidx(c: int) -> int:
                        return ((((ip_ * npol + ipol_) * nop + iop_) * ncnt
                                 + cpos[c]) * narb + ia_)

                    if axes.kind == "throughput":
                        res.append(_tp_result(spec, unit_rows, out, uidx(1)))
                        continue
                    effective, counts = recipes[(n, pl)]
                    if pl == "same_channel":
                        res.append(_cont_result(spec, unit_rows, out, uidx(n),
                                                arb, bb))
                        continue
                    per_count = {c: _cont_result(spec, unit_rows, out, uidx(c),
                                                 arb, bb) for c in set(counts)}
                    res.append(combine_placement(
                        _switch_for(spec), pl, effective, n, counts, per_count,
                        arbitration=arb, burst_beats=bb))
                return res

            return GridResult(spec=spec, axes=axes, gbps=gbps.reshape(-1),
                              bound=bound.reshape(-1),
                              queueing_delay_cycles=queueing.reshape(-1),
                              elapsed_seconds=time.perf_counter() - t0,
                              lanes_by_route=dict(collections.Counter(
                                  _route(r) for r in unit_rows)),
                              output_devices=int(out["devices"].max()),
                              _builder=build)
