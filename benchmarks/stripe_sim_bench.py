"""Striped slice broadcast sim: paired striped/unstriped fan-out numbers.

The north-star claim — stripe the DCN pull 1/S per host, let ICI finish
the copy — needs link-level accounting to measure, and real processes on
one machine run everything over one loopback NIC where DCN and ICI are
indistinguishable. This bench drives the REAL data-plane
components (daemon/peer/piece_dispatcher.PieceDispatcher in stripe mode,
scheduler/scheduling/stripe.plan_stripe) through a deterministic
discrete-event simulation with modeled links:

  - every host has one DCN NIC (ingress+egress FIFO servers at DCN_BW) —
    cross-slice piece transfers occupy both ends;
  - intra-slice transfers ride the ICI fabric (per-host FIFO at ICI_BW);
  - piece availability propagates with a small announce latency, like the
    sync streams.

Both modes run the same topology, seed, and link model; only the stripe
plan differs. Reported per mode: per-host DCN bytes, aggregate GB/s
(virtual), p50 ttfp. Virtual time + seeded RNG = byte-for-byte
reproducible results.

Usage: python benchmarks/stripe_sim_bench.py [--slices 2]
       [--hosts-per-slice 4] [--pieces 64] [--piece-mb 8]
"""

from __future__ import annotations

import argparse
import heapq
import json
import os
import random
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from dragonfly2_tpu.daemon.peer.piece_dispatcher import (  # noqa: E402
    PieceDispatcher,
)
from dragonfly2_tpu.scheduler.scheduling import stripe as stripe_mod  # noqa: E402

DCN_BW = 2.5e9       # bytes/s per host NIC direction (v5p DCN-class)
ICI_BW = 40e9        # bytes/s per host intra-slice (ICI is ~an order up)
LINK_LATENCY = 0.002   # per-transfer propagation+setup
ANNOUNCE_LATENCY = 0.001  # piece-availability sync push
WORKERS = 4          # per-host piece parallelism (daemon default)


class SimHost:
    def __init__(self, host_id: str, slice_name: str, rank_key: tuple):
        self.id = host_id
        self.slice = slice_name
        self.rank_key = rank_key
        self.dispatcher = PieceDispatcher()
        self.inflight = 0
        self.done_at = -1.0
        self.ttfp = -1.0
        self.started_at = 0.0
        self.dcn_bytes = 0
        self.ici_bytes = 0
        self.served_bytes = 0
        # FIFO link servers: next instant each link is free.
        self.dcn_free = 0.0   # the NIC (shared ingress+egress — one wire)
        self.ici_free = 0.0


def run_sim(*, n_slices: int, hosts_per_slice: int, n_pieces: int,
            piece_size: int, striped: bool, seed_rng: int = 7) -> dict:
    random.seed(seed_rng)
    content = n_pieces * piece_size

    hosts: list[SimHost] = []
    for s in range(n_slices):
        for w in range(hosts_per_slice):
            hid = f"s{s}w{w}"
            hosts.append(SimHost(hid, f"slice-{s}", (w, hid, hid)))
    seed = SimHost("seed", "slice-seed", (0, "seed", "seed"))
    seed.dispatcher.total_piece_count = n_pieces
    by_id = {h.id: h for h in hosts}
    by_id[seed.id] = seed

    # Parent wiring mirrors the scheduler's handout: the seed is every
    # host's cross-slice (DCN) parent; slice mates ride the stripe-mates
    # channel as same_slice parents. Identical in both modes — only the
    # wanted-set differs.
    for h in hosts:
        d = h.dispatcher
        d.total_piece_count = n_pieces
        d.piece_size = piece_size
        d.content_length = content
        p = d.upsert_parent(seed.id, "10.0.0.1", 1, tpu_slice=seed.slice)
        p.pieces.update(range(n_pieces))
        for m in hosts:
            if m is not h and m.slice == h.slice:
                d.upsert_parent(m.id, "10.0.0.2", 1, same_slice=True,
                                tpu_slice=m.slice)
        if striped:
            members = [m.rank_key for m in hosts if m.slice == h.slice]
            plan = stripe_mod.plan_stripe(members, h.id)
            if plan is not None:
                d.set_stripe(plan["slice_size"], plan["slice_rank"])

    events: list[tuple] = []   # (time, seq, fn, args)
    seq = 0

    def push(t, fn, *args):
        nonlocal seq
        heapq.heappush(events, (t, seq, fn, args))
        seq += 1

    def announce(now: float, owner: SimHost, piece: int) -> None:
        """Piece landed on ``owner``: its children learn after the sync
        push latency (the seed's pieces are pre-known)."""
        for h in hosts:
            if h is owner:
                continue
            if owner.id in h.dispatcher.parents:
                h.dispatcher.on_parent_pieces(owner.id, [piece])
                push(now, try_start, h)

    def finish_transfer(now: float, h: SimHost, assignment,
                        cost_s: float) -> None:
        h.inflight -= 1
        if h.ttfp < 0:
            h.ttfp = now - h.started_at
        h.dispatcher.report_success(assignment, max(1, int(cost_s * 1000)))
        push(now + ANNOUNCE_LATENCY, announce, h, assignment.piece_num)
        if h.dispatcher.is_complete() and h.done_at < 0:
            h.done_at = now
        push(now, try_start, h)

    def try_start(now: float, h: SimHost) -> None:
        while h.inflight < WORKERS:
            a = h.dispatcher.try_get()
            if a is None:
                return
            h.inflight += 1
            parent = by_id[a.parent.peer_id]
            size = a.expected_size if a.expected_size > 0 else piece_size
            if a.parent.same_slice:
                start = max(now, h.ici_free, parent.ici_free)
                done = start + size / ICI_BW + LINK_LATENCY
                h.ici_free = parent.ici_free = done
                h.ici_bytes += size
            else:
                start = max(now, h.dcn_free, parent.dcn_free)
                done = start + size / DCN_BW + LINK_LATENCY
                h.dcn_free = parent.dcn_free = done
                h.dcn_bytes += size
            parent.served_bytes += size
            push(done, finish_transfer, h, a, done - now)

    for h in hosts:
        push(0.0, try_start, h)
    now = 0.0
    while events:
        now, _, fn, args = heapq.heappop(events)
        fn(now, *args)
        if all(h.done_at >= 0 for h in hosts):
            break

    incomplete = [h.id for h in hosts if h.done_at < 0]
    if incomplete:
        raise AssertionError(f"sim stalled; incomplete hosts: {incomplete}")
    wall = max(h.done_at for h in hosts)
    total = content * len(hosts)
    return {
        "striped": striped,
        "hosts": len(hosts),
        "slices": n_slices,
        "hosts_per_slice": hosts_per_slice,
        "pieces": n_pieces,
        "piece_mb": piece_size / (1 << 20),
        "content_mb": content / (1 << 20),
        "wall_s": round(wall, 4),
        "aggregate_gbps": round(total / wall / 1e9, 3),
        "p50_ttfp_s": round(statistics.median(h.ttfp for h in hosts), 4),
        "per_host_dcn_mb": {
            h.id: round(h.dcn_bytes / (1 << 20), 2) for h in hosts},
        "max_host_dcn_mb": round(
            max(h.dcn_bytes for h in hosts) / (1 << 20), 2),
        "total_dcn_mb": round(
            sum(h.dcn_bytes for h in hosts) / (1 << 20), 2),
        "total_ici_mb": round(
            sum(h.ici_bytes for h in hosts) / (1 << 20), 2),
        "seed_dcn_egress_mb": round(seed.served_bytes / (1 << 20), 2),
        "link_model": {"dcn_gbps": DCN_BW / 1e9, "ici_gbps": ICI_BW / 1e9,
                       "latency_s": LINK_LATENCY},
    }


def run_paired(*, n_slices: int, hosts_per_slice: int, n_pieces: int,
               piece_size: int) -> dict:
    unstriped = run_sim(n_slices=n_slices, hosts_per_slice=hosts_per_slice,
                        n_pieces=n_pieces, piece_size=piece_size,
                        striped=False)
    striped = run_sim(n_slices=n_slices, hosts_per_slice=hosts_per_slice,
                      n_pieces=n_pieces, piece_size=piece_size,
                      striped=True)
    return {
        "config": "stripe-sim",
        "striped": striped,
        "unstriped": unstriped,
        "speedup": round(striped["aggregate_gbps"]
                         / unstriped["aggregate_gbps"], 3),
        "dcn_bytes_ratio": round(striped["total_dcn_mb"]
                                 / unstriped["total_dcn_mb"], 3),
    }


def check(result: dict) -> None:
    """Acceptance bounds shared with the pytest wrapper."""
    s, u = result["striped"], result["unstriped"]
    content_mb = s["content_mb"]
    hps = s["hosts_per_slice"]
    # Per-host DCN bytes <= file/S + one piece of slack (uneven stripes).
    bound = content_mb / hps + s["piece_mb"]
    assert s["max_host_dcn_mb"] <= bound, (s["max_host_dcn_mb"], bound)
    # Striping must beat the unstriped control by the claimed margin.
    assert result["speedup"] >= 1.5, result["speedup"]
    assert s["max_host_dcn_mb"] < u["max_host_dcn_mb"], result
    # Identical content either way: every host completed all pieces (the
    # sim asserts completion inside run_sim).


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slices", type=int, default=2)
    ap.add_argument("--hosts-per-slice", type=int, default=4)
    ap.add_argument("--pieces", type=int, default=64)
    ap.add_argument("--piece-mb", type=int, default=8)
    args = ap.parse_args()

    result = run_paired(n_slices=args.slices,
                        hosts_per_slice=args.hosts_per_slice,
                        n_pieces=args.pieces,
                        piece_size=args.piece_mb << 20)
    check(result)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
