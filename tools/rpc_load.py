#!/usr/bin/env python
"""Synthetic control-plane load harness: 1k-10k in-process fake workers
against a REAL gRPC master.

The control-plane scale-out (docs/control-plane.md) claims the master
stops being the ceiling: one delta-encoded ``AgentReportBatch`` per
node per tick instead of one full-payload RPC per process per channel.
This harness is the proof — and the regression gate, the way
``tools/tier1_budget.py`` gates tier-1 wall time:

- it starts a real ``MasterServicer`` behind a real gRPC server (the
  identical dispatch path production agents hit),
- drives N fake nodes through the REAL wire protocol (``comm``
  serialization, ``DeltaEncoder`` telemetry, piggybacked poll legs),
  each tick mutating a churn fraction of every node's scalars,
- measures steady-state RPCs/node/tick, client-observed latency
  p50/p99, wire bytes, and master-side service seconds per tick (the
  dispatch-time histogram the servicer already exports), and
- verifies the master's RECONSTRUCTED scalars equal every node's
  current scalars exactly — compression claims mean nothing if the
  payload doesn't survive.

Modes:

- ``delta``  — the production path: delta batches, full only on resync;
- ``full``   — batched but full snapshots every tick: the wire-bytes
  baseline the ≤0.4x delta gate divides against;
- ``legacy`` — the pre-batch protocol (TrainMetricsReport +
  GlobalStepReport reports, WorkerCommandRequest + ParallelConfigRequest
  polls = 4 RPCs/node/tick): the RPC-count baseline.

CLI::

    python tools/rpc_load.py --nodes 1000 --ticks 5 --json
    python tools/rpc_load.py --nodes 10000 --ticks 3      # slow tier
    python tools/rpc_load.py --nodes 1000 --gate-rpcs 1.25 \
        --gate-p99-ms 200 --gate-delta-ratio 0.4          # CI gate

Exit status is nonzero when any ``--gate-*`` bound is violated
(``tests/test_control_plane.py`` drives ``run_load`` at small fleets).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import numpy as np

if __package__ in (None, ""):  # script execution without pip install
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )

import grpc

from dlrover_tpu.common import comm
from dlrover_tpu.common.telemetry_delta import DeltaEncoder
from dlrover_tpu.master.servicer import (
    SERVICE_NAME,
    MasterServicer,
    create_master_service,
)

# realistic registry-style scalar names (labels inline, like the
# runtime-metrics forward): long repeated key strings are exactly what
# delta encoding and gzip exist for
_KEY_POOL = (
    "dlrover_pipeline_step_time_ms",
    "dlrover_goodput_seconds_total{category=\"productive_compute\"}",
    "dlrover_goodput_seconds_total{category=\"data_stall\"}",
    "dlrover_embedding_gather_hit_pct{table=\"t0\"}",
    "loss",
    "lr",
)


class _CollectorSink:
    """Stores the last reconstructed scalars per worker — the
    round-trip verification surface."""

    def __init__(self):
        self.metrics: Dict[int, Dict[str, float]] = {}
        self.reports = 0

    def report_train_metrics(self, worker_id, step, metrics):
        # REPLACE semantics: the servicer's contract is forwarding the
        # reconstructed FULL snapshot — a servicer that silently
        # degrades to forwarding bare deltas fails the round-trip
        # verification here
        self.metrics[worker_id] = dict(metrics)
        self.reports += 1


class _SpeedSink:
    def __init__(self):
        self.steps: Dict[int, int] = {}
        self.reports = 0

    def collect_global_step(self, step, ts=None, node_id=0):
        self.steps[node_id] = step
        self.reports += 1


class FleetSender:
    """A small pool of shared channels: 10k fake nodes must not open
    10k TCP connections — node identity rides in ``BaseRequest``, not
    in the channel."""

    def __init__(self, addr: str, channels: int = 8,
                 compression: bool = False):
        comp = (
            grpc.Compression.Gzip
            if compression
            else grpc.Compression.NoCompression
        )
        opts = [
            ("grpc.max_send_message_length", 256 << 20),
            ("grpc.max_receive_message_length", 256 << 20),
        ]
        self._channels = [
            grpc.insecure_channel(addr, options=opts, compression=comp)
            for _ in range(channels)
        ]
        self._report = [
            ch.unary_unary(f"/{SERVICE_NAME}/report")
            for ch in self._channels
        ]
        self._get = [
            ch.unary_unary(f"/{SERVICE_NAME}/get")
            for ch in self._channels
        ]

    def close(self):
        for ch in self._channels:
            ch.close()

    def _wrap(self, node_id: int, message) -> bytes:
        return comm.serialize_message(
            comm.BaseRequest(
                node_id=node_id,
                node_type="worker",
                data=comm.serialize_message(message),
            )
        )

    def call(
        self, node_id: int, message, rpc: str = "report"
    ) -> Tuple[object, float, int]:
        """Returns (payload, latency_s, request_bytes)."""
        stubs = self._report if rpc == "report" else self._get
        stub = stubs[node_id % len(stubs)]
        req = self._wrap(node_id, message)
        t0 = time.perf_counter()
        resp_bytes = stub(req, timeout=30.0)
        dt = time.perf_counter() - t0
        resp: comm.BaseResponse = comm.deserialize_message(resp_bytes)
        if not resp.success:
            raise RuntimeError(
                f"master rejected {type(message).__name__}: {resp.message}"
            )
        return comm.deserialize_message(resp.data), dt, len(req)


class FakeNode:
    """One fake agent: a scalar dict under churn, a step counter, and
    the real delta-encoder state machine."""

    def __init__(self, node_id: int, nscalars: int, rng: np.random.Generator):
        self.node_id = node_id
        self._rng = rng
        self._enc = DeltaEncoder()
        self.step = int(rng.integers(0, 1000))
        self.scalars: Dict[str, float] = {}
        for i in range(nscalars):
            base = _KEY_POOL[i % len(_KEY_POOL)]
            self.scalars[f"{base}_{i:03d}"] = float(rng.random())
        self.rpcs = 0
        self.bytes_out = 0
        self.resyncs = 0

    def churn(self, frac: float):
        self.step += 1
        keys = list(self.scalars)
        n = max(1, int(len(keys) * frac))
        for k in self._rng.choice(len(keys), size=n, replace=False):
            self.scalars[keys[int(k)]] = float(self._rng.random())

    def _batch(self, force_full: bool) -> comm.AgentReportBatch:
        if force_full:
            self._enc.force_resync()
        full, seq, deltas = self._enc.encode({0: self.scalars})
        changed, removed = deltas.get(0, ({}, []))
        return comm.AgentReportBatch(
            node_id=self.node_id,
            epoch=self._enc.epoch,
            seq=seq,
            full=full,
            procs=[
                comm.ProcDelta(
                    proc_id=0,
                    step=self.step,
                    step_ts=float(self.step),
                    step_advanced=True,
                    changed=changed,
                    removed=removed,
                )
            ],
            command_ack_id=0,
            paral_version=0,
        )

    def tick_batched(
        self, sender: FleetSender, force_full: bool
    ) -> List[float]:
        batch = self._batch(force_full)
        resp, dt, nbytes = sender.call(self.node_id, batch)
        self.rpcs += 1
        self.bytes_out += nbytes
        lat = [dt]
        if isinstance(resp, comm.AgentBatchResponse) and resp.resync:
            # resend a full snapshot immediately (counted: the gate's
            # 1.25 headroom is exactly this)
            self.resyncs += 1
            self._enc.force_resync()
            batch = self._batch(False)
            _, dt2, nbytes2 = sender.call(self.node_id, batch)
            self.rpcs += 1
            self.bytes_out += nbytes2
            lat.append(dt2)
            self._enc.ack(batch.seq)
        else:
            self._enc.ack(batch.seq)
        return lat

    def tick_legacy(self, sender: FleetSender) -> List[float]:
        """The pre-batch protocol: one full-payload telemetry report,
        one step report, one command poll, one paral-config poll."""
        lat = []
        for message, rpc in (
            (
                comm.TrainMetricsReport(
                    node_id=self.node_id,
                    step=self.step,
                    metrics=dict(self.scalars),
                ),
                "report",
            ),
            (
                comm.GlobalStepReport(
                    node_id=self.node_id, step=self.step,
                    timestamp=float(self.step),
                ),
                "report",
            ),
            (comm.WorkerCommandRequest(node_id=self.node_id), "get"),
            (comm.ParallelConfigRequest(node_id=self.node_id), "get"),
        ):
            _, dt, nbytes = sender.call(self.node_id, message, rpc)
            self.rpcs += 1
            self.bytes_out += nbytes
            lat.append(dt)
        return lat


def _service_seconds(servicer: MasterServicer) -> float:
    """Master-side dispatch service seconds so far (the sum of the
    per-message latency histograms) — the in-process proxy for master
    CPU-seconds."""
    total = 0.0
    hist = servicer._rpc_obs.latency
    for child in hist._children.values():
        total += child.sum
    return total


def run_load(
    nodes: int = 1000,
    ticks: int = 5,
    nscalars: int = 60,
    churn: float = 0.15,
    mode: str = "delta",
    channels: int = 8,
    pool: int = 32,
    compression: bool = False,
    seed: int = 0,
    verify_sample: int = 32,
    master_restart_tick: Optional[int] = None,
) -> dict:
    """Drive the fleet; returns the measurement dict (see module doc).
    ``master_restart_tick`` simulates a master restart before that tick
    by wiping the servicer's delta state — every node must resync and
    converge (the mixed-version/failover drill)."""
    assert mode in ("delta", "full", "legacy")
    collector = _CollectorSink()
    speed = _SpeedSink()
    servicer = MasterServicer(
        metric_collector=collector, speed_monitor=speed
    )
    port = comm.find_free_port()
    server = create_master_service(port, servicer, max_workers=pool)
    sender = FleetSender(
        f"127.0.0.1:{port}", channels=channels, compression=compression
    )
    rng = np.random.default_rng(seed)
    fleet = [
        FakeNode(i, nscalars, np.random.default_rng(seed + i))
        for i in range(nodes)
    ]
    latencies: List[float] = []
    tick_bytes: List[int] = []
    svc0 = _service_seconds(servicer)
    t_start = time.perf_counter()
    try:
        with ThreadPoolExecutor(max_workers=pool) as ex:
            for tick in range(ticks):
                if tick == master_restart_tick:
                    # a restarted master has no delta snapshots: the
                    # decoder is fresh, every delta must resync
                    servicer._delta.__init__()
                for n in fleet:
                    n.churn(churn)
                bytes0 = sum(n.bytes_out for n in fleet)
                if mode == "legacy":
                    futs = [
                        ex.submit(n.tick_legacy, sender) for n in fleet
                    ]
                else:
                    futs = [
                        ex.submit(n.tick_batched, sender, mode == "full")
                        for n in fleet
                    ]
                for f in futs:
                    latencies.extend(f.result())
                tick_bytes.append(
                    sum(n.bytes_out for n in fleet) - bytes0
                )
        wall_s = time.perf_counter() - t_start
        svc_s = _service_seconds(servicer) - svc0
        # round-trip verification: the master's reconstruction must be
        # IDENTICAL to the node's current scalars (sampled fleet-wide)
        sample = rng.choice(
            nodes, size=min(verify_sample, nodes), replace=False
        )
        mismatches = 0
        for i in sample:
            n = fleet[int(i)]
            got = collector.metrics.get(n.node_id, {})
            if got != n.scalars:
                mismatches += 1
        lat_ms = np.asarray(latencies) * 1e3
        total_rpcs = sum(n.rpcs for n in fleet)
        return {
            "mode": mode,
            "nodes": nodes,
            "ticks": ticks,
            "scalars_per_node": nscalars,
            "churn": churn,
            "compression": compression,
            "rpcs_total": total_rpcs,
            "rpcs_per_node_per_tick": round(
                total_rpcs / (nodes * ticks), 4
            ),
            "resyncs": sum(n.resyncs for n in fleet),
            "wire_bytes_total": sum(n.bytes_out for n in fleet),
            "wire_bytes_per_node_per_tick": round(
                sum(n.bytes_out for n in fleet) / (nodes * ticks), 1
            ),
            # steady state = ticks after the first (the first delta
            # tick is a full snapshot by construction)
            "wire_bytes_steady_per_node_per_tick": round(
                sum(tick_bytes[1:]) / max(nodes * (ticks - 1), 1), 1
            )
            if ticks > 1
            else None,
            "rpc_p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
            "rpc_p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
            "master_service_s_per_tick": round(svc_s / ticks, 4),
            "wall_s": round(wall_s, 2),
            "reconstructed_ok": mismatches == 0,
            "reconstructed_mismatches": mismatches,
            "collector_reports": collector.reports,
            "speed_reports": speed.reports,
        }
    finally:
        sender.close()
        server.stop(grace=None)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--nodes", type=int, default=1000)
    p.add_argument("--ticks", type=int, default=5)
    p.add_argument("--scalars", type=int, default=60)
    p.add_argument("--churn", type=float, default=0.15)
    p.add_argument(
        "--mode", choices=("delta", "full", "legacy", "compare"),
        default="compare",
        help="compare = delta + full baseline (the ratio gate's shape)",
    )
    p.add_argument("--channels", type=int, default=8)
    p.add_argument("--pool", type=int, default=32)
    p.add_argument("--compression", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--restart-tick", type=int, default=None,
        help="wipe the master's delta state before this tick "
        "(failover drill: every node must resync and converge)",
    )
    p.add_argument("--json", action="store_true")
    p.add_argument("--gate-rpcs", type=float, default=None,
                   help="fail if delta-mode RPCs/node/tick exceeds this")
    p.add_argument("--gate-p99-ms", type=float, default=None)
    p.add_argument("--gate-delta-ratio", type=float, default=None,
                   help="fail if delta wire bytes / full wire bytes "
                   "exceeds this (compare mode)")
    args = p.parse_args(argv)

    out: dict = {}
    modes = (
        ["delta", "full"] if args.mode == "compare" else [args.mode]
    )
    for mode in modes:
        out[mode] = run_load(
            nodes=args.nodes,
            ticks=args.ticks,
            nscalars=args.scalars,
            churn=args.churn,
            mode=mode,
            channels=args.channels,
            pool=args.pool,
            compression=args.compression,
            seed=args.seed,
            master_restart_tick=args.restart_tick,
        )
    if "delta" in out and "full" in out:
        out["delta_vs_full_bytes"] = round(
            out["delta"]["wire_bytes_total"]
            / max(out["full"]["wire_bytes_total"], 1),
            4,
        )
    if args.json:
        print(json.dumps(out, indent=2))
    else:
        for mode, r in out.items():
            if not isinstance(r, dict):
                continue
            print(
                f"{mode:7s} rpcs/node/tick={r['rpcs_per_node_per_tick']}"
                f" p99={r['rpc_p99_ms']}ms"
                f" bytes/node/tick={r['wire_bytes_per_node_per_tick']}"
                f" master_s/tick={r['master_service_s_per_tick']}"
                f" reconstructed_ok={r['reconstructed_ok']}"
            )
        if "delta_vs_full_bytes" in out:
            print(f"delta/full wire bytes = {out['delta_vs_full_bytes']}")

    ok = True
    ref = out.get("delta") or next(iter(out.values()))
    if not ref.get("reconstructed_ok", False):
        print("GATE FAIL: reconstructed master-side scalars mismatch")
        ok = False
    if args.gate_rpcs is not None and (
        ref["rpcs_per_node_per_tick"] > args.gate_rpcs
    ):
        print(
            f"GATE FAIL: {ref['rpcs_per_node_per_tick']} RPCs/node/tick "
            f"> {args.gate_rpcs}"
        )
        ok = False
    if args.gate_p99_ms is not None and (
        ref["rpc_p99_ms"] > args.gate_p99_ms
    ):
        print(f"GATE FAIL: p99 {ref['rpc_p99_ms']}ms > {args.gate_p99_ms}ms")
        ok = False
    if args.gate_delta_ratio is not None:
        ratio = out.get("delta_vs_full_bytes")
        if ratio is None or ratio > args.gate_delta_ratio:
            print(
                f"GATE FAIL: delta/full wire ratio {ratio} > "
                f"{args.gate_delta_ratio}"
            )
            ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
