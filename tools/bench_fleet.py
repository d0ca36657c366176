"""Fleet observability bench: scrape, merge, stitch, chaos (ISSUE 13).

One 3-subprocess-replica fleet under live traffic, three gated legs:

* **stitch** — one traced request through the fabric; the parent's
  :meth:`~nnstreamer_tpu.obs.fleet.FleetView.stitch_trace` must yield
  ONE Perfetto document where the parent root/attempt spans and the
  subprocess replica's serving + fused spans share the SAME trace_id,
  on distinct per-process lanes.
* **merge** — the fleet-merged ``serving:query`` digest must equal the
  bucket-wise merge of the replicas' raw exports (the exactness
  property), with every live replica contributing.
* **chaos** — SIGKILL one of the three replicas MID-SCRAPE while
  traffic flows: the fleet snapshot stays coherent (all three
  memberships reported, the dead replica marked not-ok/stale within
  the staleness bound, survivors fresh), the merged series keeps
  serving reads, zero client-visible request errors, and the scrape
  tick thread joins cleanly at stop (zero thread leaks — run under
  NNS_TSAN=1 in CI for lock-order checking too).

Report written to FLEET_r13.json (full mode) — the ISSUE 13 trajectory
point.

PR 18 adds the zero-copy data-plane legs (docs/transport.md):

* **wire_overhead** — codec µs/frame, NNSB binary vs NNST/JSON, same
  frames both ways with byte parity asserted; gate: binary ≤ 0.5× JSON.
* **shm_vs_tcp** — same-host echo fps, negotiated binary+shm ring vs
  forced-JSON loopback TCP; gate: shm ≥ 1.5× TCP, plus the XFERCHECK
  ledger assertion that the shm path moves only descriptor bytes
  through ``wire:socket`` (zero payload bytes on the socket).

The wire legs' report lands in WIRE_r18.json (full mode).

    python tools/bench_fleet.py           # full bench, JSON report
    python tools/bench_fleet.py --smoke   # CI gate, short run

Process layout: this tool starts several replica subprocesses that each
initialize jax, and a TPU chip belongs to one process at a time. It is a
CPU tool: run it with ``JAX_PLATFORMS=cpu`` (as CI does). On a one-chip
host the second child cannot get the chip and ``wait_ready`` raises
``ReplicaDeviceError``; one chip per child is ROADMAP R6.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

CAPS = "other/tensors,format=static,dimensions=4,types=float32"
STAGE = ("tensor_filter framework=jax model=builtin://scaler?factor=2 ! "
         "tensor_filter framework=jax model=builtin://scaler?factor=3")


class _Traffic:
    """Closed-loop keyed traffic across the ring; typed error buckets."""

    def __init__(self, ps, workers: int = 2, timeout: float = 15.0):
        self.ps = ps
        self.timeout = timeout
        self.completed = 0
        self.errors: list = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads = [
            threading.Thread(target=self._run, name=f"fabric:bench:{i}",
                             daemon=True)
            for i in range(workers)]

    def _run(self) -> None:
        import numpy as np

        me = threading.current_thread().name
        n = 0
        while not self._stop.is_set():
            n += 1
            try:
                self.ps.request([np.ones(4, np.float32)],
                                key=f"{me}:{n}", timeout=self.timeout)
                with self._lock:
                    self.completed += 1
            except Exception as e:  # noqa: BLE001
                with self._lock:
                    self.errors.append(f"{type(e).__name__}: {e}")
            self._stop.wait(0.02)

    def start(self):
        for t in self._threads:
            t.start()
        return self

    def stop(self):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=self.timeout + 5.0)


def _leg_stitch(ps, view) -> dict:
    import numpy as np

    from nnstreamer_tpu.obs import context as obs_ctx
    from nnstreamer_tpu.obs.fleet import PARENT_REPLICA

    ps.request([np.ones(4, np.float32)], key="stitch", timeout=30.0)
    roots = [s for s in obs_ctx.finished_spans()
             if s.kind == "fabric" and s.parent_id is None]
    tid = roots[-1].trace_id
    view.tick()
    doc = view.stitch_trace(tid)
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    lanes: dict = {}
    for e in spans:
        lanes.setdefault(e["args"]["replica"], set()).add(e["cat"])
    child = [r for r in lanes if r != PARENT_REPLICA]
    one_trace = bool(spans) and \
        {e["args"]["trace_id"] for e in spans} == {tid}
    child_kinds = set().union(*(lanes[r] for r in child)) if child else set()
    return {
        "trace_id": tid,
        "spans": len(spans),
        "process_lanes": len({e["pid"] for e in spans}),
        "parent_kinds": sorted(lanes.get(PARENT_REPLICA, ())),
        "child_kinds": sorted(child_kinds),
        "ok": (one_trace and "fabric" in lanes.get(PARENT_REPLICA, ())
               and {"serving", "fused"} <= child_kinds
               and len({e["pid"] for e in spans}) >= 2),
    }


def _leg_merge(ps, view) -> dict:
    from nnstreamer_tpu.obs.profile import QuantileDigest

    view.tick()
    merged = view.request_total("serving:query")
    manual = None
    contributing = 0
    for st in view._state_rows():
        req = (st.profile_raw or {}).get("requests", {}).get("serving:query")
        if not req:
            continue
        contributing += 1
        d = QuantileDigest.from_dict(req["total"])
        manual = d if manual is None else manual.merge(d)
    exact = (merged is not None and manual is not None
             and merged.to_dict() == manual.to_dict())
    return {
        "replicas_contributing": contributing,
        "merged_count": 0 if merged is None else merged.count,
        "merged_p50_ms": (0.0 if merged is None
                          else round(merged.quantile(0.5) * 1e3, 3)),
        "merged_p99_ms": (0.0 if merged is None
                          else round(merged.quantile(0.99) * 1e3, 3)),
        "ok": exact and contributing == len(ps.services()),
    }


def _leg_chaos(ps, view, settle_s: float) -> dict:
    killed = ps.kill_replica(0)
    t_kill = time.monotonic()
    ps.reap_dead()  # fail-fast evict (the autoscaler's reaping half)
    t_marked = None
    deadline = t_kill + max(15.0, settle_s * 4)
    while time.monotonic() < deadline:
        view.tick()
        rows = {r["replica"]: r for r in view.replicas()}
        dead = rows.get(killed)
        if dead is not None and not dead["ok"]:
            t_marked = time.monotonic()
            break
        time.sleep(0.1)
    time.sleep(settle_s)  # staleness bound elapses, survivors keep fresh
    view.tick()
    snap = view.snapshot()
    rows = {r["replica"]: r for r in snap["replicas"]}
    survivors = [r for rid, r in rows.items() if rid != killed]
    merged_alive = "serving:query" in snap["profile"]["requests"]
    return {
        "killed": killed,
        "time_to_marked_s": (None if t_marked is None
                             else round(t_marked - t_kill, 3)),
        "membership": len(rows),
        "dead_stale": bool(rows.get(killed, {}).get("stale")),
        "survivors_fresh": all(r["ok"] and not r["stale"]
                               for r in survivors),
        "merged_series_alive": merged_alive,
        "ok": (t_marked is not None and len(rows) == 3
               and bool(rows.get(killed, {}).get("stale"))
               and all(r["ok"] and not r["stale"] for r in survivors)
               and merged_alive),
    }


# ---------------------------------------------------------------------------
# zero-copy data-plane legs (PR 18, docs/transport.md)
# ---------------------------------------------------------------------------

def _wire_frame(ntensors: int = 4, dim: int = 8):
    import numpy as np

    from nnstreamer_tpu.core import Buffer

    return Buffer([np.arange(dim, dtype=np.float32) + i
                   for i in range(ntensors)],
                  pts=0.25, meta={"client_id": 1, "tag": "bench"})


def _leg_wire_overhead(frames: int) -> dict:
    """Wire-path overhead µs/frame over identical frames: what each
    codec actually costs per frame on the socket path — NNSB emits
    scatter-gather parts TX (``sendmsg`` joins them in the kernel) and
    decodes one contiguous received payload RX; NNST pays its inherent
    gather in ``pack_tensors`` TX and ``unpack_tensors`` RX. Byte
    parity is asserted on the same frames."""
    import numpy as np

    from nnstreamer_tpu.core.serialize import pack_tensors, unpack_tensors
    from nnstreamer_tpu.transport.frame import (decode_frame, encode_frame,
                                                encode_frame_bytes)

    buf = _wire_frame()
    bin_blob = bytes(encode_frame_bytes(buf))   # the RX side's payload
    json_blob = bytes(pack_tensors(buf))

    def sig(b):
        return tuple(np.ascontiguousarray(t).tobytes() for t in b.tensors)

    parity = (sig(decode_frame(bin_blob)) == sig(buf)
              and sig(unpack_tensors(json_blob)) == sig(buf))

    def clock(enc, dec, blob):
        t0 = time.perf_counter()
        for _ in range(frames):
            enc(buf)
            dec(blob)
        return (time.perf_counter() - t0) / frames * 1e6

    # warm both codecs off the clock
    for _ in range(64):
        encode_frame(buf)
        decode_frame(bin_blob)
        pack_tensors(buf)
        unpack_tensors(json_blob)
    json_us = clock(pack_tensors, unpack_tensors, json_blob)
    bin_us = clock(encode_frame, decode_frame, bin_blob)
    ratio = bin_us / json_us if json_us else float("inf")
    return {
        "frames": frames,
        "json_us_per_frame": round(json_us, 2),
        "binary_us_per_frame": round(bin_us, 2),
        "binary_over_json": round(ratio, 3),
        "byte_parity": parity,
        "ok": parity and ratio <= 0.5,
    }


def _echo_server():
    """QueryServer + echo pump; returns (server, stop_callable)."""
    import queue as _queue

    from nnstreamer_tpu.query.server import QueryServer

    srv = QueryServer().start()
    stop = threading.Event()

    def pump():
        while not stop.is_set():
            try:
                item = srv.inbox.get(timeout=0.05)
            except _queue.Empty:
                continue
            if isinstance(item, tuple):  # ("eos", cid)
                continue
            cid = item.meta.pop("client_id")
            idx = item.meta.pop("_qserve_idx", None)
            srv.send(cid, item, mark_idx=idx)

    t = threading.Thread(target=pump, name="bench:echo", daemon=True)
    t.start()

    def shutdown():
        stop.set()
        t.join(timeout=5.0)
        srv.stop()

    return srv, shutdown


def _leg_shm_vs_tcp(seconds: float) -> dict:
    """Same-host echo fps: negotiated binary+shm vs forced-JSON loopback
    TCP, identical ~512 KiB payloads, one client each way. Also runs one
    shm request under the XFERCHECK ledger and asserts the socket moved
    descriptor bytes only."""
    import numpy as np

    from nnstreamer_tpu.analysis import sanitizer
    from nnstreamer_tpu.core import Buffer, parse_caps_string
    from nnstreamer_tpu.query.client import QueryClient

    caps = parse_caps_string(CAPS)
    payload = np.zeros(128 * 1024, np.float32)  # 512 KiB, fits one slot

    def fps(wire: str, shm: bool) -> tuple:
        srv, shutdown = _echo_server()
        cli = QueryClient("127.0.0.1", srv.port, wire=wire, shm=shm)
        try:
            cli.connect(caps)
            negotiated = cli.wire_format + ("+shm" if cli.shm_active else "")
            for _ in range(3):  # warm
                cli.request(Buffer([payload]), timeout=15.0)
            n = 0
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                cli.request(Buffer([payload]), timeout=15.0)
                n += 1
            return n / (time.perf_counter() - t0), negotiated
        finally:
            cli.close()
            shutdown()

    tcp_fps, tcp_wire = fps("json", shm=False)
    shm_fps, shm_wire = fps("auto", shm=True)

    # XFERCHECK proof: one shm request, payload bytes in shm:write,
    # descriptor-sized bytes only through wire:socket
    was = sanitizer.xfercheck_enabled()
    sanitizer.enable_xfercheck()
    try:
        srv, shutdown = _echo_server()
        cli = QueryClient("127.0.0.1", srv.port)
        try:
            cli.connect(caps)
            sanitizer.reset_xfercheck()  # drop handshake bytes
            cli.request(Buffer([payload]), timeout=15.0)
        finally:
            cli.close()
            shutdown()
        rows = {(r["stage"], r["direction"]): r["bytes"]
                for r in sanitizer.xfer_transfers()}
        socket_b = rows.get(("wire:socket", "host"), 0)
        shm_b = rows.get(("shm:write", "host"), 0)
    finally:
        sanitizer.reset_xfercheck()
        if not was:
            sanitizer.disable_xfercheck()
    zero_payload_on_socket = (shm_b >= 2 * payload.nbytes
                              and 0 < socket_b < payload.nbytes // 4)
    speedup = shm_fps / tcp_fps if tcp_fps else float("inf")
    return {
        "payload_bytes": int(payload.nbytes),
        "tcp_wire": tcp_wire,
        "shm_wire": shm_wire,
        "tcp_fps": round(tcp_fps, 1),
        "shm_fps": round(shm_fps, 1),
        "shm_over_tcp": round(speedup, 3),
        "xfercheck": {"socket_bytes": socket_b, "shm_write_bytes": shm_b,
                      "zero_payload_on_socket": zero_payload_on_socket},
        "ok": (shm_wire == "binary+shm" and tcp_wire == "json"
               and speedup >= 1.5 and zero_payload_on_socket),
    }


def run_wire(frames: int, seconds: float) -> dict:
    legs = {"wire_overhead": _leg_wire_overhead(frames)}
    print(f"[bench_fleet] wire_overhead: "
          f"{'ok' if legs['wire_overhead']['ok'] else 'FAILED'} "
          f"(binary {legs['wire_overhead']['binary_us_per_frame']}us vs "
          f"json {legs['wire_overhead']['json_us_per_frame']}us/frame)",
          file=sys.stderr)
    legs["shm_vs_tcp"] = _leg_shm_vs_tcp(seconds)
    print(f"[bench_fleet] shm_vs_tcp: "
          f"{'ok' if legs['shm_vs_tcp']['ok'] else 'FAILED'} "
          f"(shm {legs['shm_vs_tcp']['shm_fps']}fps vs "
          f"tcp {legs['shm_vs_tcp']['tcp_fps']}fps)", file=sys.stderr)
    return {"bench": "wire", "legs": legs,
            "ok": all(l["ok"] for l in legs.values())}


def run(traffic_s: float, settle_s: float) -> dict:
    from nnstreamer_tpu.obs import context as obs_ctx
    from nnstreamer_tpu.obs.fleet import FleetView
    from nnstreamer_tpu.service import ProcReplicaSet

    import numpy as np

    stale_after_s = max(1.0, settle_s)
    ps = ProcReplicaSet("bench-fleet", STAGE, CAPS, replicas=3,
                        trace=True, quarantine_base_s=0.2,
                        health_poll_s=0.05)
    view = FleetView("bench-fleet", source=ps, tick_s=0.25,
                     stale_after_s=stale_after_s)
    legs: dict = {}
    traffic = None
    try:
        ps.start()
        obs_ctx.enable_tracing()
        for i in range(4):  # warm every replica's serve path off the clock
            ps.request([np.ones(4, np.float32)], key=f"warm{i}",
                       timeout=30.0)
        view.start()
        traffic = _Traffic(ps).start()
        time.sleep(traffic_s)
        legs["stitch"] = _leg_stitch(ps, view)
        print(f"[bench_fleet] stitch: "
              f"{'ok' if legs['stitch']['ok'] else 'FAILED'}",
              file=sys.stderr)
        legs["merge"] = _leg_merge(ps, view)
        print(f"[bench_fleet] merge: "
              f"{'ok' if legs['merge']['ok'] else 'FAILED'}",
              file=sys.stderr)
        legs["chaos"] = _leg_chaos(ps, view, settle_s)
        traffic.stop()
        legs["chaos"]["request_errors"] = traffic.errors
        legs["chaos"]["requests_completed"] = traffic.completed
        legs["chaos"]["ok"] = legs["chaos"]["ok"] and not traffic.errors
        print(f"[bench_fleet] chaos: "
              f"{'ok' if legs['chaos']['ok'] else 'FAILED'}",
              file=sys.stderr)
    finally:
        if traffic is not None:
            traffic.stop()
        obs_ctx.disable_tracing()
        view.stop()
        ps.stop()
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith("fleet:")]
    legs["threads"] = {"leaked_fleet_threads": leaked, "ok": not leaked}
    print(f"[bench_fleet] threads: "
          f"{'ok' if not leaked else 'LEAKED ' + str(leaked)}",
          file=sys.stderr)
    return {"bench": "fleet", "replicas": 3,
            "stale_after_s": stale_after_s, "legs": legs,
            "ok": all(l["ok"] for l in legs.values())}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI: short phases, gates only")
    ap.add_argument("--out", default=None, help="also write JSON here")
    args = ap.parse_args()
    if args.smoke:
        wire = run_wire(frames=400, seconds=0.5)
        report = run(traffic_s=2.0, settle_s=1.2)
    else:
        wire = run_wire(frames=4000, seconds=3.0)
        wire_out = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "..",
            "WIRE_r18.json")
        with open(wire_out, "w") as fh:
            json.dump(wire, fh, indent=2)
        print(f"[bench_fleet] wire report -> {wire_out}", file=sys.stderr)
        report = run(traffic_s=6.0, settle_s=2.0)
        out = args.out or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "..",
            "FLEET_r13.json")
        with open(out, "w") as fh:
            json.dump(report, fh, indent=2)
        print(f"[bench_fleet] report -> {out}", file=sys.stderr)
    report["wire"] = wire
    report["ok"] = report["ok"] and wire["ok"]
    print(json.dumps(report, indent=2))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
