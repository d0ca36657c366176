"""Among-device offload with failure recovery.

A client pipeline round-trips every frame to a worker pipeline over the
tensor-query protocol; the worker is killed and restarted mid-stream and
the client reconnects with backoff (frames during the outage are dropped,
the stream never dies).

    python examples/offload_with_reconnect.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from nnstreamer_tpu.runtime.parse import parse_launch  # noqa: E402


def start_worker(port: int, server_id: int, factor: float):
    pipe = parse_launch(
        f"tensor_query_serversrc name=src id={server_id} port={port} "
        "caps=other/tensors,format=static,dimensions=4,types=float32 "
        f"! tensor_filter framework=jax model=builtin://scaler?factor={factor} "
        f"! tensor_query_serversink id={server_id}")
    pipe.play()
    deadline = time.monotonic() + 5
    while pipe.get("src").bound_port == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    return pipe, pipe.get("src").bound_port


def main() -> None:
    worker, port = start_worker(0, server_id=100, factor=2.0)
    client = parse_launch(
        "appsrc name=in caps=other/tensors,format=static,dimensions=4,types=float32 "
        f"! tensor_query_client host=127.0.0.1 port={port} "
        "reconnect-window=20 max-reconnect-delay=0.5 "
        "! tensor_sink name=out")
    out = []
    client.get("out").connect(
        lambda b: out.append(float(np.asarray(b.tensors[0])[0])))
    client.play()
    src = client.get("in")

    deadline = time.monotonic() + 20
    while len(out) < 5 and time.monotonic() < deadline:
        src.push_buffer(np.ones(4, np.float32))
        time.sleep(0.03)
    if len(out) < 5:
        raise SystemExit("worker never answered — check the logs above")
    print(f"worker x2 answered {len(out)} frames: {out[-3:]}")

    print("killing worker ...")
    worker.stop()
    time.sleep(0.5)
    worker, _ = start_worker(port, server_id=101, factor=5.0)
    print("worker restarted (now x5); streaming continues:")

    n = len(out)
    deadline = time.monotonic() + 20
    while len(out) < n + 5 and time.monotonic() < deadline:
        src.push_buffer(np.ones(4, np.float32))
        time.sleep(0.03)
    print(f"answers after restart: {out[-3:]} (values switched 2.0 → 5.0)")
    client.stop()
    worker.stop()


if __name__ == "__main__":
    main()
