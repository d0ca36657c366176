"""MQTT-hybrid offload: broker discovery, direct-TCP data, elastic moves.

The reference's ``connect-type=HYBRID`` (nnstreamer-edge MQTT-hybrid):
an MQTT broker carries only a retained ``topic → host:port``
advertisement; tensor data flows over a direct TCP link. Because the
client re-discovers on every reconnect, a worker that comes back on a
DIFFERENT port is found automatically — this demo kills the worker,
restarts it on a fresh ephemeral port, and the stream resumes.

    python examples/hybrid_discovery.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from nnstreamer_tpu.query.mqtt import MiniBroker  # noqa: E402
from nnstreamer_tpu.runtime.parse import parse_launch  # noqa: E402

CAPS = "other/tensors,format=static,dimensions=4,types=float32"


def start_worker(broker, server_id, factor):
    pipe = parse_launch(
        f"tensor_query_serversrc name=src id={server_id} port=0 "
        f"connect-type=HYBRID dest-host={broker.host} dest-port={broker.port} "
        f"topic=demo caps={CAPS} "
        f"! tensor_filter framework=jax model=builtin://scaler?factor={factor} "
        f"! tensor_query_serversink id={server_id}")
    pipe.play()
    deadline = time.monotonic() + 10
    while pipe.get("src").bound_port == 0:
        if time.monotonic() > deadline:
            raise RuntimeError("worker never bound a port (see bus errors)")
        time.sleep(0.01)
    print(f"worker up on port {pipe.get('src').bound_port} "
          f"(advertised on the broker under 'demo')")
    return pipe


def main():
    broker = MiniBroker()
    print(f"MQTT broker (control plane only) on {broker.host}:{broker.port}")
    worker = start_worker(broker, server_id=1, factor=10.0)

    client = parse_launch(
        f"appsrc name=in caps={CAPS} "
        f"! tensor_query_client connect-type=HYBRID host={broker.host} "
        f"port={broker.port} topic=demo reconnect-window=20 "
        "! tensor_sink name=out max-stored=0")
    got = []
    client.get("out").connect(got.append)
    client.play()
    src = client.get("in")

    src.push_buffer(np.full(4, 1.0, np.float32))
    deadline = time.monotonic() + 15
    while len(got) < 1:
        if time.monotonic() > deadline:
            raise RuntimeError("no answer from the discovered worker")
        time.sleep(0.02)
    print(f"answer via discovered worker: {np.asarray(got[0].tensors[0])[0]}")

    print("killing the worker; restarting it on a NEW ephemeral port ...")
    worker.stop()
    worker = start_worker(broker, server_id=2, factor=10.0)

    deadline = time.monotonic() + 20
    while len(got) < 2 and time.monotonic() < deadline:
        src.push_buffer(np.full(4, 7.0, np.float32))
        time.sleep(0.3)
    assert len(got) >= 2, "client never re-discovered the moved worker"
    print(f"answer after the move: {np.asarray(got[-1].tensors[0])[0]} "
          "(client re-ran discovery on reconnect)")

    client.stop()
    worker.stop()
    broker.stop()
    print("OK")


if __name__ == "__main__":
    main()
