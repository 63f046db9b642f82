"""loadgen client-protocol modes (round 5).

``run_pool`` drives a server in all three client protocols (the
reference's --streaming/--async flag surface, main.py:59-70). These
tests pin the functional
contract of each mode against a live localhost server: requests
complete, latencies are recorded per request, and results are
numerically correct — so a protocol regression fails fast.
"""

import numpy as np
import pytest

from triton_client_tpu.channel.tpu_channel import TPUChannel
from triton_client_tpu.config import ModelSpec, TensorSpec
from triton_client_tpu.runtime.repository import ModelRepository
from triton_client_tpu.runtime.server import InferenceServer
from triton_client_tpu.utils.loadgen import run_pool


def _repo():
    spec = ModelSpec(
        name="addone",
        version="1",
        platform="jax",
        inputs=(TensorSpec("x", (-1, 4), "FP32"),),
        outputs=(TensorSpec("y", (-1, 4), "FP32"),),
        max_batch_size=8,
    )
    repo = ModelRepository()
    repo.register(spec, lambda inputs: {"y": np.asarray(inputs["x"]) + 1.0})
    return repo


@pytest.fixture()
def server():
    repo = _repo()
    server = InferenceServer(
        repo, TPUChannel(repo), address="127.0.0.1:0", max_workers=8
    )
    server.start()
    yield server
    server.stop()


X = np.ones((1, 4), np.float32)


@pytest.mark.parametrize(
    "mode,inflight",
    [("unary", 1), ("stream", 1), ("stream", 4), ("async", 2)],
)
def test_mode_serves_and_records_latencies(server, mode, inflight):
    res = run_pool(
        f"127.0.0.1:{server.port}",
        "addone",
        {"x": X},
        clients=2,
        duration_s=1.5,
        deadline_s=30.0,
        stagger_s=0.0,
        mode=mode,
        inflight=inflight,
    )
    assert not res.errors, res.errors[:2]
    assert res.served_frames > 0
    # roughly one latency sample per served request — requests in
    # flight when the window closes drain with a recorded latency but
    # fall outside the served count (fps stays completions-in-window),
    # so allow a pipeline depth's worth of extras per client
    assert (
        res.served_frames
        <= len(res.latencies_ms)
        <= res.served_frames + 2 * (inflight + 2)
    )
    assert min(res.latencies_ms) > 0


def test_unknown_mode_rejected():
    # mode validation fires before any connection: no server needed
    with pytest.raises(ValueError):
        run_pool(
            "127.0.0.1:1",
            "addone",
            {"x": X},
            clients=1,
            duration_s=0.2,
            mode="carrier-pigeon",
        )
