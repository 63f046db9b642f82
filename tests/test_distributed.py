"""Multi-host runtime (parallel/distributed.py).

Real multi-process clusters can't run inside one pytest process; these
tests cover what can be validated single-process: spec parsing, the
host-major device ordering, the global-mesh axis-placement policy, the
process-local batch feed (single-process path of
make_array_from_process_local_data), and the train CLI wiring.
"""

import numpy as np
import pytest

from triton_client_tpu.parallel.distributed import (
    DistributedConfig,
    global_mesh,
    host_major_devices,
    init_distributed,
    is_coordinator,
    shard_host_batch,
)
from triton_client_tpu.parallel.mesh import MeshConfig


class TestConfigParsing:
    def test_explicit_spec(self):
        cfg = DistributedConfig.from_spec("host0:9876,4,2")
        assert cfg == DistributedConfig("host0:9876", 4, 2)

    def test_env_spec(self, monkeypatch):
        monkeypatch.setenv("COORDINATOR", "c:1")
        monkeypatch.setenv("NPROC", "8")
        monkeypatch.setenv("PROC_ID", "3")
        cfg = DistributedConfig.from_spec("env")
        assert cfg == DistributedConfig("c:1", 8, 3)

    def test_env_alias(self, monkeypatch):
        monkeypatch.delenv("COORDINATOR", raising=False)
        monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "c:2")
        monkeypatch.setenv("NPROC", "2")
        monkeypatch.setenv("PROC_ID", "0")
        assert DistributedConfig.from_spec("env").coordinator == "c:2"

    def test_env_missing(self, monkeypatch):
        for k in ("COORDINATOR", "JAX_COORDINATOR_ADDRESS"):
            monkeypatch.delenv(k, raising=False)
        with pytest.raises(ValueError, match="COORDINATOR"):
            DistributedConfig.from_spec("env")

    def test_bad_spec(self):
        with pytest.raises(ValueError, match="host:port"):
            DistributedConfig.from_spec("host0:9876,4")

    def test_bad_process_id(self):
        with pytest.raises(ValueError, match="outside"):
            DistributedConfig.from_spec("c:1,4,4")


class _FakeDevice:
    def __init__(self, process_index, dev_id):
        self.process_index = process_index
        self.id = dev_id

    def __repr__(self):
        return f"dev(p{self.process_index}, {self.id})"


class TestHostMajorOrdering:
    def test_sorts_by_process_then_id(self):
        devs = [
            _FakeDevice(1, 5), _FakeDevice(0, 2),
            _FakeDevice(1, 4), _FakeDevice(0, 3),
        ]
        ordered = host_major_devices(devs)
        assert [(d.process_index, d.id) for d in ordered] == [
            (0, 2), (0, 3), (1, 4), (1, 5),
        ]


class TestSingleProcessPaths:
    def test_init_noop_single_process(self):
        # num_processes=1 must not try to dial a coordinator
        init_distributed(DistributedConfig("nowhere:1", 1, 0))

    def test_is_coordinator_single_process(self):
        assert is_coordinator()

    def test_global_mesh_axes(self):
        mesh = global_mesh(MeshConfig(data=4, model=2))
        assert mesh.shape["data"] == 4
        assert mesh.shape["model"] == 2

    def test_shard_host_batch_roundtrip(self):
        mesh = global_mesh(MeshConfig(data=8))
        local = np.arange(8 * 3, dtype=np.float32).reshape(8, 3)
        arr = shard_host_batch(local, mesh)
        assert arr.shape == (8, 3)
        np.testing.assert_array_equal(np.asarray(arr), local)
        # sharded over the data axis
        assert len(arr.sharding.device_set) == 8


class TestMultiHostGuards:
    def test_explicit_mesh_must_cover_all_devices(self, monkeypatch):
        # under >1 processes, a device-prefix mesh would strand hosts —
        # global_mesh must refuse rather than truncate
        import triton_client_tpu.parallel.distributed as dist

        monkeypatch.setattr(dist.jax, "process_count", lambda: 2)
        with pytest.raises(ValueError, match="all 8 global devices"):
            global_mesh(MeshConfig(data=4))

    def test_init_does_not_touch_backend_before_initialize(self):
        # the idempotency probe must not call process_count()/devices()
        # (they'd initialize XLA and make jax.distributed.initialize
        # unusable); _client_already_up is the only allowed probe
        import inspect

        import triton_client_tpu.parallel.distributed as dist

        src = inspect.getsource(dist.init_distributed)
        # anchor on the CALL (with paren) so the docstring's mention of
        # initialize doesn't truncate the checked prefix
        assert "process_count()" not in src.split("jax.distributed.initialize(")[0]


class TestTrainCLIWiring:
    def test_bad_distributed_spec_exits(self):
        from triton_client_tpu.cli.train import main

        with pytest.raises(SystemExit, match="host:port"):
            main(["--distributed", "nope", "--steps", "1"])

    def test_single_process_distributed_env(self, monkeypatch, tmp_path, capsys):
        # 'env' spec with NPROC=1: init is a no-op, training runs
        monkeypatch.setenv("COORDINATOR", "localhost:1")
        monkeypatch.setenv("NPROC", "1")
        monkeypatch.setenv("PROC_ID", "0")
        from triton_client_tpu.cli.train import main

        main(
            [
                "--distributed", "env",
                "-i", "synthetic:8",
                "--steps", "2",
                "-b", "8",
                "--input-size", "64",
                "--log-every", "1",
            ]
        )
        assert "step 2/2" in capsys.readouterr().out


_CHILD_SRC = '''
"""Two-process jax.distributed child: joins the cluster through the
framework's own entry points and proves the host-major mesh layout and
a real cross-host psum (the DCN/ICI axis-placement claim of
parallel/mesh.py:15-18, executed rather than narrated)."""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax

jax.config.update("jax_platforms", "cpu")

from triton_client_tpu.parallel.distributed import (
    DistributedConfig,
    global_mesh,
    init_distributed,
    is_coordinator,
    shard_host_batch,
)
from triton_client_tpu.parallel.mesh import MeshConfig

init_distributed(DistributedConfig.from_spec("env"))
pid = jax.process_index()
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 4, jax.device_count()
assert is_coordinator() == (pid == 0)

import numpy as np
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

mesh = global_mesh(MeshConfig(data=4, model=1))
# host-major: the data axis walks process 0's devices first, then
# process 1's — so a (model/seq/pipe)-group never straddles hosts
# when it fits in one
flat = mesh.devices.reshape(-1)
assert [d.process_index for d in flat] == [0, 0, 1, 1], [
    d.process_index for d in flat
]

# per-host feed -> one global array (no host gathering)
local = np.full((2, 4), pid + 1.0, np.float32)
garr = shard_host_batch(local, mesh)
assert garr.shape == (4, 4)

# cross-host collective: psum over the data axis spans both processes
psum = shard_map(
    lambda x: jax.lax.psum(jnp.sum(x), "data"),
    mesh=mesh,
    in_specs=P("data"),
    out_specs=P(),
)
total = float(jax.jit(psum)(garr))
assert total == 2 * 4 * 1.0 + 2 * 4 * 2.0, total  # both hosts contributed
print(f"CHILD {pid} OK total={total}")
'''


def test_two_process_cluster_host_major_mesh_and_cross_host_psum(tmp_path):
    """Launch TWO real jax.distributed processes on localhost CPU and
    assert the host-major mesh layout plus a cross-host psum through
    the framework's own init/mesh/feed entry points."""
    import os
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    child = tmp_path / "dist_child.py"
    child.write_text(_CHILD_SRC)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update(
            COORDINATOR=f"127.0.0.1:{port}",
            NPROC="2",
            PROC_ID=str(pid),
            PYTHONPATH=repo_root,
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, str(child)],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    for pid, proc in enumerate(procs):
        try:
            out, _ = proc.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            raise
        outs.append(out)
        if "Multiprocess computations aren't implemented" in out:
            # this jaxlib's CPU backend has no cross-process
            # collectives — the mesh/init/feed plumbing above still
            # ran; only the psum itself is unsupported here
            pytest.skip("CPU backend lacks multiprocess collectives")
        assert proc.returncode == 0, f"process {pid} failed:\n{out}"
    for pid, out in enumerate(outs):
        assert f"CHILD {pid} OK" in out, out
