"""Distributed-infrastructure paths: directory server RPC, elastic
restart across device counts, straggler hedging, compressed reduction."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.core.directory import (
    DirectoryClient,
    DirectoryServer,
    Endpoint,
)


def test_directory_server_rpc_roundtrip():
    """The out-of-process worker directory (multi-host deployments)."""
    server = DirectoryServer().start()
    try:
        client = DirectoryClient(server.host, server.port)
        got = {}

        def exporter():
            got["ep"] = client.query("ds", "q1", timeout=10)

        t = threading.Thread(target=exporter)
        t.start()
        time.sleep(0.05)
        client.register("ds", Endpoint("127.0.0.1", 12345), "q1")
        t.join(10)
        assert got["ep"].host == "127.0.0.1" and got["ep"].port == 12345
    finally:
        server.stop()


def test_directory_server_timeout():
    server = DirectoryServer().start()
    try:
        client = DirectoryClient(server.host, server.port)
        with pytest.raises((TimeoutError, IOError)):
            client.query("nobody", "q", timeout=0.3)
    finally:
        server.stop()


def test_feeder_abandons_stalled_source():
    """Straggler mitigation: a source that never delivers is abandoned and
    the stream still terminates."""
    from repro.core.datapipe import DataPipeOutput, PipeConfig
    from repro.pipeline import PipeFeeder, SyntheticSource

    names = ["db://fast?query=s", "db://stall?query=s"]
    feeder = PipeFeeder(names, batch_size=2, seq_len=4,
                        hedge_timeout=0.5).start()

    def fast():
        SyntheticSource(32, 4, seed=0).serve(names[0], 6)

    def stall():
        # register + connect, send schema, then hang past the hedge window
        out = DataPipeOutput(names[1], config=PipeConfig())
        time.sleep(1.2)
        out.close()

    t1 = threading.Thread(target=fast, daemon=True)
    t2 = threading.Thread(target=stall, daemon=True)
    t1.start(); t2.start()
    batches = list(feeder.batches())
    assert sum(b.data["tokens"].shape[0] for b in batches) >= 6


ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.launch.mesh import make_production_mesh
from repro.models import build_model, get_config
from repro.train import CheckpointManager, TrainState, adamw_init
from repro.train.step import train_state_specs
from repro.distrib.sharding import named_sharding

cfg = get_config("smollm-360m").reduced()
model = build_model(cfg)
ckpt = sys.argv[1]
phase = sys.argv[2]
mesh_shape = (4, 2) if phase == "save" else (2, 4)   # elastic re-mesh
mesh = jax.make_mesh(mesh_shape, ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
params = model.init(jax.random.PRNGKey(0))
state = TrainState(params, adamw_init(params))
specs = train_state_specs(state, mesh, cfg)
shardings = named_sharding(mesh, specs)
state = jax.device_put(state, shardings)   # sharded on this mesh
mgr = CheckpointManager(ckpt)
if phase == "save":
    mgr.save(11, state)
    print("SAVED", 11)
else:
    restored, step = mgr.restore(jax.eval_shape(lambda: state))
    restored = jax.device_put(restored, shardings)  # reshard on new mesh
    a = jax.tree_util.tree_leaves(state.params)[0]
    b = jax.tree_util.tree_leaves(restored.params)[0]
    import numpy as np
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("RESTORED", step)
"""


@pytest.mark.slow
def test_elastic_checkpoint_across_mesh_shapes(tmp_path):
    """Save on a (4,2) mesh, restore + reshard on a (2,4) mesh (elastic
    re-mesh after a device-count change)."""
    script = tmp_path / "elastic.py"
    script.write_text(ELASTIC_SCRIPT)
    env = dict(os.environ, PYTHONPATH="src")
    ckpt = str(tmp_path / "ck")
    r1 = subprocess.run([sys.executable, str(script), ckpt, "save"],
                        capture_output=True, text=True, env=env, timeout=300)
    assert "SAVED 11" in r1.stdout, r1.stderr[-1500:]
    r2 = subprocess.run([sys.executable, str(script), ckpt, "restore"],
                        capture_output=True, text=True, env=env, timeout=300)
    assert "RESTORED 11" in r2.stdout, r2.stderr[-1500:]


def test_compressed_psum_matches_fullprec_within_tolerance():
    """q8 cross-pod gradient compression: sum of dequantized shards must
    track the exact sum within blockwise-quantization error."""
    import jax
    import jax.numpy as jnp

    from repro.distrib.compress import dequantize_q8, quantize_q8

    rng = jax.random.PRNGKey(0)
    shards = [jax.random.normal(jax.random.fold_in(rng, i), (2048,))
              for i in range(4)]
    exact = sum(np.asarray(s) for s in shards)
    approx = np.zeros_like(exact)
    max_scale = 0.0
    for s in shards:
        q, scale = quantize_q8(s)
        approx += np.asarray(dequantize_q8(q, scale, s.shape, jnp.float32))
        max_scale = max(max_scale, float(scale.max()))
    err = np.abs(exact - approx).max()
    assert err <= 4 * (max_scale * 0.5 + 1e-6)
