"""Distribution smoke: lower+compile reduced archs on a multi-device mesh.

The 512-device production dry-run is exercised via ``repro.launch.dryrun``
(results in results/dryrun/).  Here we prove the same machinery — policies,
shardings, constraints — works in-process on an 8-device host mesh, for one
representative arch per family.  Runs in a subprocess because
``xla_force_host_platform_device_count`` must be set before jax init.
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
from repro.configs import ARCHS
from repro.configs.base import ShapeConfig
from repro.runtime.sharding import ShardingPolicy, make_policy
from repro.runtime.train_loop import TrainRuntime, shard_train_step
from repro.runtime.serve_loop import shard_decode_step

arch_id = sys_argv_arch
mesh = jax.make_mesh(
    (2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2
)
cfg = ARCHS[arch_id].reduced()
out = {}

shape = ShapeConfig("t", seq_len=64, global_batch=8, kind="train")
policy = make_policy(mesh)
with mesh:
    fn, abstract = shard_train_step(cfg, shape, policy, TrainRuntime())
    compiled = fn.lower(*abstract).compile()
    from repro.launch.hlo_cost import xla_cost_analysis
    out["train_flops"] = xla_cost_analysis(compiled).get("flops", 0.0)

shape = ShapeConfig("d", seq_len=64, global_batch=8, kind="decode")
with mesh:
    fn, abstract = shard_decode_step(cfg, shape, policy)
    compiled = fn.lower(*abstract).compile()
    out["decode_ok"] = True

# pure-DP policy as well
shape = ShapeConfig("t2", seq_len=64, global_batch=8, kind="train")
policy = make_policy(mesh, pure_dp=True)
with mesh:
    fn, abstract = shard_train_step(cfg, shape, policy, TrainRuntime())
    fn.lower(*abstract).compile()
    out["pure_dp_ok"] = True
print("RESULT:" + json.dumps(out))
"""


@pytest.mark.parametrize(
    "arch_id",
    ["qwen2-0.5b", "mixtral-8x22b", "mamba2-1.3b", "zamba2-1.2b", "whisper-large-v3"],
)
def test_multidevice_lower_compile(arch_id):
    code = f"sys_argv_arch = {arch_id!r}\n" + SCRIPT
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        timeout=540,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stdout
    out = json.loads(line[0][len("RESULT:"):])
    assert out.get("decode_ok") and out.get("pure_dp_ok")
