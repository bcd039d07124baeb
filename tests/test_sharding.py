"""Sharding rules, divisibility guards, collective-byte parsing, and a
small-mesh SPMD integration test (8 fake devices, no dry-run needed)."""

import numpy as np
import pytest

# 8 fake CPU devices for this module ONLY: tests run in a subprocess via
# pytest-forked? No — we spawn a subprocess manually for the mesh test and
# keep everything else single-device.
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch.dryrun import collective_bytes
from repro.runtime.sharding import (AxisRules, _divisible_spec,
                                    single_pod_rules, multi_pod_rules)


class TestAxisRules:
    def test_spec_mapping(self):
        rules = single_pod_rules()
        assert rules.spec(("act_batch", None, None)) == P("data")
        assert rules.spec(("embed", "heads", "head_dim")) == \
            P("data", "model")
        assert rules.spec(("unsharded",)) == P()

    def test_multi_pod_batch(self):
        rules = multi_pod_rules()
        assert rules.spec(("act_batch", None)) == P(("pod", "data"))

    def test_overrides(self):
        rules = single_pod_rules().with_overrides(act_seq=None)
        assert rules.spec(("act_batch", "act_seq", None)) == P("data")


class TestDivisibleSpec:
    def _mesh(self):
        dev = np.array(jax.devices()[:1]).reshape(1, 1)
        return Mesh(dev, ("data", "model"))

    def test_drops_indivisible(self):
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        sizes_mesh = mesh
        # fake a 4x2 mesh via axis size lookup by constructing spec directly
        # (mesh of size 1 divides everything -> keep)
        spec = _divisible_spec(mesh, P("data", "model"), (3, 5))
        assert spec == P("data", "model")

    def test_duplicate_axis_dropped(self):
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        spec = _divisible_spec(mesh, P("model", "model"), (4, 4))
        assert spec == P("model")


class TestCollectiveParser:
    HLO = """
  %all-reduce.1 = bf16[16,512,128]{2,1,0} all-reduce(bf16[16,512,128]{2,1,0} %x), replica_groups={{0,1}}
  %ag = f32[1024,256]{1,0} all-gather(f32[512,256]{1,0} %y), dimensions={0}
  %rs.7 = f32[64]{0} reduce-scatter(f32[128]{0} %z), dimensions={0}
  %a2a = bf16[8,64]{1,0} all-to-all(bf16[8,64]{1,0} %w), dimensions={0}
  %cp = u32[4]{0} collective-permute(u32[4]{0} %v), source_target_pairs={{0,1}}
  %notacoll = f32[9]{0} add(f32[9]{0} %a, f32[9]{0} %b)
"""

    def test_counts_each_kind(self):
        out = collective_bytes(self.HLO)
        assert out["all-reduce"] == 16 * 512 * 128 * 2
        assert out["all-gather"] == 1024 * 256 * 4
        assert out["reduce-scatter"] == 64 * 4
        assert out["all-to-all"] == 8 * 64 * 2
        assert out["collective-permute"] == 4 * 4
        assert out["total"] == sum(out[k] for k in
                                   ("all-reduce", "all-gather",
                                    "reduce-scatter", "all-to-all",
                                    "collective-permute"))

    def test_start_done_not_double_counted(self):
        hlo = """
  %ar0 = bf16[128]{0} all-reduce-start(bf16[128]{0} %x)
  %ar1 = bf16[128]{0} all-reduce-done(bf16[128]{0} %ar0)
"""
        out = collective_bytes(hlo)
        assert out["all-reduce"] == 128 * 2


SPMD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import jax, jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step, input_specs, shardings_for, rules_for
from repro.models import build_model, get_config
from repro.optim import AdamWConfig, init_state
from repro.configs.base import ShapeConfig
from repro.runtime.sharding import use_rules, single_pod_rules

# tiny config, 2x4 mesh: numerics of the sharded train step must match
# the single-device step
cfg = get_config("granite-8b").replace(
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
    vocab=256, remat=False, q_chunk=32, loss_seq_chunk=None)
model = build_model(cfg)
shape = ShapeConfig("t", seq_len=32, global_batch=8, kind="train")
mesh = make_mesh((2, 4), ("data", "model"))
rules = single_pod_rules()

params = model.init(jax.random.PRNGKey(0))
opt = init_state(params)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}

from repro.launch.steps import make_train_step
step_plain = jax.jit(make_train_step(model, AdamWConfig(), None, None))
_,_, m0 = step_plain(params, opt, batch)

specs = input_specs(cfg, shape)
sh = shardings_for(cfg, shape, mesh, rules, specs)
with mesh:
    step_spmd = jax.jit(make_train_step(model, AdamWConfig(), rules, mesh),
                        in_shardings=(sh["params"], sh["opt_state"], sh["batch"]))
    _,_, m1 = step_spmd(params, opt, batch)

l0, l1 = float(m0["loss"]), float(m1["loss"])
assert abs(l0 - l1) / abs(l0) < 2e-2, (l0, l1)
print("SPMD_OK", l0, l1)
"""


def test_spmd_matches_single_device(tmp_path):
    """Run the 8-device SPMD parity check in a subprocess (device count must
    be set before jax init)."""
    import os
    import pathlib
    import subprocess
    import sys
    repo = pathlib.Path(__file__).resolve().parents[1]
    p = subprocess.run(
        [sys.executable, "-c", SPMD_SCRIPT], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": str(repo / "src")},
        cwd=repo, timeout=600)
    assert "SPMD_OK" in p.stdout, p.stdout + p.stderr
